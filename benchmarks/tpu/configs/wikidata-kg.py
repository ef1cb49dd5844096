"""Data of the ``wikidata-kg`` configuration: a Wikidata-shaped graph.

A copy of the repository's ``scale_free_graph`` generator (node
popularity falls as rank^-0.8, predicate use is Zipf), kept here so
that a change to the program's fixtures cannot move the benchmark.
Node ids are popularity ranks, so the ``node`` anchor domain weighs
node ``i`` by ``(i + 1) ** -0.8``.
"""
import numpy as np


def build(cfg):
    g = cfg["graph"]
    V, P, E = int(g["nodes"]), int(g["predicates"]), int(g["triples"])
    rng = np.random.default_rng(int(g["seed"]))
    ranks = np.arange(1, V + 1, dtype=np.float64)
    wn = 1.0 / ranks ** float(g["node_popularity_exponent"])
    wn /= wn.sum()
    s = rng.choice(V, size=E, p=wn)
    o = rng.choice(V, size=E, p=wn)
    wp = 1.0 / np.arange(1, P + 1)
    wp /= wp.sum()
    p = rng.choice(P, size=E, p=wp)
    return {"s": s.astype(np.int64), "p": p.astype(np.int64),
            "o": o.astype(np.int64), "num_nodes": V, "num_preds": P,
            "pred_names": None,
            "domains": {"node": (np.arange(V, dtype=np.int64), wn)}}

