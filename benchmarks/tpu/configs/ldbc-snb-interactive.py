"""Data of the ``ldbc-snb-interactive`` configuration: a social network
shaped like the LDBC SNB Interactive data set, with its update stream.

Every entity (static dictionaries, persons, forums, posts, comments) is
given a node id up front, because the served overlay's node dictionary
is fixed between rebuilds.  Every edge is given a creation time after
the creation of both its ends.  Simulated time advances with the edges
created, and, as in SNB, the first ``stream_from`` (0.9) of it is the
bulk load and the rest is the update stream, grouped into the insert
operations IU1-IU8 that create the edges:

    IU1 person  isLocatedIn city, hasInterest tags, studyAt, workAt
    IU2 like of a post, IU3 like of a comment   (person likes message)
    IU4 forum   hasModerator person, hasTag tags
    IU5 forum   hasMember person
    IU6 post    hasCreator, containerOf (from its forum), isLocatedIn
                country, hasTag tags
    IU7 comment hasCreator, replyOf, isLocatedIn country, hasTag tags
    IU8 friendship: knows in both directions

Counts and laws are the configuration's (``graph``), each scaled by
``nodes`` over the sum of the entity counts, so a test can cut the same
shapes to a few thousand nodes.  Raw edges are distinct.

``build`` returns the bulk graph as arrays, the anchor domains
(``person`` and ``message``: those of the bulk load; ``tag`` and
``tagclass``: all), and
``stream``: the update operations in stream order, each a run of edges
(``s``, ``p``, ``o``) between ``off[i]`` and ``off[i + 1]``, with its
``kind`` (1-8 for IU1-IU8) and its time ``t``.
"""
import numpy as np

PREDICATES = ["knows", "hasCreator", "replyOf", "containerOf", "hasMember",
              "likes", "hasTag", "hasType", "isSubclassOf", "isLocatedIn",
              "hasInterest", "studyAt", "workAt", "isPartOf",
              "hasModerator"]
P = {name: i for i, name in enumerate(PREDICATES)}
STATIC = 0.0


def _zipf(rng, n, size, exponent=1.0):
    """``size`` draws over ``range(n)`` with weight (rank + 1)^-exponent."""
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent
    c = np.cumsum(w / w.sum())
    return np.searchsorted(c, rng.random(size), side="right").clip(0, n - 1)


def _later(rng, t, power=1.0):
    """A time after ``t`` and before 1: soon after when ``power`` > 1."""
    return t + (1.0 - t) * rng.random(t.shape) ** power


def _counts(g):
    scale = float(g["nodes"]) / sum(g["entities"].values())
    return {k: max(2, int(round(v * scale)))
            for k, v in g["entities"].items()}


def build(cfg):
    g = cfg["graph"]
    rng = np.random.default_rng(int(g["seed"]))
    n = _counts(g)
    # -- node ids, allocated up front ------------------------------------
    base = {}
    nxt = 0
    for kind in ("tagclass", "tag", "continent", "country", "city",
                 "university", "company", "person", "forum", "post",
                 "comment"):
        base[kind] = nxt
        nxt += n[kind]
    V = nxt
    ids = {k: base[k] + np.arange(n[k], dtype=np.int64) for k in base}
    edges = []          # (s, p, o, t, op) with op = global operation key
    ops_kind = []       # per operation kind offset table
    op_base = {}
    nop = 0

    def ops(kind_code, count):
        nonlocal nop
        op_base[kind_code] = nop
        ops_kind.append((nop, count, kind_code))
        nop += count
        return op_base[kind_code] + np.arange(count, dtype=np.int64)

    def add(s, pred, o, t, op):
        s = np.asarray(s, dtype=np.int64)
        o = np.asarray(o, dtype=np.int64)
        t = np.broadcast_to(np.asarray(t, dtype=np.float64), s.shape)
        op = np.broadcast_to(np.asarray(op, dtype=np.int64), s.shape)
        edges.append((s, np.full(s.shape, P[pred], np.int64), o, t, op))

    # -- static dictionaries: tag classes, tags, places, organisations ---
    nt = n["tagclass"]
    parent = np.array([rng.integers(0, k) for k in range(1, nt)],
                      dtype=np.int64)
    add(ids["tagclass"][1:], "isSubclassOf", ids["tagclass"][parent],
        STATIC, -1)
    add(ids["tag"], "hasType",
        ids["tagclass"][_zipf(rng, nt, n["tag"], 0.5)], STATIC, -1)
    city_country = _zipf(rng, n["country"], n["city"], 1.0)
    add(ids["city"], "isPartOf", ids["country"][city_country], STATIC, -1)
    add(ids["country"], "isPartOf",
        ids["continent"][rng.integers(0, n["continent"], n["country"])],
        STATIC, -1)
    add(ids["university"], "isLocatedIn",
        ids["city"][_zipf(rng, n["city"], n["university"], 1.0)], STATIC, -1)
    add(ids["company"], "isLocatedIn",
        ids["country"][_zipf(rng, n["country"], n["company"], 1.0)],
        STATIC, -1)

    def tags(count, mean):
        """(owner index, tag id) pairs: ``mean`` Zipf tags per owner."""
        k = rng.poisson(mean, count)
        own = np.repeat(np.arange(count), k)
        return own, ids["tag"][_zipf(rng, n["tag"], own.size,
                                     float(g["tag_zipf"]))]

    # -- persons (IU1) ----------------------------------------------------
    npn = n["person"]
    tp = np.sort(rng.random(npn))
    pop = ids["person"]
    op1 = ops(1, npn)
    pcity = _zipf(rng, n["city"], npn, 1.0)
    add(pop, "isLocatedIn", ids["city"][pcity], tp, op1)
    own, tg = tags(npn, float(g["interests_per_person"]))
    add(pop[own], "hasInterest", tg, tp[own], op1[own])
    st = np.nonzero(rng.random(npn) < float(g["study_share"]))[0]
    add(pop[st], "studyAt",
        ids["university"][_zipf(rng, n["university"], st.size, 1.0)],
        tp[st], op1[st])
    k = rng.poisson(float(g["jobs_per_person"]), npn)
    own = np.repeat(np.arange(npn), k)
    add(pop[own], "workAt",
        ids["company"][_zipf(rng, n["company"], own.size, 1.0)],
        tp[own], op1[own])
    pcountry = city_country[pcity]

    # -- knows (IU8): degrees from a power law, partners near in a
    #    similarity order, a share of them anywhere --------------------
    alpha = float(g["knows_degree_alpha"])
    x = rng.random(npn) ** (-1.0 / (alpha - 1.0)) * (alpha - 2.0) / \
        (alpha - 1.0)
    deg = np.clip(np.round(float(g["knows_mean_degree"]) * x / 2), 1,
                  npn - 1).astype(np.int64)
    rank = rng.permutation(npn)              # position in similarity order
    by_rank = np.argsort(rank)
    a = np.repeat(np.arange(npn), deg)
    step = rng.geometric(1.0 / float(g["knows_window"]), a.size) * \
        rng.choice(np.array([-1, 1]), a.size)
    b = by_rank[(rank[a] + step) % npn]
    far = rng.random(a.size) < float(g["knows_random_share"])
    b[far] = rng.integers(0, npn, int(far.sum()))
    keep = a != b
    lo, hi = np.minimum(a[keep], b[keep]), np.maximum(a[keep], b[keep])
    pair = np.unique(lo * npn + hi)
    ka, kb = pair // npn, pair % npn
    tk = _later(rng, np.maximum(tp[ka], tp[kb]), 3.0)
    op8 = ops(8, pair.size)
    add(pop[ka], "knows", pop[kb], tk, op8)
    add(pop[kb], "knows", pop[ka], tk, op8)
    # friends by person (CSR), for creators and likers near the author
    fa = np.concatenate([ka, kb])
    fb = np.concatenate([kb, ka])
    order = np.argsort(fa, kind="stable")
    friends = fb[order]
    foff = np.searchsorted(fa[order], np.arange(npn + 1))
    fdeg = np.diff(foff)

    def friend_or_any(who, share):
        """A friend of each person in ``who`` with probability ``share``
        (when it has one), else a person drawn by degree."""
        out = rng.choice(npn, who.size, p=(fdeg + 1) / (fdeg + 1).sum())
        use = (rng.random(who.size) < share) & (fdeg[who] > 0)
        pick = foff[who[use]] + (rng.random(int(use.sum()))
                                 * fdeg[who[use]]).astype(np.int64)
        out[use] = friends[pick]
        return out

    # -- forums (IU4): a wall per person, then groups ---------------------
    nf = n["forum"]
    ngr = max(1, nf - npn)
    walls = np.arange(npn)
    gmod = rng.choice(npn, ngr, p=(fdeg + 1) / (fdeg + 1).sum())
    fmod = np.concatenate([walls, gmod])[:nf]
    tf = np.concatenate([tp, _later(rng, tp[gmod], 1.0)])[:nf]
    fid = ids["forum"]
    op4 = ops(4, nf)
    add(fid, "hasModerator", pop[fmod], tf, op4)
    own, tg = tags(nf, float(g["tags_per_forum"]))
    add(fid[own], "hasTag", tg, tf[own], op4[own])
    # members (IU5): a wall's are its owner's friends, a group's drawn by
    # degree; each joins after both exist
    wf = np.concatenate([ka, kb])
    wp = np.concatenate([kb, ka])
    wt = np.concatenate([tk, tk])
    gsize = rng.geometric(1.0 / float(g["group_members_mean"]), nf - npn) \
        if nf > npn else np.zeros(0, np.int64)
    gf = npn + np.repeat(np.arange(nf - npn), gsize)
    gp = rng.choice(npn, gf.size, p=(fdeg + 1) / (fdeg + 1).sum())
    gt = _later(rng, np.maximum(tf[gf], tp[gp]), 2.0)
    mf = np.concatenate([wf[wf < nf], gf])
    mp = np.concatenate([wp[wf < nf], gp])
    mt = np.concatenate([wt[wf < nf], gt])
    key, first = np.unique(mf * npn + mp, return_index=True)
    mf, mp, mt = mf[first], mp[first], mt[first]
    op5 = ops(5, mf.size)
    add(fid[mf], "hasMember", pop[mp], mt, op5)
    fsize = np.bincount(mf, minlength=nf)

    # -- posts (IU6) --------------------------------------------------------
    nps = n["post"]
    w = np.where(np.arange(nf) < npn, fdeg[fmod] + 1, fsize + 1) \
        .astype(np.float64)
    pf = rng.choice(nf, nps, p=w / w.sum())
    # a group's post is by one of its members (or its moderator)
    moff = np.searchsorted(np.sort(mf), np.arange(nf + 1))
    mlist = mp[np.argsort(mf, kind="stable")]
    pc = fmod[pf].copy()
    grp = (pf >= npn) & (fsize[pf] > 0)
    pc[grp] = mlist[moff[pf[grp]] + (rng.random(int(grp.sum()))
                                     * fsize[pf[grp]]).astype(np.int64)]
    tpo = _later(rng, np.maximum(tf[pf], tp[pc]), 1.0)
    post = ids["post"]
    op6 = ops(6, nps)
    add(post, "hasCreator", pop[pc], tpo, op6)
    add(fid[pf], "containerOf", post, tpo, op6)
    add(post, "isLocatedIn", ids["country"][pcountry[pc]], tpo, op6)
    own, tg = tags(nps, float(g["tags_per_post"]))
    add(post[own], "hasTag", tg, tpo[own], op6[own])

    # -- comments (IU7): threads under posts, replies to the post or to an
    #    earlier comment of the thread ------------------------------------
    ncm = n["comment"]
    root = rng.permutation(nps)[_zipf(rng, nps, ncm,
                                      float(g["thread_zipf"]))]
    cc = friend_or_any(pc[root], float(g["reply_friend_share"]))
    tcm = _later(rng, np.maximum(tpo[root], tp[cc]), 2.0)
    order = np.lexsort((tcm, root))
    root, cc, tcm = root[order], cc[order], tcm[order]
    start = np.searchsorted(root, root)              # thread's first comment
    pos = np.arange(ncm) - start
    to_root = (pos == 0) | (rng.random(ncm) < float(g["reply_root_share"]))
    earlier = start + (rng.random(ncm) * np.maximum(pos, 1)).astype(np.int64)
    com = ids["comment"]
    parent = np.where(to_root, post[root], com[earlier])
    op7 = ops(7, ncm)
    add(com, "hasCreator", pop[cc], tcm, op7)
    add(com, "replyOf", parent, tcm, op7)
    add(com, "isLocatedIn", ids["country"][pcountry[cc]], tcm, op7)
    own, tg = tags(ncm, float(g["tags_per_comment"]))
    add(com[own], "hasTag", tg, tcm[own], op7[own])

    # -- likes (IU2 posts, IU3 comments) ----------------------------------
    nl = int(round(float(g["likes_per_message"]) * (nps + ncm)))
    msg = rng.integers(0, nps + ncm, nl)
    creator = np.where(msg < nps, pc[np.minimum(msg, nps - 1)],
                       cc[np.maximum(msg - nps, 0)])
    tmsg = np.where(msg < nps, tpo[np.minimum(msg, nps - 1)],
                    tcm[np.maximum(msg - nps, 0)])
    liker = friend_or_any(creator, float(g["like_friend_share"]))
    key, first = np.unique(liker * (nps + ncm) + msg, return_index=True)
    msg, liker, tmsg = msg[first], liker[first], tmsg[first]
    tl = _later(rng, np.maximum(tmsg, tp[liker]), 3.0)
    is_post = msg < nps
    target = np.where(is_post, post[np.minimum(msg, nps - 1)],
                      com[np.maximum(msg - nps, 0)])
    op2 = ops(2, int(is_post.sum()))
    op3 = ops(3, int((~is_post).sum()))
    lop = np.empty(msg.size, np.int64)
    lop[is_post] = op2
    lop[~is_post] = op3
    add(pop[liker], "likes", target, tl, lop)

    # -- distinct raw edges, split at stream_from --------------------------
    s, p, o, t, op = (np.concatenate(c) for c in zip(*edges))
    key = (s * len(PREDICATES) + p) * V + o
    order = np.lexsort((t, key))
    keep = np.r_[True, key[order][1:] != key[order][:-1]]
    sel = order[keep]
    s, p, o, t, op = s[sel], p[sel], o[sel], t[sel], op[sel]
    # simulated time advances with the edges created, so the last
    # ``stream_from`` share of time holds that share of the edges (a
    # monotone change of clock: every edge stays after its ends)
    split = float(np.quantile(t[t > STATIC], float(g["stream_from"])))
    bulk = t < split
    kinds = np.zeros(max(nop, 1), np.int64)
    for first_op, count, code in ops_kind:
        kinds[first_op:first_op + count] = code
    # the stream: operations in time order, each a contiguous run
    st = np.nonzero(~bulk)[0]
    st = st[np.lexsort((op[st], t[st]))]
    sop = op[st]
    bounds = np.r_[0, np.nonzero(sop[1:] != sop[:-1])[0] + 1, st.size]
    stream = {"s": s[st], "p": p[st], "o": o[st], "off": bounds,
              "kind": kinds[sop[bounds[:-1]]], "t": t[st][bounds[:-1]]}
    person_bulk = pop[tp < split]
    msg_ids = np.concatenate([post, com])
    msg_t = np.concatenate([tpo, tcm])
    return {"s": s[bulk], "p": p[bulk], "o": o[bulk], "num_nodes": V,
            "num_preds": len(PREDICATES), "pred_names": PREDICATES,
            "domains": {"person": (person_bulk, None),
                        "message": (msg_ids[msg_t < split], None),
                        "tag": (ids["tag"], None),
                        "tagclass": (ids["tagclass"], None)},
            "stream": stream}
