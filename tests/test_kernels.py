"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _mask_tail(arr, S):
    if S % 32:
        arr[..., -1] &= np.uint32((1 << (S % 32)) - 1)
    return arr


@pytest.mark.parametrize("N,S", [(1, 1), (5, 4), (700, 33), (1024, 64),
                                 (513, 32), (2048, 7)])
def test_nfa_step_shapes(N, S):
    W = (S + 31) // 32
    X = _mask_tail(RNG.integers(0, 2**32, (N, W), dtype=np.uint32), S)
    bwd = _mask_tail(RNG.integers(0, 2**32, (S, W), dtype=np.uint32), S)
    got = np.asarray(ops.nfa_step(X, bwd))
    exp = np.asarray(ref.nfa_step_ref(jnp.asarray(X), jnp.asarray(bwd)))
    np.testing.assert_array_equal(got, exp)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 400), st.integers(1, 40), st.integers(0, 2**31 - 1))
def test_nfa_step_property(N, S, seed):
    rng = np.random.default_rng(seed)
    W = (S + 31) // 32
    X = _mask_tail(rng.integers(0, 2**32, (N, W), dtype=np.uint32), S)
    bwd = _mask_tail(rng.integers(0, 2**32, (S, W), dtype=np.uint32), S)
    got = np.asarray(ops.nfa_step(X, bwd))
    exp = np.asarray(ref.nfa_step_ref(jnp.asarray(X), jnp.asarray(bwd)))
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("n_bits", [100, 515, 8192, 40000])
def test_rank_kernel(n_bits):
    bits = RNG.random(n_bits) < 0.5
    nw = ((n_bits + 511) // 512) * 16 + 16
    padded = np.zeros(nw * 32, dtype=bool)
    padded[:n_bits] = bits
    words = np.packbits(padded.reshape(nw, 32), axis=1,
                        bitorder="little").view(np.uint32).ravel()
    directory = ops.build_rank_directory(words)
    # directory matches ref
    exp_pc = np.asarray(ref.superblock_popcounts_ref(jnp.asarray(words)))
    assert np.array_equal(np.diff(np.asarray(directory)), exp_pc)
    q = RNG.integers(0, n_bits + 1, 200)
    got = np.asarray(ops.rank1(jnp.asarray(words), directory, q))
    exp = np.concatenate([[0], np.cumsum(bits)])[q]
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("n_bits", [100, 515, 8192])
def test_rank1_matches_ref(n_bits):
    """Kernel-pipeline rank1 (directory + window gather + rank_window)
    vs the end-to-end pure-jnp oracle ref.rank1_ref."""
    bits = RNG.random(n_bits) < 0.3
    nw = ((n_bits + 511) // 512) * 16 + 16
    padded = np.zeros(nw * 32, dtype=bool)
    padded[:n_bits] = bits
    words = np.packbits(padded.reshape(nw, 32), axis=1,
                        bitorder="little").view(np.uint32).ravel()
    q = RNG.integers(0, n_bits + 1, 300).astype(np.int32)
    directory = ops.build_rank_directory(jnp.asarray(words))
    got = np.asarray(ops.rank1(jnp.asarray(words), directory, q))
    exp = np.asarray(ref.rank1_ref(jnp.asarray(words), jnp.asarray(q)))
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("E,W,V", [(1, 1, 1), (10, 1, 4), (3000, 2, 50),
                                   (2050, 1, 2000), (1024, 3, 7)])
def test_segment_or_shapes(E, W, V):
    seg = np.sort(RNG.integers(0, V, E)).astype(np.int32)
    vals = RNG.integers(0, 2**32, (E, W), dtype=np.uint32)
    got = np.asarray(ops.segment_or(vals, seg, V))
    exp = np.asarray(ref.segment_or_ref(jnp.asarray(vals), jnp.asarray(seg), V))
    np.testing.assert_array_equal(got, exp)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3000), st.integers(1, 3), st.integers(1, 100),
       st.integers(0, 2**31 - 1))
def test_segment_or_property(E, W, V, seed):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, V, E)).astype(np.int32)
    vals = rng.integers(0, 2**32, (E, W), dtype=np.uint32)
    got = np.asarray(ops.segment_or(vals, seg, V))
    exp = np.zeros((V, W), dtype=np.uint32)
    np.bitwise_or.at(exp, seg, vals)
    np.testing.assert_array_equal(got, exp)


def test_segmented_scan_matches_associative_scan():
    from repro.kernels.segment_or import segmented_or_scan
    E, W = 2500, 2
    vals = RNG.integers(0, 2**32, (E, W), dtype=np.uint32)
    flags = (RNG.random(E) < 0.1).astype(np.int32)
    flags[0] = 1
    got = np.asarray(segmented_or_scan(jnp.asarray(vals), jnp.asarray(flags)))
    exp = np.asarray(ref.segmented_or_scan_ref(jnp.asarray(vals),
                                               jnp.asarray(flags)))
    # kernel output is within-tile only; compare within the first tile
    from repro.kernels.segment_or import TILE_E
    np.testing.assert_array_equal(got[:TILE_E], exp[:TILE_E])


def test_pack_unpack_roundtrip():
    planes = RNG.integers(0, 2, (17, 45)).astype(np.uint8)
    packed = ops.pack_bits(planes)
    assert packed.shape == (17, 2)
    back = ops.unpack_bits(packed, 45)
    np.testing.assert_array_equal(back, planes)


# --------------------------------------------------------------------------
# dense superstep: sorted-segment OR against the segment_max scatter
# --------------------------------------------------------------------------
def _scatter_or_ref(subj, pred, obj, B, PRED, frontier, V):
    """The superstep's segment-OR as a ``segment_max`` scatter over every
    edge row: the formulation the sorted-segment OR replaced."""
    import jax
    X = frontier[obj] * B[pred]
    Y = (X.astype(jnp.int32) @ PRED.astype(jnp.int32)) > 0
    return jnp.maximum(
        jax.ops.segment_max(Y.astype(jnp.int8), subj, num_segments=V), 0)


def _sorted_edges(rng, V, E, nodes):
    """E edge rows over subjects drawn from ``nodes``, sorted by subject,
    with their segment offsets."""
    subj = np.sort(rng.choice(nodes, size=E)).astype(np.int32)
    return subj, np.searchsorted(subj, np.arange(V + 1)).astype(np.int32)


def _segor_case(case):
    """(subj, pred, obj, off or None, n_sorted, B, PRED, frontier, V):
    B [C, L + 1, S] (last row the inert label), PRED [C, S, S] and
    frontier [C, V, S] carry C hetero rows."""
    from repro.core.dense import DenseGraph
    from repro.core.ring import LabeledGraph
    rng = np.random.default_rng(sum(map(ord, case)))
    V, E, L, S, C = 40, 300, 6, 4, 1
    if case == "s8":
        S = 8
    elif case == "s16":
        S = 16
    elif case == "hetero-c4":
        C = 4
    n_tail = 0
    if case == "empty-ends":
        # node 0, the last node and a run in the middle have no edges
        live = [v for v in range(1, V - 1) if not 10 <= v < 15]
        s = rng.choice(live, size=120)
        o = rng.choice(live, size=120)
        dg = DenseGraph.from_graph(LabeledGraph(
            s=s, p=rng.integers(0, L // 2, 120), o=o, num_nodes=V,
            num_preds=L // 2))
        subj, pred, obj, off = (np.asarray(a) for a in
                                (dg.subj, dg.pred, dg.obj, dg.off))
        assert off[0] == off[1] == 0 and off[V - 1] == off[V] == subj.size
        E = subj.size
    else:
        if case == "hub":
            S, E = 8, 12_000 + 300
            subj = np.sort(np.concatenate([
                np.full(12_000, 17), rng.integers(0, V, 300)])).astype(np.int32)
            off = np.searchsorted(subj, np.arange(V + 1)).astype(np.int32)
        else:
            subj, off = _sorted_edges(rng, V, E, np.arange(V))
        pred = rng.integers(0, L, E).astype(np.int32)
        obj = rng.integers(0, V, E).astype(np.int32)
    if case == "tombstoned":
        # relabeled to the inert label in place: the prefix stays sorted
        dead = rng.random(E) < 0.3
        pred = np.where(dead, L, pred).astype(np.int32)
    n_sorted = E
    if case == "insert-tail":
        # an unsorted insert buffer, pow2-padded with inert rows
        n_tail, cap = 11, 16
        ts = np.zeros(cap, np.int32)
        tp = np.full(cap, L, np.int32)
        to = np.zeros(cap, np.int32)
        ts[:n_tail] = rng.integers(0, V, n_tail)
        tp[:n_tail] = rng.integers(0, L, n_tail)
        to[:n_tail] = rng.integers(0, V, n_tail)
        subj, pred, obj = (np.concatenate([a, t]) for a, t in
                           ((subj, ts), (pred, tp), (obj, to)))
    B = (rng.random((C, L + 1, S)) < 0.5).astype(np.int8)
    B[:, L] = 0
    PRED = (rng.random((C, S, S)) < 0.4).astype(np.int8)
    frontier = (rng.random((C, V, S)) < 0.3).astype(np.int8)
    if case == "no-offsets":
        off = None
    return subj, pred, obj, off, n_sorted, B, PRED, frontier, V


@pytest.mark.parametrize("case", [
    "empty-ends", "hub", "s4", "s8", "s16", "hetero-c4", "insert-tail",
    "tombstoned", "no-offsets"])
def test_sorted_segment_or_matches_segment_max(case):
    """The superstep's segment-OR (``dense._edge_scatter``: sorted rows
    reduced by running counts at the segment offsets, rows after the
    sorted prefix scattered) equals the ``segment_max`` scatter over
    every row, bit for bit."""
    import jax
    from repro.core import dense
    subj, pred, obj, off, n_sorted, B, PRED, frontier, V = _segor_case(case)
    subj, pred, obj = (jnp.asarray(a) for a in (subj, pred, obj))
    off = None if off is None else jnp.asarray(off)
    got = jax.vmap(lambda b, p, f: dense._edge_scatter(
        subj, pred, obj, b, p, f, V, off, n_sorted))(B, PRED, frontier)
    want = jax.vmap(lambda b, p, f: _scatter_or_ref(
        subj, pred, obj, b, p, f, V))(B, PRED, frontier)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(want).any() and not np.asarray(want).all()
    if case == "hetero-c4":     # the rows' own tables give their own ORs
        assert len({np.asarray(want)[r].tobytes() for r in range(4)}) == 4
