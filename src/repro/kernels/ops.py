"""Jitted public wrappers around the Pallas kernels.

On a TPU backend the kernels compile to Mosaic; on any other backend
(CPU tests and rehearsals) they run in ``interpret=True`` mode.
``interpret`` is resolved from the default backend at every call, so
nothing fixed at import time can route a TPU process to the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import nfa_step as _nfa
from . import rank_popcount as _rank
from . import segment_or as _seg


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def pack_bits(planes: np.ndarray) -> np.ndarray:
    """bool/int planes [..., S] -> packed uint32 [..., ceil(S/32)]."""
    planes = np.asarray(planes)
    S = planes.shape[-1]
    W = (S + 31) // 32
    pad = W * 32 - S
    p = np.pad(planes.astype(np.uint8), [(0, 0)] * (planes.ndim - 1) + [(0, pad)])
    p = p.reshape(*p.shape[:-1], W, 32)
    weights = (1 << np.arange(32, dtype=np.uint64)).astype(np.uint64)
    out = (p.astype(np.uint64) * weights).sum(axis=-1)
    return out.astype(np.uint32)


def unpack_bits(packed: np.ndarray, S: int) -> np.ndarray:
    """packed uint32 [..., W] -> planes [..., S] uint8."""
    packed = np.asarray(packed)
    W = packed.shape[-1]
    bits = (packed[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(*packed.shape[:-1], W * 32)[..., :S].astype(np.uint8)


def nfa_step(X, bwd):
    """Bit-parallel reverse Glushkov step: Y = T'[X] (packed)."""
    return _nfa.nfa_step_pallas(jnp.asarray(X), jnp.asarray(bwd),
                                interpret=_interpret())


def superblock_popcounts(words):
    return _rank.superblock_popcounts_pallas(jnp.asarray(words),
                                             interpret=_interpret())


def build_rank_directory(words):
    """Prefix-sum rank directory from per-superblock popcounts."""
    pc = superblock_popcounts(words)
    return jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(pc, dtype=jnp.int32)])


def rank1(words, directory, i):
    """Batched rank1 over a packed bitvector (uint32 words, 512-bit
    superblocks): gathers each query's superblock window in XLA, reduces
    masked popcounts in the kernel."""
    i = jnp.asarray(i, dtype=jnp.int32)
    sb = i >> 9
    w0 = sb * _rank.SB_WORDS
    offs = jnp.arange(_rank.SB_WORDS, dtype=jnp.int32)
    widx = w0[:, None] + offs[None, :]
    windows = words[jnp.clip(widx, 0, words.shape[0] - 1)]
    wq = i >> 5
    rel = wq[:, None] - widx
    inword = (i & 31).astype(jnp.uint32)[:, None]
    partial = jnp.where(
        inword == 0,
        jnp.uint32(0),
        (jnp.uint32(0xFFFFFFFF)) >> (jnp.uint32(32) - inword),  # repro: noqa B002 — amount hits 32 only in lanes where the enclosing where() selects the inword==0 branch; the out-of-range lane is discarded
    )
    masks = jnp.where(
        rel > 0,
        jnp.uint32(0xFFFFFFFF),
        jnp.where(rel == 0, partial, jnp.uint32(0)),
    )
    bases = directory[sb]
    return _rank.rank_window(windows, masks, bases, interpret=_interpret())


def segment_or(vals, seg_ids, num_segments: int):
    """Scatter-OR of packed rows: out[v] = OR of vals[e] with
    seg_ids[e] == v.  seg_ids must be sorted ascending."""
    vals = jnp.asarray(vals, dtype=jnp.uint32)
    seg_ids = jnp.asarray(seg_ids, dtype=jnp.int32)
    E, W = vals.shape
    flags = jnp.concatenate(
        [jnp.ones(1, jnp.int32), (seg_ids[1:] != seg_ids[:-1]).astype(jnp.int32)]
    )
    scanned = _seg.segmented_or_scan(vals, flags, interpret=_interpret())

    # ---- stitch tile carries ----
    T = _seg.TILE_E
    pad = (T - E % T) % T
    n_tiles = (E + pad) // T
    fl = jnp.pad(flags, (0, pad), constant_values=1).reshape(n_tiles, T)
    sc = jnp.pad(scanned, ((0, pad), (0, 0))).reshape(n_tiles, T, W)
    tile_last = sc[:, -1, :]                          # [n_tiles, W]
    tile_has_flag = fl.sum(axis=1) > 0                # padded rows flag -> True mostly
    # has a *real* flag anywhere in the tile (padding rows always flagged,
    # so restrict to the unpadded region)
    real = (jnp.arange(n_tiles * T).reshape(n_tiles, T) < E)
    tile_has_flag = (fl * real).sum(axis=1) > 0

    def carry_step(c, x):
        has_flag, last = x
        nxt = jnp.where(has_flag, last, c | last)
        return nxt, c

    _, carries = jax.lax.scan(carry_step, jnp.zeros(W, jnp.uint32),
                              (tile_has_flag, tile_last))
    # row receives carry iff no flag within its tile at or before it
    cum = jnp.cumsum(fl, axis=1)
    open_prefix = (cum == 0)
    final = sc | (carries[:, None, :] * open_prefix[:, :, None].astype(jnp.uint32))
    final = final.reshape(-1, W)[:E]

    # ---- pick each segment's last row ----
    last_idx = jnp.searchsorted(seg_ids, jnp.arange(num_segments), side="right") - 1
    counts = jnp.searchsorted(seg_ids, jnp.arange(num_segments), side="right") - \
        jnp.searchsorted(seg_ids, jnp.arange(num_segments), side="left")
    gathered = final[jnp.clip(last_idx, 0, E - 1)]
    return jnp.where((counts > 0)[:, None], gathered, jnp.uint32(0))
