"""Pallas TPU kernels for the paper's hot loops.

:mod:`.ops` is the public API — jitted wrappers that compile the
kernels on a TPU backend and interpret them on any other; the sibling
modules hold the raw ``pallas_call`` bodies (suffixed ``_pallas`` so
the wrapper names are never shadowed).  The package re-exports the ``ops`` entry points, so
``from repro.kernels import nfa_step`` is the supported spelling.

``PALLAS_KERNELS`` names the kernel-backed entry points: the precise
"public kernel" set the R003 parity gate (``repro.analysis``) enforces —
each must have a ``<name>_ref`` pure-jnp oracle in :mod:`.ref` and a
parity test exercising it in ``tests/test_kernels.py``.  Host-side
packing helpers (``pack_bits``/``unpack_bits``/``build_rank_directory``)
are public but not kernel-backed, so they sit outside that contract.
"""
from .ops import (build_rank_directory, nfa_step, pack_bits, rank1,
                  segment_or, superblock_popcounts, unpack_bits)

# kernel-backed public entry points (R003: each needs `<name>_ref` + a
# parity test)
PALLAS_KERNELS = ("nfa_step", "superblock_popcounts", "rank1", "segment_or")

__all__ = [
    "PALLAS_KERNELS",
    "build_rank_directory",
    "nfa_step",
    "pack_bits",
    "rank1",
    "segment_or",
    "superblock_popcounts",
    "unpack_bits",
]
