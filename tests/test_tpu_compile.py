"""The served path's kernels and dense chunk program, compiled for a
described TPU v5e chip (no chip attached): the compiler refuses here
what it would refuse on the chip — unaligned slices, too much fast
memory, a program larger than the device's HBM.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import dense
from repro.kernels import rank_popcount
from repro.kernels.nfa_step import nfa_step_pallas
from repro.kernels.segment_or import segmented_or_scan

HBM_BYTES = 15.75e9   # what the compiler lets one v5e program use
# the dense smoke deployment: scale_free_graph(2**20, 64, 2**22) has at
# most 2**23 completed edges over 128 completed labels
SMOKE_V, SMOKE_E, SMOKE_L = 2**20, 2**23, 128
# the wikidata-kg deployment: 8,375,130 completed edges over 1,526,402
# nodes and 10,838 completed labels (plus the inert label row)
KG_V, KG_E, KG_L = 1_526_402, 8_375_130, 10_838


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("N,W,S", [(4096, 1, 16), (4096, 2, 40)])
def test_nfa_step_compiles(one_chip, N, W, S):
    compiled = nfa_step_pallas.lower(
        _sds((N, W), jnp.uint32, one_chip), _sds((S, W), jnp.uint32, one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_superblock_popcounts_compiles(one_chip):
    compiled = rank_popcount.superblock_popcounts_pallas.lower(
        _sds((2**20,), jnp.uint32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rank_window_compiles(one_chip):
    Q, SB = 4096, rank_popcount.SB_WORDS
    compiled = rank_popcount.rank_window.lower(
        _sds((Q, SB), jnp.uint32, one_chip), _sds((Q, SB), jnp.uint32, one_chip),
        _sds((Q,), jnp.int32, one_chip), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("V,E,L,C,S", [
    (SMOKE_V, SMOKE_E, SMOKE_L, 8, 16),
    (KG_V, KG_E, KG_L, 8, 8),
], ids=["smoke", "wikidata-kg"])
def test_dense_chunk_fits_one_chip(one_chip, V, E, L, C, S):
    """The slot tick's program, C rows of S-state planes over E sorted
    edges with no overlay, within one chip's HBM — and with no scatter
    left: the segment-OR runs over the sorted edges."""
    edge = _sds((E,), jnp.int32, one_chip)
    compiled = dense._bfs_chunk_hetero.lower(
        edge, edge, edge,
        _sds((C, L + 1, S), jnp.int8, one_chip),
        _sds((C, S, S), jnp.int8, one_chip),
        _sds((C, V, S), jnp.int8, one_chip),
        _sds((C, V, S), jnp.int8, one_chip),
        num_nodes=V, chunk=1, off=_sds((V + 1,), jnp.int32, one_chip),
        n_sorted=E).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    assert "scatter(" not in compiled.as_text()


def test_segmented_or_scan_refused(one_chip):
    """Mosaic refuses the in-tile lane shift of ``segmented_or_scan``
    (a pad-then-slice that reads outside the first tile).  The kernel
    is off the served path; when it compiles, this test fails and the
    kernel is ready to be wired in."""
    with pytest.raises(Exception,
                       match="Input offsets outside of the first tile"):
        segmented_or_scan.lower(
            _sds((4096, 1), jnp.uint32, one_chip),
            _sds((4096,), jnp.int32, one_chip), interpret=False).compile()
