"""Compile rehearsal: the engine kernels compiled for a described TPU v5e.

Interpret mode checks what the kernels compute; only the TPU compiler
checks that they are legal on the chip — tiling of every block and DMA,
SMEM and VMEM budgets.  These tests compile each engine kernel for one
chip of a described (not attached) ``v5e:2x2`` topology at deployment
sizes: 2^20 vertices and 16M edges, for scalar tables (PageRank, every
scheduler scatter) and for ALS's x·xᵀ table at d = 20 (400 wide, 512
padded), the Netflix ALS-WR cell's gathers at its own sizes (x·xᵀ also
formed in the kernel from the 20-wide table), and the segment sum at the
widths its dispatch rule takes.  The
16M-edge cases guard the SMEM budget: a kernel that scalar-prefetches an
``[E]`` array is refused here.  Nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.gas import gas
from repro.kernels.segsum import segsum
from repro.kernels.segsum.ops import kernel_takes_width

N_ROWS = 1 << 20
N_EDGES = 1 << 24
ALS_XXT = 20 * 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what compiles for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _edge_shapes():
    n_steps = N_EDGES // gas.EDGE_BLOCK + N_ROWS // gas.ROW_BLOCK
    return [((N_EDGES,), jnp.float32), ((N_EDGES,), jnp.int32),
            ((N_EDGES,), jnp.int32), ((n_steps,), jnp.int32),
            ((n_steps,), jnp.int32)]


@pytest.mark.parametrize("width", [1, ALS_XXT])
def test_gather_combine_compiles(one_chip, width):
    def fn(feat, w, snd, rcv, step_rb, step_eb, act):
        return gas.gas_gather_combine_pallas(feat, w, snd, rcv, N_ROWS,
                                             step_rb, step_eb, act)

    text = _compiled_text(fn, one_chip, ((N_ROWS, width), jnp.float32),
                          *_edge_shapes(),
                          ((N_ROWS // gas.ROW_BLOCK,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [ALS_XXT, 20])
def test_als_cell_gathers_compile(one_chip, width):
    """The row-block gathers of the Netflix ALS-WR cell's widths at its
    sizes: 77,794 vertices and a color-step padded to 2^24 edges.  A
    400-wide x·xᵀ table (159 MB, the outer gather's fallback) is staged
    from HBM; the 20-wide table (39.8 MB) stays resident in VMEM, the
    largest resident table the cell asks for."""
    n_rows = 77_794
    n_steps = N_EDGES // gas.EDGE_BLOCK + -(-n_rows // gas.ROW_BLOCK)
    planes = -(-width // gas.LANES)
    resident = planes * -(-n_rows // 8) * 8 * gas.LANES * 4 \
        <= gas.RESIDENT_BYTES
    assert resident == (width == 20)

    def fn(feat, w, snd, rcv, step_rb, step_eb, act):
        return gas.gas_gather_combine_pallas(feat, w, snd, rcv, n_rows,
                                             step_rb, step_eb, act)

    shapes = _edge_shapes()[:3] + [((n_steps,), jnp.int32)] * 2
    text = _compiled_text(fn, one_chip, ((n_rows, width), jnp.float32),
                          *shapes,
                          ((-(-n_rows // gas.ROW_BLOCK),), jnp.int32))
    assert "tpu_custom_call" in text


def test_als_cell_outer_gather_compiles(one_chip):
    """The cell's ``xxt`` gather as it runs: x·xᵀ formed in the row-block
    kernel from the resident 20-wide factor table, at the cell's sizes."""
    n_rows, d = 77_794, 20
    assert gas.kernel_takes_outer(n_rows, d)
    n_steps = N_EDGES // gas.EDGE_BLOCK + -(-n_rows // gas.ROW_BLOCK)

    def fn(feat, w, snd, rcv, step_rb, step_eb, act):
        return gas.gas_gather_combine_pallas(feat, w, snd, rcv, n_rows,
                                             step_rb, step_eb, act,
                                             outer=True)

    shapes = _edge_shapes()[:3] + [((n_steps,), jnp.int32)] * 2
    text = _compiled_text(fn, one_chip, ((n_rows, d), jnp.float32),
                          *shapes,
                          ((-(-n_rows // gas.ROW_BLOCK),), jnp.int32))
    assert "tpu_custom_call" in text


def test_scatter_reschedule_compiles(one_chip):
    def fn(contrib, prio, consume, w, snd, rcv, act):
        return gas.gas_scatter_reschedule_pallas(contrib, prio, consume, w,
                                                 snd, rcv, N_ROWS, act)

    text = _compiled_text(
        fn, one_chip, ((N_ROWS,), jnp.float32), ((N_ROWS,), jnp.float32),
        ((N_ROWS,), jnp.int32), *_edge_shapes()[:3],
        ((N_EDGES // gas.EDGE_BLOCK,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width,n_edges", [(128, N_EDGES), (512, 1 << 22)])
def test_segment_sum_compiles(one_chip, width, n_edges):
    """At the widths the dispatch rule sends to the kernel; 16M edges of
    512-wide messages (32 GiB) would not fit one chip's HBM, so that width
    compiles at 4M edges."""
    assert kernel_takes_width(width)
    n_steps = n_edges // gas.EDGE_BLOCK + N_ROWS // gas.ROW_BLOCK

    def fn(msgs, rcv, step_rb, step_eb):
        return segsum.segment_sum_sorted_pallas(msgs, rcv, N_ROWS, step_rb,
                                                step_eb)

    text = _compiled_text(fn, one_chip, ((n_edges, width), jnp.float32),
                          ((n_edges,), jnp.int32), ((n_steps,), jnp.int32),
                          ((n_steps,), jnp.int32))
    assert "tpu_custom_call" in text
