"""The serving path's Pallas kernels compile for a TPU v5e chip.

Ahead-of-time compiles against a *described* v5e topology — no chip
attached — at the shapes the serve engine issues: GenASM-DC v1/v2 window
batches, the graph filter's BitAlign kernel over the windows the mapper
gathers at the smallest and largest bucket caps, the graph align's
BitAlign DC over a graph window, and the jitted batched align step at
the smallest and largest bucket caps.  Interpret-mode tests
cannot see what only the TPU compiler refuses (block shapes off the
(8, 128) tiling, reversals, integer reductions, VMEM overflow).

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every xdist worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.align.batched import batched_kernel_align
from repro.core.genasm import GenASMConfig
from repro.graph.windowed import text_window
from repro.kernels.bitalign import bitalign_dc_batch, bitalign_filter_batch
from repro.kernels.genasm_dc import window_dc_batch
from repro.kernels.genasm_dc_v2 import window_dc_batch_v2

CFG = GenASMConfig()  # w=64, k=24: the engine's alignment geometry
BATCH = 32  # EngineConfig.max_batch
FILTER_BITS, FILTER_K = 128, 11  # graph filter of the serving launcher
SPAN = 128  # graph anchor span: tile stride 64 + margin 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # AOT executables for an absent chip cannot round-trip the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return text


@pytest.mark.parametrize("kernel", [window_dc_batch, window_dc_batch_v2])
def test_window_dc_kernels_compile(one_chip, kernel):
    s = _spec(one_chip, (BATCH, CFG.w), jnp.int8)
    _compiled_kernel(
        lambda t, p: kernel(t, p, w=CFG.w, k=CFG.k, interpret=False), s, s)


@pytest.mark.parametrize("cap", [160, 1280])
def test_graph_filter_kernel_compiles(one_chip, cap):
    rows = BATCH * 4  # max_candidates slots: the top tile rung
    win = cap + 2 * CFG.w + SPAN  # the window gathered per slot
    _compiled_kernel(
        lambda b, s, p, n: bitalign_filter_batch(
            b, s, p, n, m_bits=FILTER_BITS, k=FILTER_K, span=SPAN,
            interpret=False),
        _spec(one_chip, (rows, win), jnp.int8),
        _spec(one_chip, (rows, win), jnp.uint32),
        _spec(one_chip, (rows, FILTER_BITS), jnp.int8),
        _spec(one_chip, (rows,), jnp.int32))


def test_graph_align_dc_kernel_compiles(one_chip):
    nodes = text_window(CFG)
    _compiled_kernel(
        lambda b, s, p, n: bitalign_dc_batch(
            b, s, p, n, m_bits=CFG.w, k=CFG.k, interpret=False),
        _spec(one_chip, (BATCH, nodes), jnp.int8),
        _spec(one_chip, (BATCH, nodes), jnp.uint32),
        _spec(one_chip, (BATCH, CFG.w), jnp.int8),
        _spec(one_chip, (BATCH,), jnp.int32))


@pytest.mark.parametrize("cap", [160, 1280])
def test_batched_align_step_compiles(one_chip, cap):
    lens = _spec(one_chip, (BATCH,), jnp.int32)
    _compiled_kernel(
        lambda t, p, pl_, tl: batched_kernel_align(
            t, p, pl_, tl, cfg=CFG, p_cap=cap, block_bt=128,
            interpret=False),
        _spec(one_chip, (BATCH, cap + 2 * CFG.w), jnp.int8),
        _spec(one_chip, (BATCH, cap), jnp.int8), lens, lens)
