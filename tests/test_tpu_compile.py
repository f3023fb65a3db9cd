"""Ahead-of-time compiles of the three Pallas graph kernels for a described
TPU v5e (no chip attached): the chip's compiler refuses what interpret
mode accepts — blocks off the (8, 128) tiling, more VMEM than a kernel
may take — so every kernel of the round engine is compiled here at the
paper CNN's width (P = 62,006) and at up to 1,024 clients.

The topology is described inside a module fixture, never while a module
is imported: only one process at a time may load the TPU library."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compressed_graph_mix import compressed_graph_mix
from repro.kernels.graph_mix import graph_mix
from repro.kernels.sparse_graph_mix import sparse_graph_mix

P_CNN = 62006        # PaperCNN's flattened parameter count
TOPK_K = 3101        # ceil(0.05 * P_CNN)


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent
    compilation cache off: what is compiled for a described chip is
    written to it but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("n,m", [(100, 100), (100, 1), (1024, 1024),
                                 (1024, 1)])
def test_graph_mix_compiles_for_v5e(one_chip, n, m):
    """Eq.-4 mix (M = N) and GGC probe row (M = 1): the panel width
    narrows with N so that A stays resident within the VMEM limit."""
    text = _compiled_text(graph_mix, [((m, n), F32), ((n, P_CNN), F32)],
                          one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,b", [(100, 10), (1024, 10)])
def test_sparse_graph_mix_compiles_for_v5e(one_chip, n, b):
    """Neighbor-list gather: weights and indices in SMEM, one client's
    (bp/128, 128) row tile per block."""
    text = _compiled_text(
        sparse_graph_mix,
        [((n,), F32), ((n, b), F32), ((n, b), I32), ((n, P_CNN), F32),
         ((n, P_CNN), F32)], one_chip)
    assert "tpu_custom_call" in text


def test_compressed_graph_mix_compiles_for_v5e(one_chip):
    """Top-k 5% mix at 100 clients: 8-client payload blocks densified
    into a VMEM panel, then one A @ panel matmul."""
    n = 100
    text = _compiled_text(
        functools.partial(compressed_graph_mix, p_dim=P_CNN),
        [((n, n), F32), ((n, TOPK_K), F32), ((n, TOPK_K), I32)], one_chip)
    assert "tpu_custom_call" in text
