"""Pallas-TPU kernel mixing top-k-sparsified client models (DESIGN.md §11).

Computes ``out = A @ densify(vals, idx)`` where A is the (M, N) mixing
operator with its diagonal zeroed (the Eq.-4 self term stays exact and is
added by the caller), and (vals, idx) is the (N, K) top-k payload of each
client's flattened params — K = ceil(topk_frac * P) << P. The dense
(N, P) peer matrix is never materialized in HBM: for each column panel,
the kernel densifies the payloads into an (N, bp) fp32 VMEM scratch, one
block of 8 clients and one K chunk per grid step, and then applies A to
the whole panel with one MXU matmul

    D[8 clients, panel] += spread(v) @ onehot(idx, panel)^T
    out[:, panel]        = A @ D[:, panel]          (after the last step)

``spread`` lays the 8 clients' (8, bk) value rows block-diagonally into an
(8, 8·bk) operand, so one NT matmul against the (bp, 8·bk) one-hot
scatters all 8 rows at once and duplicate indices ADD. Grid is (P panels,
N/8 client blocks, K chunks) with the panel index OUTERMOST, so the output
block and the scratch stay resident across the whole sweep of a panel.
Every block is (8, 128)-tiled: clients pad to a multiple of 8 (zero
values, index -1, zero A columns) and K and P pad to their block sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8      # clients per grid step: the fp32 sublane tile
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernel(a_ref, v_ref, i_ref, o_ref, d_ref, *, bp):
    pi = pl.program_id(0)
    nb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when((nb == 0) & (kb == 0))
    def _init():
        d_ref[...] = jnp.zeros_like(d_ref)

    v = v_ref[...].astype(jnp.float32)          # (8, bk) payload values
    idx = i_ref[...]                            # (8, bk) int32 (-1 = pad)
    bk = v.shape[1]
    shape = (_ROWS, _ROWS * bk)
    own = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // bk
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    spread = jnp.where(own == row, jnp.concatenate([v] * _ROWS, axis=1), 0.0)
    flat_idx = jnp.concatenate([idx[r:r + 1] for r in range(_ROWS)], axis=1)
    cols = pi * bp + jax.lax.broadcasted_iota(jnp.int32,
                                              (bp, _ROWS * bk), 0)
    onehot = (cols == flat_idx).astype(jnp.float32)    # (bp, 8·bk)
    dense = jax.lax.dot_general(
        spread, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)           # (8, bp)
    start = pl.multiple_of(nb * _ROWS, _ROWS)
    d_ref[pl.ds(start, _ROWS), :] += dense

    @pl.when((nb == pl.num_programs(1) - 1) & (kb == pl.num_programs(2) - 1))
    def _mix():
        o_ref[...] = jnp.dot(a_ref[...].astype(jnp.float32), d_ref[...],
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)


@functools.partial(
    jax.jit,
    static_argnames=("p_dim", "block_p", "block_k", "interpret"))
def compressed_graph_mix(A, vals, idx, p_dim: int, *, block_p: int = 256,
                         block_k: int = 128, interpret: bool = False):
    """A: (M, N); vals/idx: (N, K), idx in [0, p_dim). Returns (M, p_dim)
    = A @ densify(vals, idx) in fp32 accumulation, cast to vals.dtype."""
    M, N = A.shape
    K = vals.shape[1]
    bp = min(block_p, _round_up(p_dim, _LANES))
    bk = min(block_k, _round_up(K, _LANES))
    Np, Kp, Pp = _round_up(N, _ROWS), _round_up(K, bk), _round_up(p_dim, bp)
    A = jnp.pad(A, ((0, 0), (0, Np - N)))
    vals = jnp.pad(vals, ((0, Np - N), (0, Kp - K)))
    idx = jnp.pad(idx, ((0, Np - N), (0, Kp - K)), constant_values=-1)
    out = pl.pallas_call(
        functools.partial(_kernel, bp=bp),
        grid=(Pp // bp, Np // _ROWS, Kp // bk),
        in_specs=[
            pl.BlockSpec((M, Np), lambda pi, nb, kb: (0, 0)),  # A resident
            pl.BlockSpec((_ROWS, bk), lambda pi, nb, kb: (nb, kb)),
            pl.BlockSpec((_ROWS, bk), lambda pi, nb, kb: (nb, kb)),
        ],
        out_specs=pl.BlockSpec((M, bp), lambda pi, nb, kb: (0, pi)),
        out_shape=jax.ShapeDtypeStruct((M, Pp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Np, bp), jnp.float32)],
        interpret=interpret,
    )(A, vals, idx)
    out = out[:, :p_dim] if Pp != p_dim else out
    return out.astype(vals.dtype)
