"""Pallas-TPU kernel for the budget-sparse Eq.-4 mix (DESIGN.md §12).

Computes the neighbor-list form of the DPFL aggregation

    out[n] = self_w[n] * W_self[n] + sum_b nbr_w[n, b] * W_peers[idx[n, b]]

where idx is the (N, B) int32 neighbor-index table of the constrained
greedy (B = budget << N, -1 = empty slot) and W_self / W_peers are (N, P)
client-stacked flattened params (identical arrays in the uncompressed
path; under compression W_peers is the decoded payload table while the
self term stays exact — DESIGN.md §11). The dense (N, N) mixing matrix is
never materialized and the work is O(N·B·P) instead of O(N²·P).

The gather is expressed through `pltpu.PrefetchScalarGridSpec`: the
neighbor table and both weight tables are scalar-prefetch operands
(flattened to 1-D in SMEM), so the BlockSpec index map of the peer panel
reads ``idx[n, b]`` and DMAs ONLY the selected peer's column panel into
VMEM — grid (P panels, N clients, B slots) with the panel index outermost
so the fp32 output block stays resident across each client's B slots.
Each (N, P) panel is viewed as (N, P/128, 128) and a block takes one
client's (bp/128, 128) tile with the client dimension squeezed, which
keeps every block on the TPU's (8, 128) tiling. Sentinel slots arrive
clamped to row 0 with weight 0.0 (exact no-ops), so the kernel body is
branch-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _kernel(idx_ref, sw_ref, nw_ref, wself_ref, wpeer_ref, o_ref, *, slots):
    del idx_ref  # consumed by the BlockSpec index maps
    n = pl.program_id(1)
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = sw_ref[n] * wself_ref[...].astype(jnp.float32)

    o_ref[...] += nw_ref[n * slots + b] * wpeer_ref[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("block_p", "interpret"))
def sparse_graph_mix(self_w, nbr_w, nbr_idx, W_self, W_peers, *,
                     block_p: int = 2048, interpret: bool = False):
    """self_w: (N,) fp32; nbr_w/nbr_idx: (N, B) fp32/int32 (idx in
    [0, N) or -1 with nbr_w 0); W_self/W_peers: (N, P). Returns (N, P)
    fp32-accumulated mix, cast to W_self.dtype. ``block_p`` (a multiple
    of 1024) is the panel width; narrower models take one panel."""
    N, B = nbr_idx.shape
    P = W_self.shape[1]
    bp = min(block_p, -(-P // _LANES) * _LANES)
    pad = (-P) % bp
    Pp = P + pad

    def panels(w):
        w = jnp.pad(w, ((0, 0), (0, pad))) if pad else w
        return w.reshape(N, Pp // _LANES, _LANES)

    # 1-D tables: SMEM pads a 2-D table's last dim to 128 words, which
    # overflows its 1 MiB at N = 1024
    safe_idx = jnp.clip(nbr_idx, 0, N - 1).astype(jnp.int32).reshape(-1)
    zero_w = jnp.where(nbr_idx >= 0, nbr_w, 0.0).astype(
        jnp.float32).reshape(-1)
    rows = bp // _LANES
    row_block = pl.BlockSpec((None, rows, _LANES),
                             lambda pi, n, b, *_: (n, pi, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Pp // bp, N, B),
        in_specs=[
            row_block,
            # `B` is the static slot count of the flattened table, fixed
            # per trace — capturing it is intentional
            pl.BlockSpec((None, rows, _LANES),
                         lambda pi, n, b, idx, *_: (idx[n * B + b], pi, 0)),  # tracelint: disable=T6
        ],
        out_specs=row_block,
    )
    out = pl.pallas_call(
        functools.partial(_kernel, slots=B), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, Pp // _LANES, _LANES),
                                       jnp.float32),
        interpret=interpret,
    )(safe_idx, self_w.astype(jnp.float32), zero_w,
      panels(W_self), panels(W_peers))
    out = out.reshape(N, Pp)
    out = out[:, :P] if pad else out
    return out.astype(W_self.dtype)
