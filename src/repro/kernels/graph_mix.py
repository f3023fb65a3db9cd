"""Pallas-TPU kernel for DPFL collaboration-graph aggregation (Eq. 4).

Computes ``out = A @ W`` where A is the (M, N) mixing operator — the full
(N, N) row-stochastic matrix for Eq.-4 aggregation, or a single (1, N)
mask-weight row for the GGC set-average probes — and W the (N, P)
client-stacked flattened parameters. M, N are small (clients); P is huge
(model size), so we tile P into VMEM-sized column panels and keep A
resident in VMEM. Accumulation in fp32 regardless of the parameter dtype.

The panel width is chosen from (M, N) so that the double-buffered blocks
(A, one W panel, one output panel) fit `VMEM_BUDGET`, and the kernel's
scoped VMEM limit is raised to `VMEM_LIMIT` to hold them beside the
compiler's temporaries for the fp32 matmul (about 2x the bytes of A at
M = N = 1024). A itself must fit, which holds up to N ≈ 1200 at M = N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of VMEM the kernel's blocks may take, and the scoped limit the
# kernel asks for (a v5e core has 128 MiB; the default limit is 16 MiB)
VMEM_BUDGET = 12 * 2**20
VMEM_LIMIT = 48 * 2**20


def panel_width(M: int, N: int, P: int, block_p: int = 2048) -> int:
    """Width of the W/output column panels: ``block_p``, narrowed to the
    widest multiple of 128 whose double-buffered fp32 blocks — A (M, N), a
    W panel (N, bp) and an output panel (M, bp) — fit `VMEM_BUDGET`; P
    itself when that is narrower (a full-width block is always legal)."""
    per_col = 2 * (N + M) * 4
    fit = max(VMEM_BUDGET - 2 * M * N * 4, 0) // per_col // 128 * 128
    bp = min(block_p, max(fit, 128))
    return P if P <= bp else bp


def _kernel(a_ref, w_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(a, w, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_p", "interpret"))
def graph_mix(A, W, *, block_p: int = 2048, interpret: bool = False):
    """A: (M, N); W: (N, P). Returns (M, P) = A @ W."""
    M = A.shape[0]
    N, P = W.shape
    bp = panel_width(M, N, P, block_p)
    pad = (-P) % bp
    Wp = jnp.pad(W, ((0, 0), (0, pad))) if pad else W
    Pp = P + pad
    out = pl.pallas_call(
        _kernel,
        grid=(Pp // bp,),
        in_specs=[
            pl.BlockSpec((M, N), lambda i: (0, 0)),       # A resident
            pl.BlockSpec((N, bp), lambda i: (0, i)),      # panel of W
        ],
        out_specs=pl.BlockSpec((M, bp), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((M, Pp), W.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(A, Wp)
    return out[:, :P] if pad else out
