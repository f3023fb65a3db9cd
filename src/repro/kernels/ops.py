"""Public kernel ops with implementation dispatch.

impl resolution order: explicit arg > REPRO_KERNEL_IMPL env > platform
default ('pallas' on TPU, 'ref' elsewhere — 'interpret' runs the Pallas
kernel body in Python on CPU and is what the test-suite sweeps use).
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from ..analysis.registry import exchange_site
from . import ref
from .compressed_graph_mix import compressed_graph_mix as _compressed_mix
from .flash_attention import flash_attention as _flash
from .graph_mix import graph_mix as _graph_mix
from .rglru_scan import rglru_scan as _rglru_scan
from .sparse_graph_mix import sparse_graph_mix as _sparse_mix
from .ssd import ssd as _ssd


def resolve_impl(impl: Optional[str]) -> str:
    """The kernel implementation a call with ``impl`` runs: 'pallas',
    'interpret' or 'ref'."""
    if impl:
        return impl
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@exchange_site(charges="caller")
def graph_mix(A, W, impl: Optional[str] = None, *, mesh=None,
              client_axes=None, **kw):
    """Eq.-4 mixing matmul ``A @ W`` ((M, N) @ (N, P)).

    With ``mesh``/``client_axes`` the op runs as a `shard_map` over the
    client axis: each shard all-gathers the peer parameter panels and
    computes its own row-block of A @ W with the dispatched kernel, so
    fp32 accumulation is preserved shard-for-shard and the gather is the
    round's only model-sized collective (DESIGN.md §8).
    """
    m = resolve_impl(impl)

    def local(a, w):
        if m == "ref":
            return ref.graph_mix_ref(a, w)
        return _graph_mix(a, w, interpret=(m == "interpret"), **kw)

    if mesh is None:
        return local(A, W)
    from jax.sharding import PartitionSpec as P

    ca = tuple(client_axes)

    def row_block(a_blk, w_blk):
        w_full = jax.lax.all_gather(w_blk, ca, axis=0, tiled=True)
        return local(a_blk, w_full)

    # check_vma=False: pallas_call has no shard_map replication rule
    return jax.shard_map(row_block, mesh=mesh,
                         in_specs=(P(ca, None), P(ca, None)),
                         out_specs=P(ca, None), check_vma=False)(A, W)


@exchange_site(charges="caller")
def compressed_graph_mix(A, vals, idx, p_dim: int,
                         impl: Optional[str] = None, *, mesh=None,
                         client_axes=None, **kw):
    """Top-k-compressed Eq.-4 mixing ``A @ densify(vals, idx)`` without
    materializing the dense (N, P) peer matrix on the host (DESIGN.md
    §11). A: (M, N) with a zeroed diagonal (the exact self term is the
    caller's); vals/idx: the (N, K) top-k payload, idx in [0, p_dim).

    With ``mesh``/``client_axes`` the op runs as a `shard_map` over the
    client axis, and the all-gather moves the COMPRESSED (values,
    indices) panels — 2K words per peer instead of P, which is the whole
    point of sparsifying the exchange; each shard then computes its own
    row-block with the dispatched kernel.
    """
    m = resolve_impl(impl)

    def local(a, v, i):
        if m == "ref":
            return ref.compressed_graph_mix_ref(a, v, i, p_dim)
        return _compressed_mix(a, v, i, p_dim,
                               interpret=(m == "interpret"), **kw)

    if mesh is None:
        return local(A, vals, idx)
    from jax.sharding import PartitionSpec as P

    ca = tuple(client_axes)

    def row_block(a_blk, v_blk, i_blk):
        v_full = jax.lax.all_gather(v_blk, ca, axis=0, tiled=True)
        i_full = jax.lax.all_gather(i_blk, ca, axis=0, tiled=True)
        return local(a_blk, v_full, i_full)

    # check_vma=False: pallas_call has no shard_map replication rule
    return jax.shard_map(row_block, mesh=mesh,
                         in_specs=(P(ca, None), P(ca, None), P(ca, None)),
                         out_specs=P(ca, None), check_vma=False)(A, vals, idx)


def _rotation_schedule(mesh, client_axes):
    """Static shard-to-shard rotation plan over the (possibly multi-axis)
    client mesh: a list of (axis_name, cumulative per-axis offsets) — one
    single-axis cyclic ppermute per step — whose cumulative offsets visit
    every non-zero shard offset of the torus exactly once. Row-major over
    ``client_axes``, matching how shard_map splits the client axis."""
    from ..sharding.compat import mesh_axis_sizes

    sizes = [mesh_axis_sizes(mesh)[a] for a in client_axes]
    steps = []
    off = [0] * len(sizes)
    total = 1
    for s in sizes:
        total *= s
    for _ in range(total - 1):
        # increment the multi-axis offset by one, rightmost axis fastest;
        # each carry is one extra single-axis rotation of the panel
        moves = []
        for ax in reversed(range(len(sizes))):
            off[ax] = (off[ax] + 1) % sizes[ax]
            moves.append(client_axes[ax])
            if off[ax] != 0:
                break
        steps.append((tuple(moves), tuple(off)))
    return sizes, steps


@exchange_site(charges="caller")
def sparse_graph_mix(self_w, nbr_w, nbr_idx, W_self, peer_parts=None,
                     peer_decode=None, impl: Optional[str] = None, *,
                     mesh=None, client_axes=None, **kw):
    """Budget-sparse Eq.-4 mix over (N, B) neighbor lists (DESIGN.md §12):
    ``out[n] = self_w[n]·W_self[n] + Σ_b nbr_w[n,b]·peers[idx[n,b]]``
    with idx -1 = empty slot. ``peer_parts`` is a tuple of client-stacked
    arrays holding what peers actually transmit (default: ``(W_self,)``);
    ``peer_decode(*parts) -> (n, P)`` reconstructs the peer model table
    shard-locally (identity by default) — under compression the parts are
    the codec payload, so the simulated exchange moves encoded bytes.

    With ``mesh``/``client_axes`` the op runs as a `shard_map` that
    ROTATES the peer parts shard-to-shard (one single-axis `ppermute` per
    step) instead of all-gathering the full (N, P) panel: each shard
    inspects the visiting shard's panel, keeps only the rows its neighbor
    lists request, and accumulates their weighted contribution with the
    dispatched kernel. Peak per-shard peer storage is one (N/D, P) panel
    (vs the dense path's (N, P) gather) and every kept row was explicitly
    requested — the exchange is list-shaped, like the decentralized
    system it simulates.
    """
    m = resolve_impl(impl)
    if peer_parts is None:
        peer_parts = (W_self,)
    if peer_decode is None:
        peer_decode = lambda part, *_: part  # noqa: E731

    def local(sw, nw, idx, ws, wp):
        if m == "ref":
            return ref.sparse_graph_mix_ref(sw, nw, idx, ws, wp)
        return _sparse_mix(sw, nw, idx, ws, wp,
                           interpret=(m == "interpret"), **kw)

    if mesh is None:
        return local(self_w, nbr_w, nbr_idx, W_self,
                     peer_decode(*peer_parts))
    from jax.sharding import PartitionSpec as P

    ca = tuple(client_axes)
    sizes, schedule = _rotation_schedule(mesh, ca)
    strides = []
    acc = 1
    for s in reversed(sizes):
        strides.append(acc)
        acc *= s
    strides = list(reversed(strides))  # row-major over ca

    def row_block(sw_blk, nw_blk, idx_blk, ws_blk, *parts):
        n_loc = ws_blk.shape[0]
        coords = [jax.lax.axis_index(a) for a in ca]

        def contribution(offsets, panel_parts, with_self):
            src = sum(((c - o) % s) * st for c, o, s, st
                      in zip(coords, offsets, sizes, strides))
            local_idx = idx_blk - src * n_loc
            match = (idx_blk >= 0) & (local_idx >= 0) & \
                (local_idx < n_loc)
            idx_l = jnp.where(match, jnp.clip(local_idx, 0, n_loc - 1), -1)
            w_l = jnp.where(match, nw_blk, 0.0)
            sw = sw_blk if with_self else jnp.zeros_like(sw_blk)
            return local(sw, w_l, idx_l, ws_blk,
                         peer_decode(*panel_parts))

        out = contribution((0,) * len(ca), parts, True)
        panel = parts
        for moves, offsets in schedule:
            for axis in moves:
                size = sizes[ca.index(axis)]
                perm = [(i, (i + 1) % size) for i in range(size)]
                panel = tuple(
                    jax.lax.ppermute(x, axis, perm) for x in panel)
            out = out + contribution(offsets, panel, False)
        return out

    part_specs = tuple(P(ca, *((None,) * (x.ndim - 1)))
                       for x in peer_parts)
    # check_vma=False: pallas_call has no shard_map replication rule
    return jax.shard_map(
        row_block, mesh=mesh,
        in_specs=(P(ca), P(ca, None), P(ca, None), P(ca, None))
        + part_specs,
        out_specs=P(ca, None), check_vma=False)(
            self_w, nbr_w, nbr_idx, W_self, *peer_parts)


def flash_attention(q, k, v, *, causal=True, window=None,
                    impl: Optional[str] = None, **kw):
    m = resolve_impl(impl)
    if m == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=(m == "interpret"), **kw)


def rglru_scan(a, b, h0=None, impl: Optional[str] = None, **kw):
    m = resolve_impl(impl)
    if m == "ref":
        return ref.linear_scan_ref(a, b, h0)
    return _rglru_scan(a, b, h0, interpret=(m == "interpret"), **kw)


def ssd(x, dlogA, B, C, chunk: int = 256, h0=None,
        impl: Optional[str] = None, **kw):
    m = resolve_impl(impl)
    if m == "ref":
        return ref.ssd_ref(x, dlogA, B, C, chunk, h0)
    return _ssd(x, dlogA, B, C, chunk=chunk, h0=h0,
                interpret=(m == "interpret"), **kw)
