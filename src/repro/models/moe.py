"""Dropless top-k Mixture-of-Experts with expert parallelism.

TPU adaptation: tokens are sorted by expert id and processed with
``jax.lax.ragged_dot`` (grouped matmul — the MXU-native dropless
formulation). Expert parallelism is expressed with ``shard_map`` over the
``model`` mesh axis: activations are replicated across that axis already
(batch shards over ``data``), so dispatch needs **no all-to-all of tokens**
— each model-shard computes its local experts' contribution for its local
batch and a single ``psum`` over ``model`` combines, which is the same
collective the tensor-parallel dense FFN would need.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import dense_init


def init_moe(key, cfg, dtype):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_expert_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "we_gate": dense_init(ks[1], (E, d, f), dtype),
        "we_up": dense_init(ks[2], (E, d, f), dtype),
        "we_down": dense_init(ks[3], (E, f, d), dtype),
    }


def _moe_ragged(x, we_gate, we_up, we_down, topk_idx, gates, first_expert,
                n_global_experts=None):
    """Sorted dropless expert compute via ``jax.lax.ragged_dot`` for experts
    [first, first+E_local). NOTE: flop-exact on TPU (grouped matmul), but
    the CPU *reference lowering* densifies per group — so the dry-run uses
    the capacity-based path below (see EXPERIMENTS.md §Dry-run).
    """
    E_l = we_gate.shape[0]
    k = topk_idx.shape[1]
    flat_e = topk_idx.reshape(-1)
    local = (flat_e >= first_expert) & (flat_e < first_expert + E_l)
    le = jnp.where(local, flat_e - first_expert, E_l)  # E_l = drop bucket
    order = jnp.argsort(le)
    tok = order // k
    xs = jnp.take(x, tok, axis=0)
    group_sizes = jnp.bincount(le, length=E_l + 1).astype(jnp.int32)[:E_l]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, we_gate, group_sizes))
    h = h * jax.lax.ragged_dot(xs, we_up, group_sizes)
    out = jax.lax.ragged_dot(h, we_down, group_sizes)
    w = gates.reshape(-1)[order] * local[order].astype(gates.dtype)
    out = out * w[:, None].astype(out.dtype)
    return jnp.zeros_like(x).at[tok].add(out)


def _moe_capacity(x, we_gate, we_up, we_down, topk_idx, gates, first_expert,
                  n_global_experts=None, capacity_factor: float = 1.25):
    """GShard-style capacity dispatch via scatter (no (T,E,C) one-hot):
    sort token-copies by local expert, place the first `capacity` of each
    expert into an (E_l, C, d) buffer, run three einsums on the MXU, gather
    back weighted. Flop-exact (2*E_l*C*d*f per matmul) and memory-honest;
    overflow tokens are dropped (standard capacity semantics).
    """
    E_l = we_gate.shape[0]
    T, d = x.shape
    k = topk_idx.shape[1]
    flat_e = topk_idx.reshape(-1)
    local = (flat_e >= first_expert) & (flat_e < first_expert + E_l)
    le = jnp.where(local, flat_e - first_expert, E_l)  # E_l = drop bucket
    order = jnp.argsort(le)
    tok = order // k
    sorted_le = le[order]
    group_sizes = jnp.bincount(le, length=E_l + 1).astype(jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes)[:-1]])
    pos_in_group = jnp.arange(T * k, dtype=jnp.int32) - seg_start[sorted_le]
    # expected load per local expert is T*k/E_global; shard sees E_l of them
    E_g = n_global_experts or E_l
    cap = max(int(capacity_factor * (T * k) / max(E_g, 1)), 8)
    keep = (pos_in_group < cap) & (sorted_le < E_l)
    slot = jnp.where(keep, sorted_le * cap + pos_in_group, E_l * cap)
    xe = jnp.zeros((E_l * cap + 1, d), x.dtype).at[slot].set(x[tok])
    xe = xe[: E_l * cap].reshape(E_l, cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, we_gate))
    h = h * jnp.einsum("ecd,edf->ecf", xe, we_up)
    oe = jnp.einsum("ecf,efd->ecd", h, we_down).reshape(E_l * cap, d)
    w = gates.reshape(-1)[order] * keep.astype(gates.dtype)
    vals = oe[jnp.minimum(slot, E_l * cap - 1)] * w[:, None].astype(oe.dtype)
    return jnp.zeros_like(x).at[tok].add(vals)


def router_probs(x2d, router_w):
    logits = (x2d.astype(jnp.float32)) @ router_w
    return jax.nn.softmax(logits, axis=-1)


def load_balance_loss(probs, topk_idx, n_experts: int):
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    pe = probs.mean(axis=0)  # (E,)
    counts = jnp.zeros((n_experts,), jnp.float32).at[topk_idx.reshape(-1)].add(1.0)
    fe = counts / jnp.maximum(counts.sum(), 1.0)
    return n_experts * jnp.sum(fe * pe)


MOE_IMPLS = {"ragged": _moe_ragged, "capacity": _moe_capacity}


def moe_apply(p, x, cfg, mesh=None, data_axes=("data",), impl="capacity"):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    impl: 'capacity' (GShard dispatch; flop-exact under the CPU dry-run) or
    'ragged' (dropless ragged_dot; preferred on real TPU)."""
    kernel = MOE_IMPLS[impl]
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    probs = router_probs(x2, p["router"])
    gates, topk_idx = jax.lax.top_k(probs, cfg.topk)
    gates = (gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)).astype(x.dtype)
    aux = load_balance_loss(probs, topk_idx, cfg.n_experts)

    if mesh is None or "model" not in mesh.axis_names:
        out = kernel(x2, p["we_gate"], p["we_up"], p["we_down"],
                     topk_idx, gates, 0, cfg.n_experts)
        return out.reshape(B, S, d), aux

    def local_fn(xb, wg, wu, wd, idx, g):
        E_l = wg.shape[0]
        first = jax.lax.axis_index("model") * E_l
        Bl, Sl, dl = xb.shape
        y = kernel(xb.reshape(Bl * Sl, dl), wg, wu, wd,
                   idx.reshape(Bl * Sl, -1), g.reshape(Bl * Sl, -1), first,
                   cfg.n_experts)
        return jax.lax.psum(y.reshape(Bl, Sl, dl), "model")

    dspec = P(tuple(data_axes)) if data_axes else P()
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(dspec, P("model"), P("model"), P("model"), dspec, dspec),
        out_specs=dspec, check_vma=False)
    idx3 = topk_idx.reshape(B, S, -1)
    g3 = gates.reshape(B, S, -1)
    out = fn(x, p["we_gate"], p["we_up"], p["we_down"], idx3, g3)
    return out, aux
