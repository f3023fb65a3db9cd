"""Decoder-only language models covering the dense / moe / vlm / ssm /
hybrid families, with scan-over-layers, GQA(+qk-norm), sliding windows,
ring-buffer KV caches, chunked CE, and optional remat.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .common import (NEG_INF, apply_rope, attention_ref, chunked_softmax_xent,
                     dense_init, embed_init, rms_norm, swiglu)
from .moe import init_moe, moe_apply
from .rglru import init_rec_block, init_rec_cache, rec_block
from .ssm import init_mamba_block, init_mamba_cache, mamba_block


# ----------------------------------------------------------------- attention


def init_attn(key, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, Hq * hd), dtype),
        "wk": dense_init(ks[1], (d, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, Hkv * hd), dtype),
        "wo": dense_init(ks[3], (Hq * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_decode_seqshard(q, k_new, v_new, cache, pos, cfg: ArchConfig,
                         mesh, window=None, data_axes=("data",)):
    """Flash-decoding with the KV cache sharded over the `model` axis on
    the SEQUENCE dim (beyond-paper §Perf optimization): each model-shard
    holds C/n_model cache rows, computes a partial online-softmax over its
    rows, and two small psums ((B,Hkv,rep,hd) numerator + (B,Hkv,rep)
    denominator) combine — instead of replicating the whole cache.

    q: (B,1,Hq,hd); k_new/v_new: (B,1,Hkv,hd); cache k/v: (B,C,Hkv,hd)
    sharded (data_axes, 'model', None, None); pos: scalar int32.
    Returns (out (B,1,Hq,hd), new_cache).
    """
    from jax.sharding import PartitionSpec as P

    B, _, Hq, hd = q.shape
    Hkv = k_new.shape[2]
    rep = Hq // Hkv
    scale = 1.0 / math.sqrt(hd)
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]

    def local(q, kn, vn, ck, cv, cpos):
        i = jax.lax.axis_index("model")
        Cl = ck.shape[1]
        slot = pos % (Cl * n_model)
        lslot = slot - i * Cl
        in_range = (lslot >= 0) & (lslot < Cl)
        ls = jnp.clip(lslot, 0, Cl - 1)
        ck2 = jax.lax.dynamic_update_slice_in_dim(ck, kn, ls, 1)
        cv2 = jax.lax.dynamic_update_slice_in_dim(cv, vn, ls, 1)
        cp2 = jax.lax.dynamic_update_slice_in_dim(
            cpos, jnp.broadcast_to(pos[None, None],
                                   (ck.shape[0], 1)).astype(jnp.int32), ls, 1)
        ck = jnp.where(in_range, ck2, ck)
        cv = jnp.where(in_range, cv2, cv)
        cp = jnp.where(in_range, cp2, cpos)
        # partial attention over local cache rows (operands stay bf16,
        # f32 accumulation — never materialize an f32 cache copy)
        qg = q.reshape(q.shape[0], Hkv, rep, hd)
        s = jnp.einsum("bgrd,bkgd->bgrk", qg, ck,
                       preferred_element_type=jnp.float32) * scale
        mask = (cp >= 0) & (cp <= pos)
        if window is not None:
            mask = mask & (cp > pos - window)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_loc = s.max(axis=-1)                                 # (b,g,r)
        m = jax.lax.pmax(m_loc, "model")
        p_ = jnp.exp(s - m[..., None])
        l = jax.lax.psum(p_.sum(-1), "model")                  # (b,g,r)
        o = jnp.einsum("bgrk,bkgd->bgrd", p_.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
        o = jax.lax.psum(o, "model") / jnp.maximum(l, 1e-30)[..., None]
        return (o.reshape(q.shape[0], 1, Hq, hd).astype(q.dtype),
                ck, cv, cp)

    da = tuple(data_axes) if data_axes else ()
    b = P(da) if da else P(None)
    bq = P(da if da else None, None, None, None)
    ckv = P(da if da else None, "model", None, None)
    cpos_spec = P(da if da else None, "model")
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bq, bq, bq, ckv, ckv, cpos_spec),
        out_specs=(bq, ckv, ckv, cpos_spec), check_vma=False)
    o, ck, cv, cp = fn(q, k_new, v_new, cache["k"], cache["v"],
                       cache["pos"])
    return o, {"k": ck, "v": cv, "pos": cp}


def attn_apply(p, x, cfg: ArchConfig, q_pos, cache=None, window=None,
               seqshard=None):
    """x: (B,S,d). q_pos: (S,) int32 absolute positions (decode: (1,)).
    cache: {"k": (B,C,Hkv,hd), "v": ..., "pos": (B,C)} ring buffer or None.
    seqshard: None or (mesh, data_axes) — decode-time flash-decoding with
    the cache sequence dim sharded over 'model' (see attn_decode_seqshard).
    Returns (out, new_cache)."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    q = (x @ p["wq"]).reshape(B, S, Hq, hd)
    k = (x @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, q_pos, cfg.rope_theta)
    k = apply_rope(k, q_pos, cfg.rope_theta)

    if cache is None:
        kv_pos = jnp.broadcast_to(q_pos[None, :], (B, S))
        out = attention_ref(q, k, v, q_pos, kv_pos, causal=True, window=window)
        new_cache = None
    elif seqshard is not None and S == 1:
        mesh, data_axes = seqshard
        out, new_cache = attn_decode_seqshard(
            q, k, v, cache, q_pos[0], cfg, mesh, window=window,
            data_axes=data_axes)
        return out.reshape(B, S, Hq * hd) @ p["wo"], new_cache
    else:
        C = cache["k"].shape[1]
        pos = q_pos[0]
        slot = pos % C
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32),
            slot, axis=1)
        out = attention_ref(q, ck, cv, q_pos, cpos, causal=True, window=window)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    return out.reshape(B, S, Hq * hd) @ p["wo"], new_cache


def init_attn_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype,
                    window=None):
    C = min(cache_len, window) if window else cache_len
    hd, Hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    return {
        "k": jnp.zeros((batch, C, Hkv, hd), dtype),
        "v": jnp.zeros((batch, C, Hkv, hd), dtype),
        "pos": -jnp.ones((batch, C), jnp.int32),
    }


def cache_from_prefill(k, v, q_pos, cache_len: int, window=None):
    """Build a ring cache from full-sequence prefill keys/values."""
    B, S = k.shape[0], k.shape[1]
    C = min(cache_len, window) if window else cache_len
    if C >= S:
        pad = C - S
        kk = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.concatenate([
            jnp.broadcast_to(q_pos[None, :], (B, S)),
            -jnp.ones((B, pad), jnp.int32)], axis=1)
        return {"k": kk, "v": vv, "pos": pos.astype(jnp.int32)}
    # keep the last C entries at their ring slots
    idx = jnp.arange(S - C, S)
    slots = idx % C
    kk = jnp.zeros((B, C) + k.shape[2:], k.dtype).at[:, slots].set(k[:, idx])
    vv = jnp.zeros((B, C) + v.shape[2:], v.dtype).at[:, slots].set(v[:, idx])
    pos = jnp.zeros((B, C), jnp.int32).at[:, slots].set(
        jnp.broadcast_to(idx[None, :], (B, C)).astype(jnp.int32))
    return {"k": kk, "v": vv, "pos": pos}


# ------------------------------------------------------------- layer blocks


def init_dense_layer(key, cfg, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    return {
        "ln1": jnp.ones((d,), dtype),
        "attn": init_attn(ks[0], cfg, dtype),
        "ln2": jnp.ones((d,), dtype),
        "wi_gate": dense_init(ks[1], (d, cfg.d_ff), dtype),
        "wi_up": dense_init(ks[2], (d, cfg.d_ff), dtype),
        "wo_mlp": dense_init(ks[3], (cfg.d_ff, d), dtype),
    }


def init_moe_layer(key, cfg, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {
        "ln1": jnp.ones((d,), dtype),
        "attn": init_attn(ks[0], cfg, dtype),
        "ln2": jnp.ones((d,), dtype),
        "moe": init_moe(ks[1], cfg, dtype),
    }


class DecoderLM:
    """Unified decoder-only LM. family in dense|moe|vlm|ssm|hybrid."""

    def __init__(self, cfg: ArchConfig, mesh=None, remat: str = "full",
                 vocab_pad_multiple: int = 1, attn_window: Optional[int] = None,
                 loss_chunks: int = 8, moe_data_axes=("data",),
                 moe_impl: str = "capacity",
                 decode_cache_seqshard: bool = False,
                 parallel_block: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.remat = remat
        self.moe_data_axes = tuple(moe_data_axes)
        self.moe_impl = moe_impl
        self.decode_cache_seqshard = decode_cache_seqshard
        self.parallel_block = parallel_block
        self.window = attn_window if attn_window is not None else cfg.attn_window
        if cfg.family == "hybrid" and cfg.local_window and self.window is None:
            self.window = cfg.local_window
        self.vp = cfg.padded_vocab(vocab_pad_multiple) if vocab_pad_multiple > 1 \
            else cfg.vocab_size
        self.loss_chunks = loss_chunks
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------ params
    def _layer_init(self, cfg):
        fam = cfg.family
        if fam in ("dense", "vlm"):
            return init_dense_layer
        if fam == "moe":
            return init_moe_layer
        if fam == "ssm":
            return lambda k, c, dt: init_mamba_block(k, c, dt)
        raise ValueError(fam)

    def _hybrid_segments(self):
        cfg = self.cfg
        unit = cfg.hybrid_pattern
        n_groups, rem = divmod(cfg.n_layers, len(unit))
        segs = [(unit, n_groups)]
        if rem:
            segs.append((unit[:rem], 1))
        return segs

    def init(self, key):
        cfg, dtype = self.cfg, self.dtype
        ks = jax.random.split(key, 4)
        params = {
            "tok_embed": embed_init(ks[0], (self.vp, cfg.d_model), dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[1], (cfg.d_model, self.vp), dtype)

        if cfg.family == "hybrid":
            segs = self._hybrid_segments()
            params["segments"] = []
            for si, (unit, n) in enumerate(segs):
                seg = {}
                for bi, kind in enumerate(unit):
                    init_one = (init_rec_block if kind == "rec"
                                else init_dense_layer)
                    keys = jax.random.split(
                        jax.random.fold_in(ks[2], si * 16 + bi), n)
                    seg[f"b{bi}"] = jax.vmap(
                        lambda kk: init_one(kk, cfg, dtype))(keys)
                params["segments"].append(seg)
        else:
            layer_init = self._layer_init(cfg)
            keys = jax.random.split(ks[2], cfg.n_layers)
            params["layers"] = jax.vmap(
                lambda kk: layer_init(kk, cfg, dtype))(keys)
        return params

    # ------------------------------------------------------------ blocks
    def _seqshard(self):
        if self.decode_cache_seqshard and self.mesh is not None:
            return (self.mesh, self.moe_data_axes)
        return None

    def _dense_block(self, lp, x, q_pos, cache):
        cfg = self.cfg
        xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
        h, new_c = attn_apply(lp["attn"], xn, cfg, q_pos, cache, self.window,
                              seqshard=self._seqshard())
        if self.parallel_block:
            # PaLM/GPT-J-style parallel attention+MLP: both branches read
            # one norm and their partial sums share ONE tensor-parallel
            # all-reduce (§Perf H2 variant; numerics differ from the
            # sequential source models — off by default)
            m = swiglu(xn, lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])
            return x + h + m, new_c, jnp.float32(0.0)
        x = x + h
        x = x + swiglu(rms_norm(x, lp["ln2"], cfg.norm_eps),
                       lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])
        return x, new_c, jnp.float32(0.0)

    def _moe_block(self, lp, x, q_pos, cache):
        cfg = self.cfg
        h, new_c = attn_apply(lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps),
                              cfg, q_pos, cache, self.window,
                              seqshard=self._seqshard())
        x = x + h
        mo, aux = moe_apply(lp["moe"], rms_norm(x, lp["ln2"], cfg.norm_eps),
                            cfg, self.mesh, data_axes=self.moe_data_axes,
                            impl=self.moe_impl)
        return x + mo, new_c, aux

    def _block(self, kind):
        cfg = self.cfg
        if kind == "attn_dense":
            return self._dense_block
        if kind == "attn_moe":
            return self._moe_block
        if kind == "ssm":
            def f(lp, x, q_pos, cache):
                x, c = mamba_block(lp, x, cfg, cache)
                return x, c, jnp.float32(0.0)
            return f
        if kind == "rec":
            def f(lp, x, q_pos, cache):
                x, c = rec_block(lp, x, cfg, cache)
                return x, c, jnp.float32(0.0)
            return f
        raise ValueError(kind)

    def _uniform_kind(self):
        return {"dense": "attn_dense", "vlm": "attn_dense",
                "moe": "attn_moe", "ssm": "ssm"}[self.cfg.family]

    # ------------------------------------------------- stacked application
    def _apply_stack(self, params, x, q_pos, caches=None):
        """Run all layers. caches: matching stacked pytree or None.
        Returns (x, new_caches, aux_sum)."""
        cfg = self.cfg

        def run_scan(stacked_params, stacked_caches, x, kinds):
            def body(carry, inp):
                x, aux = carry
                lp, lc = inp
                for bi, kind in enumerate(kinds):
                    fn = self._block(kind)
                    if self.remat == "full":
                        fn = jax.checkpoint(fn)
                    cache_i = None if lc is None else lc[f"b{bi}"]
                    x, nc, a = fn(lp[f"b{bi}"], x, q_pos, cache_i)
                    if lc is not None:
                        lc = dict(lc)
                        lc[f"b{bi}"] = nc
                    aux = aux + a
                return (x, aux), lc

            (x, aux), new_caches = jax.lax.scan(
                body, (x, jnp.float32(0.0)), (stacked_params, stacked_caches))
            return x, new_caches, aux

        aux_tot = jnp.float32(0.0)
        if cfg.family == "hybrid":
            segs = self._hybrid_segments()
            new_caches = []
            for si, (unit, n) in enumerate(segs):
                kinds = ["rec" if k == "rec" else "attn_dense" for k in unit]
                seg_p = params["segments"][si]
                seg_c = None if caches is None else caches[si]
                x, nc, aux = run_scan(seg_p, seg_c, x, kinds)
                new_caches.append(nc)
                aux_tot = aux_tot + aux
            return x, (None if caches is None else new_caches), aux_tot

        kind = self._uniform_kind()
        # wrap single-block layers as one-block "groups" for shared code
        stacked = {"b0": params["layers"]}
        stacked_c = None if caches is None else {"b0": caches}
        x, nc, aux = run_scan(stacked, stacked_c, x, [kind])
        new_caches = None if caches is None else nc["b0"]
        return x, new_caches, aux

    # ------------------------------------------------------------- embed/out
    def _embed(self, params, tokens):
        return jnp.take(params["tok_embed"], tokens, axis=0)

    def _logits(self, params, x):
        head = (params["tok_embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head
        if self.vp != self.cfg.vocab_size:
            mask = jnp.arange(self.vp) < self.cfg.vocab_size
            logits = jnp.where(mask[None, ...], logits, NEG_INF)
        return logits

    # ---------------------------------------------------------------- loss
    def loss(self, params, batch):
        """batch: {"tokens": (B, T+1) int32[, "vision": (B, Nv, d)]}.
        Returns (loss, aux_dict)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens[:, :-1])
        labels = tokens[:, 1:]
        mask = jnp.ones(labels.shape, jnp.float32)
        if "mask" in batch:
            mask = batch["mask"][:, 1:].astype(jnp.float32)
        if cfg.family == "vlm":
            vis = batch["vision"].astype(x.dtype)
            B, Nv = vis.shape[0], vis.shape[1]
            x = jnp.concatenate([vis, x], axis=1)
            labels = jnp.concatenate(
                [jnp.zeros((B, Nv), labels.dtype), labels], axis=1)
            mask = jnp.concatenate([jnp.zeros((B, Nv), mask.dtype), mask], axis=1)

        S = x.shape[1]
        q_pos = jnp.arange(S, dtype=jnp.int32)
        x, _, aux = self._apply_stack(params, x, q_pos, None)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        ce, _ = chunked_softmax_xent(
            lambda xs: self._logits(params, xs), x, labels, mask,
            n_chunks=self.loss_chunks)
        total = ce + cfg.router_aux_coef * aux
        return total, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------- serving
    def init_cache(self, batch: int, cache_len: int):
        cfg, dtype = self.cfg, self.dtype

        def attn_c():
            return init_attn_cache(cfg, batch, cache_len, dtype, self.window)

        def one(kind):
            if kind in ("attn_dense", "attn_moe"):
                return attn_c()
            if kind == "ssm":
                return init_mamba_cache(cfg, batch, dtype)
            if kind == "rec":
                return init_rec_cache(cfg, batch, dtype)
            raise ValueError(kind)

        def stack(tree, n):
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)

        if cfg.family == "hybrid":
            caches = []
            for unit, n in self._hybrid_segments():
                seg = {}
                for bi, kindu in enumerate(unit):
                    kind = "rec" if kindu == "rec" else "attn_dense"
                    seg[f"b{bi}"] = stack(one(kind), n)
                caches.append(seg)
            return caches
        return stack(one(self._uniform_kind()), cfg.n_layers)

    def prefill(self, params, tokens, vision=None, cache_len=None):
        """tokens: (B, S). Returns (last-position logits (B, V), caches)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.family == "vlm" and vision is not None:
            x = jnp.concatenate([vision.astype(x.dtype), x], axis=1)
        B, S = x.shape[0], x.shape[1]
        cache_len = cache_len or S
        caches = self.init_cache(B, cache_len)
        q_pos = jnp.arange(S, dtype=jnp.int32)
        # run without caches (scan) then rebuild attention caches by a second
        # pass would double compute; instead run *with* per-layer cache build:
        x, new_caches, _ = self._apply_stack_prefill(params, x, q_pos, cache_len)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._logits(params, x[:, -1:, :])[:, 0]
        return logits, new_caches

    def _apply_stack_prefill(self, params, x, q_pos, cache_len):
        """Prefill pass that materializes serving caches per layer."""
        cfg = self.cfg

        def prefill_block(kind, lp, x):
            if kind in ("attn_dense", "attn_moe"):
                # recompute k/v for the cache from the (pre-norm) input
                xn = rms_norm(x, lp["ln1"], cfg.norm_eps)
                hd, Hkv = cfg.resolved_head_dim, cfg.n_kv_heads
                B, S, _ = x.shape
                k = (xn @ lp["attn"]["wk"]).reshape(B, S, Hkv, hd)
                v = (xn @ lp["attn"]["wv"]).reshape(B, S, Hkv, hd)
                if cfg.qk_norm:
                    k = rms_norm(k, lp["attn"]["k_norm"], cfg.norm_eps)
                k = apply_rope(k, q_pos, cfg.rope_theta)
                cache = cache_from_prefill(k, v, q_pos, cache_len, self.window)
                x, _, aux = self._block(kind)(lp, x, q_pos, None)
                return x, cache, aux
            x, cache, aux = self._block(kind)(lp, x, q_pos, None)
            return x, cache, aux

        def run_scan(stacked_params, x, kinds):
            def body(carry, lp):
                x = carry
                caches = {}
                for bi, kind in enumerate(kinds):
                    fn = functools.partial(prefill_block, kind)
                    if self.remat == "full":
                        fn = jax.checkpoint(fn)
                    x, c, _ = fn(lp[f"b{bi}"], x)
                    caches[f"b{bi}"] = c
                return x, caches

            return jax.lax.scan(body, x, stacked_params)

        if cfg.family == "hybrid":
            new_caches = []
            for si, (unit, n) in enumerate(self._hybrid_segments()):
                kinds = ["rec" if k == "rec" else "attn_dense" for k in unit]
                x, nc = run_scan(params["segments"][si], x, kinds)
                new_caches.append(nc)
            return x, new_caches, jnp.float32(0.0)

        kind = self._uniform_kind()
        x, nc = run_scan({"b0": params["layers"]}, x, [kind])
        return x, nc["b0"], jnp.float32(0.0)

    def decode_step(self, params, caches, token, pos):
        """token: (B, 1) int32; pos: scalar int32. Returns (logits, caches)."""
        cfg = self.cfg
        x = self._embed(params, token)
        q_pos = jnp.asarray(pos, jnp.int32)[None]
        x, new_caches, _ = self._apply_stack(params, x, q_pos, caches)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return self._logits(params, x)[:, 0], new_caches
