"""Communication-compression codecs for the peer model exchange
(DESIGN.md §11).

The paper's cost unit is "models downloaded"; real decentralized systems
pay per byte, and DisPFL-style sparse exchange shows decentralized PFL
tolerates heavily compressed peer models. This module is the codec
registry the round engine compresses with:

  * ``identity`` — lossless; the traced round step is BITWISE-identical
    to the compression-free path (the codec is normalized away before
    tracing, so XLA sees the exact same program).
  * ``topk``     — magnitude sparsification: each client transmits the k
    = ceil(topk_frac * P) largest-|.| coordinates of its flattened
    params as (value, index) pairs. Error-feedback residuals accumulate
    what was dropped (client-sharded, riding in ``RoundState.aux["ef"]``).
  * ``int8``     — stochastic uniform quantization to ``quant_bits`` bits
    with a per-model fp32 scale (unbiased: E[decode] = input).

What travels the wire each round is ``C(x_k + e_k)`` (the error-
compensated compressed model); receivers mix DECODED peer models while
every client keeps its OWN model exact (the Eq.-4 self term never moves,
so it is never compressed — `mix_compressed`). The GGC refresh probes
also evaluate decoded peers: one download serves both the probe and the
mix, matching the download-count accounting.

Byte accounting is static per codec (`bytes_per_model`): every download
moves one encoded model, so per-round bytes are the realized download
count times a static payload size — exact integer arithmetic at any
scale (DESIGN.md §11).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..analysis.registry import exchange_site
from ..kernels import ops as _kops
from ..kernels.ref import densify_topk

CODECS = ("identity", "topk", "int8")


@dataclass(frozen=True)
class CompressionConfig:
    """Peer-exchange codec spec (frozen: hashable, so it keys the
    engine's compiled-step caches).

    codec:          one of CODECS.
    topk_frac:      topk only — fraction of P transmitted, in (0, 1].
    quant_bits:     int8 only — wire bits per coordinate, in [2, 8]
                    (storage stays int8; accounting charges ``quant_bits``).
    error_feedback: lossy codecs only — carry the compression residual
                    into the next round's encode (EF; Stich et al.).
    """
    codec: str = "identity"
    topk_frac: float = 0.1
    quant_bits: int = 8
    error_feedback: bool = True

    def __post_init__(self):
        if self.codec not in CODECS:
            raise ValueError(f"codec must be one of {CODECS}, "
                             f"got {self.codec!r}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], "
                             f"got {self.topk_frac}")
        if not 2 <= self.quant_bits <= 8:
            raise ValueError(f"quant_bits must be in [2, 8], "
                             f"got {self.quant_bits}")


def lossless(cfg) -> bool:
    """True when ``cfg`` compresses nothing (None or identity)."""
    return cfg is None or cfg.codec == "identity"


def normalize(cfg):
    """The traced-program key: identity IS the compression-free path, so
    it normalizes to None and reuses the exact pre-compression trace —
    the bitwise invariant holds by construction, not by luck."""
    return None if lossless(cfg) else cfg


def uses_ef(cfg) -> bool:
    return not lossless(cfg) and cfg.error_feedback


def topk_k(cfg, n_params: int) -> int:
    """Transmitted coordinates per model: ceil(frac * P), in [1, P]."""
    return max(1, min(n_params, int(math.ceil(cfg.topk_frac * n_params))))


def bytes_per_model(cfg, n_params: int) -> int:
    """Wire bytes of ONE transmitted model (None = raw fp32). Static per
    codec — python int arithmetic, never a device counter (int32 would
    overflow at production scale; DESIGN.md §11)."""
    if lossless(cfg):
        return 4 * n_params
    if cfg.codec == "topk":
        return 8 * topk_k(cfg, n_params)        # fp32 value + int32 index
    # int8: quant_bits per coordinate + one fp32 scale per model
    return (n_params * cfg.quant_bits + 7) // 8 + 4


# ------------------------------------------------------------------ codecs


def encode(cfg, x, key):
    """x: (N, P) client-stacked flattened params -> payload pytree.
    ``key`` feeds the int8 stochastic rounding (topk is deterministic)."""
    if cfg.codec == "topk":
        k = topk_k(cfg, x.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(x), k)
        vals = jnp.take_along_axis(x, idx, axis=1)
        return {"vals": vals, "idx": idx.astype(jnp.int32)}
    if cfg.codec == "int8":
        levels = (1 << (cfg.quant_bits - 1)) - 1
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1) / levels, 1e-30)
        y = x / scale[:, None]                   # in [-levels, levels]
        lo = jnp.floor(y)
        up = jax.random.uniform(key, x.shape) < (y - lo)
        q = jnp.clip(lo + up, -levels, levels)   # clip guards fp edges only
        return {"q": q.astype(jnp.int8), "scale": scale}
    raise ValueError(cfg.codec)


def decode(cfg, payload, n_params: int):
    """payload -> dense (N, P) fp32 — what a receiving peer reconstructs."""
    if cfg.codec == "topk":
        return densify_topk(payload["vals"], payload["idx"], n_params)
    if cfg.codec == "int8":
        return payload["q"].astype(jnp.float32) * payload["scale"][:, None]
    raise ValueError(cfg.codec)


def _pin_rows(t, mesh, client_axes):
    """Constrain one encode/decode product to client-row sharding."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    spec = P(tuple(client_axes), *([None] * (t.ndim - 1)))
    return jax.lax.with_sharding_constraint(t, NamedSharding(mesh, spec))


def compress_exchange(cfg, flat, ef, key, *, mesh=None, client_axes=None):
    """One round's transmit side: encode the error-compensated models.

    flat: (N, P); ef: (N, P) residuals or None (EF off).
    Returns (payload, dec, new_ef): the wire payload, the decoded (N, P)
    models every receiver reconstructs, and the updated residuals
    (``new_ef`` is None iff ``ef`` is). Every op here is row-local in
    the protocol — encode/decode run on the owning client. That is NOT
    automatic in the lowering: XLA's sharding propagation gives up on
    top_k's sort and the densify scatter, replicating their operands,
    which put raw fp32 panels and duplicate payload copies on the wire
    in a compressed config (caught by `analysis.commaudit`). Threading
    the client ``mesh`` pins row sharding on everything produced here so
    the compiled exchange moves compressed parts exactly once."""
    xin = flat + ef if ef is not None else flat
    if mesh is not None and cfg.codec == "topk":
        # row-local by construction: the sort partitioner replicates
        # top_k's operand and the densify scatter replicates the payload
        # even under output sharding constraints, so run the whole
        # encode/decode on the owning shard. Per-row ops — bit-identical
        # to the unsharded path (the engine-vs-reference parity tests
        # cover the topk codec). int8 stays outside: its dither must draw
        # from the full-(N, P) key stream to match the reference.
        from jax.sharding import PartitionSpec as P

        ca = tuple(client_axes)

        def enc_dec(x_blk):
            p = encode(cfg, x_blk, None)
            return p, decode(cfg, p, x_blk.shape[1])

        payload, dec = jax.shard_map(
            enc_dec, mesh=mesh, in_specs=P(ca, None),
            out_specs=({"vals": P(ca, None), "idx": P(ca, None)},
                       P(ca, None)))(xin)
    else:
        payload = encode(cfg, xin, key)
        dec = decode(cfg, payload, flat.shape[1])
        if mesh is not None:
            pin = lambda t: _pin_rows(t, mesh, client_axes)  # noqa: E731
            payload = {k: pin(v) for k, v in payload.items()}
            dec = pin(dec)
    new_ef = xin - dec if ef is not None else None
    return payload, dec, new_ef


# ------------------------------------------------------------------ mixing


@exchange_site(charges="caller")
def _mix_int8_offdiag(A_off, payload, dec, *, impl, mesh, client_axes):
    """Off-diagonal Eq.-4 term for the int8 codec. Single device: reuse
    the already-decoded models through the standard graph_mix. Under a
    client mesh, all-gather the COMPRESSED payload (int8 q + fp32 scale —
    ~4x less collective traffic than dense fp32 panels) and dequantize
    shard-locally before the row-block matmul."""
    if mesh is None:
        return _kops.graph_mix(A_off, dec, impl=impl)
    from jax.sharding import PartitionSpec as P

    ca = tuple(client_axes)

    def row_block(a_blk, q_blk, s_blk):
        q_full = jax.lax.all_gather(q_blk, ca, axis=0, tiled=True)
        s_full = jax.lax.all_gather(s_blk, ca, axis=0, tiled=True)
        d = q_full.astype(jnp.float32) * s_full[:, None]
        return _kops.graph_mix(a_blk, d, impl=impl)

    # check_vma=False: graph_mix may dispatch to the Pallas kernel, which
    # has no shard_map replication rule
    return jax.shard_map(row_block, mesh=mesh,
                         in_specs=(P(ca, None), P(ca, None), P(ca)),
                         out_specs=P(ca, None), check_vma=False)(
                         A_off, payload["q"], payload["scale"])


@exchange_site(charges="caller")
def mix_compressed(cfg, A, flat, payload, dec, *, impl=None, mesh=None,
                   client_axes=None):
    """Eq.-4 mixing over compressed peers: off-diagonal contributions use
    the DECODED payloads, the self term uses the client's exact local
    model (a client never downloads — or compresses — the model it
    already holds). topk routes through `kernels.ops.compressed_graph_mix`
    so the dense (N, P) peer matrix is never materialized for the mix;
    int8 dequantizes shard-locally from the gathered payload."""
    N = A.shape[0]
    diag = jnp.diagonal(A)
    A_off = A * (1.0 - jnp.eye(N, dtype=A.dtype))
    if cfg.codec == "topk":
        off = _kops.compressed_graph_mix(
            A_off, payload["vals"], payload["idx"], flat.shape[1],
            impl=impl, mesh=mesh, client_axes=client_axes)
    elif cfg.codec == "int8":
        off = _mix_int8_offdiag(A_off, payload, dec, impl=impl, mesh=mesh,
                                client_axes=client_axes)
    else:
        raise ValueError(cfg.codec)
    return off + diag[:, None] * flat


def _payload_parts(cfg, payload, n_params: int):
    """(parts, shard-local decode) of a codec payload — what the sparse
    exchange rotates shard-to-shard instead of dense fp32 panels
    (DESIGN.md §12): topk moves (vals, idx) = 2K words per peer, int8
    moves (int8 q, fp32 scale)."""
    if cfg.codec == "topk":
        return ((payload["vals"], payload["idx"]),
                lambda v, i: densify_topk(v, i, n_params))
    if cfg.codec == "int8":
        return ((payload["q"], payload["scale"]),
                lambda q, s: q.astype(jnp.float32) * s[:, None])
    raise ValueError(cfg.codec)


@exchange_site(charges="caller")
def sparse_mix_compressed(cfg, self_w, nbr_w, nbr_idx, flat, payload, dec,
                          *, impl=None, mesh=None, client_axes=None):
    """Neighbor-list Eq.-4 mixing over compressed peers (DESIGN.md §12):
    the <= B selected peer rows are DECODED payloads while the self term
    reads the client's exact local model, mirroring `mix_compressed` for
    the (N, B) sparse representation. self_w: (N,); nbr_w/nbr_idx:
    (N, B); flat: (N, P) exact local models; payload/dec: the codec wire
    payload and its decoded (N, P) table from `compress_exchange`.

    Single device reuses ``dec`` (already reconstructed for the GGC
    probes). Under a client mesh the rotation exchange of
    `kernels.ops.sparse_graph_mix` carries the COMPRESSED payload parts
    and decodes each visiting panel shard-locally, so the simulated
    collective shrinks with the codec exactly like the dense compressed
    paths."""
    if mesh is None:
        return _kops.sparse_graph_mix(self_w, nbr_w, nbr_idx, flat,
                                      (dec,), impl=impl)
    parts, decode = _payload_parts(cfg, payload, flat.shape[1])
    return _kops.sparse_graph_mix(self_w, nbr_w, nbr_idx, flat, parts,
                                  decode, impl=impl, mesh=mesh,
                                  client_axes=client_axes)
