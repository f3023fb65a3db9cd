"""DPFL — Algorithm 1 (Decentralized Personalized Federated Learning).

Preprocess: same-init local models, tau_init local epochs, BGGC builds the
budgeted candidate graph Omega. Training loop: tau_train local epochs, GGC
re-selects C_k within Omega_k (optionally every P rounds — paper Table 3),
weighted aggregation over C_k ∪ {k} (Eq. 4). Best-on-validation models are
retained per client and used for final test accuracy (paper §4.1).

The round loop is the compiled device-resident engine (DESIGN.md §8): one
jitted ``round_step`` fuses local-train -> GGC refresh -> Eq.-4 mix ->
eval -> best-model update over a `RoundState` pytree. Communication
accounting lives in device-side counters; histories are preallocated
device buffers pulled off device only at the end (or every
``cfg.history_every`` rounds). ``run_dpfl_reference`` keeps the original
host-driven python loop as the equivalence/perf baseline
(`benchmarks/perf_hillclimb.py --dpfl` reports rounds/sec for both).

When the engine carries a mesh (`FLEngine.shard_clients`), the same
round_step runs SPMD with the client axis sharded over ('pod', 'data'):
local train/eval stay shard-local and the Eq.-4 mix plus GGC refresh are
the only cross-client collectives (`--mesh` modes of
`benchmarks/perf_hillclimb.py` and `benchmarks/bench_ggc_scaling.py`
report rounds/sec and graph-build time vs device count).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PSpec

from ..data.availability import ParticipationConfig, schedule_for_data
from ..fl import adversary as _adversary
from ..fl import compress as _compress
from ..fl import robust as _robust
from ..analysis.registry import exchange_site
from ..fl.adversary import AdversaryConfig
from ..fl.compress import CompressionConfig
from ..fl.engine import FLEngine
from ..fl.robust import MIX_RULES
from ..fl.round_engine import (RoundState, init_round_state, make_round_step,
                               run_rounds, shard_round_state)
from .graph import (all_clients_bggc, all_clients_bggc_sparse,
                    all_clients_graph, all_clients_graph_sparse,
                    count_neighbor_downloads, eq4_weights_unnormalized,
                    mixing_matrix, mix_flat, mix_flat_sparse,
                    sparse_eq4_unnormalized, sparse_mixing_weights)


@dataclass
class DPFLConfig:
    rounds: int = 20
    tau_init: int = 10
    tau_train: int = 5
    budget: Optional[int] = None      # B_c; None = inf (no constraint)
    refresh_period: int = 1           # P: run GGC every P rounds (Table 3)
    seed: int = 0
    graph_impl: str = "ggc"           # ggc | naive (oracle)
    random_graph: bool = False        # Fig. 3 ablation: random C_k
    track_history: bool = True
    mix_impl: Optional[str] = None    # kernels.ops.graph_mix impl override
    history_every: int = 0            # pull histories off device every K
    #                                   rounds (0 = once at the end); also
    #                                   bounds the device history buffers
    participation: Optional[ParticipationConfig] = None
    # partial client participation (DESIGN.md §9): a seeded (rounds, N)
    # availability schedule rides in aux; absent clients hold their
    # params, mixing/GGC restrict to available peers, comm counters count
    # only realized downloads. None = full participation (the schedule-
    # free compiled path). Preprocessing (tau_init + BGGC) runs before
    # the schedule starts and always sees every client.
    graph_repr: str = "dense"         # dense | sparse (DESIGN.md §12)
    # "sparse" stores the collaboration graph as (N, B) int32 neighbor
    # lists instead of (N, N) masks: the GGC refresh probes only the
    # <= B candidates per client, the Eq.-4 mix gathers only selected
    # peer rows (kernels.ops.sparse_graph_mix — O(N·B·P) instead of
    # O(N²·P)), and under a mesh the exchange rotates peer panels
    # keeping only requested rows. Decisions and comm counters are
    # layout-independent integers; "sparse" requires graph_impl="ggc".
    compression: Optional[CompressionConfig] = None
    # peer-exchange codec (DESIGN.md §11): lossy codecs transmit
    # C(x_k + e_k) — error-feedback residuals ride client-sharded in
    # aux["ef"] — receivers mix DECODED peers (self term exact), the GGC
    # refresh probes decoded peers, and byte accounting charges the
    # codec's wire size per realized download. None and the `identity`
    # codec are the SAME traced program (bitwise; identity normalizes
    # away before tracing). Preprocessing exchanges raw fp32 models (the
    # candidate graph is built on full-fidelity models, before any EF
    # state exists) and is charged at the raw rate.
    adversary: Optional[AdversaryConfig] = None
    # adversarial clients (DESIGN.md §15): a seeded (rounds, N) attack
    # schedule rides in aux["adv"]; attacks apply inside the compiled
    # round_step (label_flip via the local-train hook, grad_scale/
    # sign_flip/free_rider via the post_train hook + wire table). None
    # — and fraction=0.0 with the default mix_rule — is bitwise-
    # identical to the adversary-free step on one device (tested).
    # Preprocessing (tau_init + BGGC) runs before the schedule starts
    # and is attack-free: Omega is built on clean models, so robustness
    # benchmarks measure how the GGC refresh REACTS to attacks.
    mix_rule: str = "weighted"
    # Eq.-4 aggregation rule (DESIGN.md §15): "weighted" = the paper's
    # weighted average (default; bitwise-identical to the pre-robustness
    # path), "trimmed" = coordinate-wise trimmed mean over the decoded
    # peer panel (trim_frac per tail), "clipped" = per-peer update-norm
    # clipping relative to self (clip_mult x own update norm).
    trim_frac: float = 0.2            # mix_rule="trimmed": per-tail frac
    clip_mult: float = 1.0            # mix_rule="clipped": tau multiplier

@dataclass
class DPFLResult:
    test_acc: np.ndarray              # (N,) per-client acc of best-val model
    val_acc_history: list = field(default_factory=list)
    graph_history: list = field(default_factory=list)   # adjacency per round
    omega: Optional[np.ndarray] = None
    best_flat: Optional[np.ndarray] = None  # (N, P) best-val client models
    # communication accounting (models downloaded, the paper's cost unit):
    # preprocessing BGGC = 2(N-1) per client (Algorithm 3 streams every
    # peer in BOTH phases — w^Y accumulation, then batched decisions; a
    # client can hold at most B_c models, so the decision phase must
    # re-receive each batch), but the random-graph (Fig. 3) ablation only
    # downloads its `budget` sampled peers once; each training round =
    # |Omega_k| when GGC refreshes (needs all candidates) else |C_k|
    # (aggregation only), restricted to AVAILABLE (downloader AND peer)
    # clients under partial participation
    comm_downloads: list = field(default_factory=list)  # per-round totals
    comm_preprocess: int = 0
    # reward probes the GGC refresh EXECUTED each round (0 on rounds that
    # do not refresh): 4 x N x scan length, the N x B list scan of either
    # representation (the N^2 full scan for graph_impl="naive"), whatever
    # the candidate sets hold (`repro.analysis.counters`, "ggc.probes")
    ggc_probes: list = field(default_factory=list)      # per-round totals
    # byte-level accounting (DESIGN.md §11): every download moves one
    # encoded model, so bytes = downloads x the codec's static wire size
    # (`compress.bytes_per_model`) — exact python-int arithmetic at any
    # scale. Preprocessing moved raw fp32 models and is charged 4P each.
    comm_bytes: list = field(default_factory=list)      # per-round totals
    comm_bytes_preprocess: int = 0
    participation: Optional[np.ndarray] = None  # (rounds, N) realized
    #                                             schedule, if enabled
    malicious: Optional[np.ndarray] = None      # (N,) bool malicious set,
    #                                             if an adversary ran


def _nbr_to_adj_np(idx: np.ndarray, n: int) -> np.ndarray:
    """Host-side (N, B) neighbor lists -> (N, n) bool adjacency (diag
    True), for result reporting of sparse runs."""
    idx = np.asarray(idx)
    adj = np.zeros((idx.shape[0], n), bool)
    rows, cols = np.nonzero(idx >= 0)
    adj[rows, idx[rows, cols]] = True
    adj |= np.eye(idx.shape[0], n, dtype=bool)
    return adj


def _sparsity(adj: np.ndarray) -> float:
    n = adj.shape[0]
    off = adj.sum() - np.trace(adj)
    return 1.0 - off / (n * (n - 1))


def _symmetry(adj: np.ndarray) -> float:
    a = adj.copy().astype(bool)
    np.fill_diagonal(a, False)
    denom = a.sum()
    return float((a & a.T).sum() / denom) if denom else 1.0


def _comm_preprocess(cfg: DPFLConfig, N: int, budget: int) -> int:
    """Models downloaded during preprocessing. BGGC (Algorithm 3) streams
    every peer in BOTH communication phases — once to accumulate the
    shrink-set sum w^Y (lines 2-7) and once more for the batched greedy
    decisions: the whole point of BGGC is that a client never holds more
    than B_c models, so the decision phase cannot replay stored batches
    and must re-receive them. Realized downloads are therefore 2(N-1) per
    client (audited against `make_bggc`, which `tests/test_round_engine`
    asserts for engine and reference alike; DESIGN.md §9). The
    random-graph (Fig. 3) ablation downloads only the `budget` sampled
    peers of each client, once."""
    if cfg.random_graph:
        return N * min(budget, N - 1)
    return 2 * N * (N - 1)


def _fill_comm_bytes(result: DPFLResult, cfg: DPFLConfig, n_params: int):
    """Download counts -> bytes, shared verbatim by the compiled engine
    and the host reference so the two accountings cannot drift: training
    rounds move one codec-encoded model per realized download,
    preprocessing moved raw fp32 models (DESIGN.md §11)."""
    bpm = _compress.bytes_per_model(cfg.compression, n_params)
    result.comm_bytes = [int(d) * bpm for d in result.comm_downloads]
    result.comm_bytes_preprocess = result.comm_preprocess * 4 * n_params


def _comp_base_key(seed: int) -> jax.Array:
    """Base key of the codec's stochastic-rounding stream (round t folds
    it with t): branched off the run seed on a constant the preprocessing
    split never touches, so enabling compression changes no existing PRNG
    stream. Rides in aux["k_comp"] — never a closure constant — so the
    compiled step stays reusable across runs."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), 977)


def _sparse(cfg: DPFLConfig) -> bool:
    """True for the neighbor-list representation (DESIGN.md §12); also
    validates the combination — the literal-oracle graph_impl="naive"
    only exists dense, and the Fig.-3 random graph is repr-agnostic."""
    if cfg.graph_repr not in ("dense", "sparse"):
        raise ValueError(f"graph_repr must be 'dense' or 'sparse', "
                         f"got {cfg.graph_repr!r}")
    if cfg.graph_repr == "sparse" and cfg.graph_impl != "ggc" \
            and not cfg.random_graph:
        raise ValueError("graph_repr='sparse' supports graph_impl='ggc' "
                         "only (the naive oracle is dense-only)")
    return cfg.graph_repr == "sparse"


def _mix_rule(cfg: DPFLConfig) -> str:
    """Validated Eq.-4 aggregation rule (DESIGN.md §15)."""
    if cfg.mix_rule not in MIX_RULES:
        raise ValueError(f"mix_rule must be one of {MIX_RULES}, "
                         f"got {cfg.mix_rule!r}")
    if cfg.mix_rule == "trimmed" and not 0.0 <= cfg.trim_frac < 0.5:
        raise ValueError(f"trim_frac must be in [0, 0.5), "
                         f"got {cfg.trim_frac}")
    if cfg.mix_rule == "clipped" and cfg.clip_mult <= 0.0:
        raise ValueError(f"clip_mult must be > 0, got {cfg.clip_mult}")
    return cfg.mix_rule


def _nbr_width(N: int, budget: int) -> int:
    """Slot count B of the (N, B) neighbor lists: a client selects at
    most min(budget, N-1) off-diagonal peers."""
    return max(1, min(budget, N - 1))


def _cached_bggc(engine: FLEngine, cfg: DPFLConfig, reward_fn, budget: int):
    """Fetch-or-build the jitted all-clients BGGC preprocessing. The old
    path ran N eager un-jitted `bggc` calls in a python loop — N separate
    traces per run; this compiles the vmapped program ONCE per (budget,
    mix_impl, mesh) and memoizes it on the engine (selections are
    bitwise-identical to the loop; tested)."""
    cache = getattr(engine, "_bggc_cache", None)
    if cache is None:
        cache = engine._bggc_cache = {}
    sparse = _sparse(cfg)
    key = (budget, cfg.mix_impl, sparse, engine.mesh, engine.client_axes)
    if key not in cache:
        mesh, ca = engine.mesh, engine.client_axes

        if sparse:
            # neighbor-list BGGC: full candidacy is implicit, no (N, N)
            # candidate table; emits the (N, B) Omega lists directly
            def bggc(k_graph, flat, p):
                return all_clients_bggc_sparse(
                    k_graph, flat, p, reward_fn, budget,
                    mix_impl=cfg.mix_impl, mesh=mesh, client_axes=ca)
        else:
            def bggc(k_graph, flat, cand, p):
                return all_clients_bggc(k_graph, flat, p, cand, reward_fn,
                                        budget, mix_impl=cfg.mix_impl,
                                        mesh=mesh, client_axes=ca)

        cache[key] = engine.jit(bggc)
    return cache[key]


def _cached_refresh(engine: FLEngine, cfg: DPFLConfig, reward_fn,
                    budget: int):
    """Fetch-or-build the reference loop's jitted GGC refresh,
    ``refresh(key, flat, p, cand, active) -> new graph`` (``cand`` is the
    dense candidate masks or the Omega lists), memoized on the engine
    like `_cached_bggc`. The reward reads the client data, so the refresh
    runs as an `FLEngine.jit` program."""
    cache = getattr(engine, "_refresh_cache", None)
    if cache is None:
        cache = engine._refresh_cache = {}
    sparse = _sparse(cfg)
    key = (budget, cfg.graph_impl, cfg.mix_impl, sparse)
    if key not in cache:
        if sparse:
            def refresh(k_graph, flat, p, cand, active):
                return all_clients_graph_sparse(
                    k_graph, flat, p, cand, reward_fn, budget,
                    mix_impl=cfg.mix_impl, active=active)
        else:
            def refresh(k_graph, flat, p, cand, active):
                if active is not None:
                    cand = cand & active[None, :]
                return all_clients_graph(
                    k_graph, flat, p, cand, reward_fn, budget,
                    impl=cfg.graph_impl, mix_impl=cfg.mix_impl,
                    width=_nbr_width(flat.shape[0], budget))

        cache[key] = engine.jit(refresh)
    return cache[key]


def _preprocess(engine: FLEngine, cfg: DPFLConfig, reward_fn, budget: int):
    """Alg. 1 lines 1-5: same-init clients, tau_init local epochs, BGGC (or
    random) candidate graph Omega, one Eq.-4 mix over Omega. Shared by the
    compiled and the reference round loops, so both start from the exact
    same (omega, flat) and differ only in how the round loop executes."""
    data = engine.data
    N = data.n_clients
    p = engine.p
    key = jax.random.PRNGKey(cfg.seed)
    k_init, k_pre, k_graph, k_train = jax.random.split(key, 4)

    # host spans of the three stages on the profiler's host plane (each
    # encloses the stage's dispatch; the device may still be running it)
    with jax.profiler.TraceAnnotation("dpfl.preprocess.train"):
        stacked = engine.init_clients(k_init)
        stacked, _ = engine.local_train(stacked, k_pre,
                                        epochs=cfg.tau_init)
        flat = engine.flatten(stacked)

    sparse = _sparse(cfg)
    with jax.profiler.TraceAnnotation("dpfl.preprocess.bggc"):
        if cfg.random_graph:
            # Fig. 3 ablation: random Omega_k of size budget; both
            # representations sample the SAME peer sets from the same rng
            rng = np.random.default_rng(cfg.seed)
            B = _nbr_width(N, budget)
            omega = np.zeros((N, N), bool)
            nbr = np.full((N, B), -1, np.int32)
            for k_ in range(N):
                others = np.setdiff1d(np.arange(N), [k_])
                sel = rng.choice(others, size=min(budget, N - 1),
                                 replace=False)
                omega[k_, sel] = True
                omega[k_, k_] = True
                nbr[k_, :len(sel)] = np.sort(sel)
            omega = jnp.asarray(nbr) if sparse else jnp.asarray(omega)
        elif sparse:
            # BGGC emitting (N, B) Omega lists (no (N, N) table anywhere)
            omega = _cached_bggc(engine, cfg, reward_fn, budget)(
                k_graph, flat, p)
        else:
            # BGGC: batched preprocessing within the communication budget,
            # compiled once for all clients (vmapped; sharded under a mesh)
            omega = _cached_bggc(engine, cfg, reward_fn, budget)(
                k_graph, flat, jnp.ones((N, N), bool), p)

    with jax.profiler.TraceAnnotation("dpfl.preprocess.mix"):
        if sparse:
            self_w, nbr_w = sparse_mixing_weights(omega, p)
            flat = mix_flat_sparse(self_w, nbr_w, omega, flat,
                                   impl=cfg.mix_impl, mesh=engine.mesh,
                                   client_axes=engine.client_axes)
        else:
            A = mixing_matrix(omega, p)
            flat = mix_flat(A, flat, impl=cfg.mix_impl, mesh=engine.mesh,
                            client_axes=engine.client_axes)
    return omega, flat, k_graph, k_train


def _realized_downloads(g, active):
    """Downloads that actually happen on a partial-participation round:
    an AVAILABLE client downloads its AVAILABLE peers in graph ``g``
    (diagonal excluded — a client never downloads itself). With an
    all-ones mask this equals ``sum(g) - N`` exactly (integer arithmetic),
    the full-participation count."""
    N = g.shape[0]
    off = jnp.asarray(g, bool) & ~jnp.eye(N, dtype=bool)
    return jnp.sum(off & active[:, None] & active[None, :])


def _make_dpfl_aggregate(engine: FLEngine, cfg: DPFLConfig, reward_fn,
                         budget: int, hist_len: int):
    """The traced communication step of one DPFL round: conditional GGC
    refresh (Alg. 1 line 9, every cfg.refresh_period rounds), Eq.-4 mixing,
    and device-side comm-download accounting. Omega and the graph PRNG key
    are read from ``aux`` (not closed over), so the compiled step is
    reusable across runs. Under a client mesh, the GGC refresh and the
    Eq.-4 mix run their shard_map paths — the round's only cross-client
    collectives.

    With ``cfg.participation`` (DESIGN.md §9), round t reads its
    availability row from ``aux["part"]``: the GGC refresh selects only
    among AVAILABLE candidates in Omega_k and absent clients keep their
    previous C_k; the Eq.-4 matrix is row/col-restricted to available
    peers and renormalized; comm counters count only realized downloads.

    With a lossy ``cfg.compression`` (DESIGN.md §11), what peers exchange
    is the codec payload of the error-compensated models C(x + e): the
    GGC refresh probes the DECODED peer models (one download serves both
    probe and mix), the Eq.-4 off-diagonal term mixes decoded payloads —
    top-k through the `compressed_graph_mix` kernel, never densified for
    the mix — while the self term stays exact, and the EF residuals
    update in client-sharded aux["ef"] (absent clients transmit nothing,
    so their residuals hold). The `identity` codec normalizes to None and
    this function emits the exact pre-compression trace.

    With ``cfg.adversary`` (DESIGN.md §15), everything peers SEE — the
    refresh probes, the codec input, the off-diagonal mix — reads the
    WIRE table: identical to ``flat`` except that active free riders
    swap in their stale/noise upload; the self-mix term keeps reading
    the exact local row. ``cfg.mix_rule`` selects the Eq.-4 aggregation:
    "weighted" is the paper's rule verbatim, "trimmed"/"clipped"
    (`repro.fl.robust`) bound a poisoned peer's influence; the clipped
    rule's reference point is the round-start panel (``prev``).
    """
    p = engine.p
    mesh, ca = engine.mesh, engine.client_axes
    part = cfg.participation is not None
    comp = _compress.normalize(cfg.compression)
    ef = comp is not None and _compress.uses_ef(comp)
    adv = cfg.adversary
    fr = _adversary.free_rider_active(adv)
    rule = _mix_rule(cfg)

    # bare @exchange_site: this aggregate charges its own bytes — the
    # aux["comm"] counters below (fedlint F2 verifies the body does)
    @exchange_site
    def aggregate(flat, aux, t, prev=None):
        adj = aux["adj"]
        omega = aux["omega"]
        N = adj.shape[0]
        # Omega is _preprocess's BGGC or random graph: at most this many
        # off-diagonal peers a row, so the refresh scans rows as lists
        width = _nbr_width(N, budget)
        active = aux["part"][t] if part else None
        # the peer-visible upload table; trace-gated on a STATIC config
        # predicate so fraction=0.0 keeps the adversary-free trace
        wire = _adversary.wire_view(
            adv, flat, aux["adv"]["sched"][t],
            aux["adv"]["key"], t) if fr else flat
        if comp is None:
            probe_w, payload, dec, new_ef = wire, None, None, None
        else:
            payload, dec, new_ef = _compress.compress_exchange(
                comp, wire, aux["ef"] if ef else None,
                jax.random.fold_in(aux["k_comp"], t),
                mesh=mesh, client_axes=ca)
            probe_w = dec
            if ef and part:
                # an absent client transmits nothing: its residual holds
                new_ef = jnp.where(active[:, None], new_ef, aux["ef"])
        if cfg.random_graph:
            new_adj = adj  # Omega is the (fixed, random) graph
            comm_t = (_realized_downloads(adj, active) if part
                      else jnp.sum(adj) - N)
        else:
            refresh = (t % cfg.refresh_period) == 0
            # line 9 needs all of Omega_k; aggregation-only rounds download
            # the currently selected C_k — in both cases only the
            # available downloader/peer pairs move models
            if part:
                comm_t = jnp.where(refresh,
                                   _realized_downloads(omega, active),
                                   _realized_downloads(adj, active))

                def do_refresh(f):
                    # available clients re-select among their AVAILABLE
                    # candidates; absent clients keep their previous C_k
                    refreshed = all_clients_graph(
                        jax.random.fold_in(aux["k_graph"], 1000 + t), f, p,
                        omega & active[None, :], reward_fn, budget,
                        impl=cfg.graph_impl, mix_impl=cfg.mix_impl,
                        mesh=mesh, client_axes=ca, width=width)
                    return jnp.where(active[:, None], refreshed, adj)
            else:
                comm_t = jnp.where(refresh, jnp.sum(omega),
                                   jnp.sum(adj)) - N

                def do_refresh(f):
                    return all_clients_graph(
                        jax.random.fold_in(aux["k_graph"], 1000 + t), f, p,
                        omega, reward_fn, budget, impl=cfg.graph_impl,
                        mix_impl=cfg.mix_impl, mesh=mesh, client_axes=ca,
                        width=width)
            with jax.named_scope("round.refresh"):
                new_adj = jax.lax.cond(refresh, do_refresh,
                                       lambda f: adj, probe_w)
        # recv = what row k receives from peer i: decoded payloads under
        # compression, the wire table under free-riding, flat otherwise
        recv = dec if comp is not None else wire
        if rule == "trimmed":
            w_un = eq4_weights_unnormalized(new_adj, p, active=active)
            mixed = _robust.trimmed_mix_dense(w_un, flat, recv,
                                              cfg.trim_frac)
        else:
            A = mixing_matrix(new_adj, p, active=active)
            if rule == "clipped":
                gamma = _robust.clip_factors(recv, flat, prev,
                                             cfg.clip_mult)
                A = _robust.clipped_matrix(A, gamma)
            if comp is None:
                if fr:
                    # peers mix the wire table, the self term stays the
                    # exact local row — the same off-diagonal/diagonal
                    # split `mix_compressed` makes (DESIGN.md §11)
                    diag = jnp.diagonal(A)
                    A_off = A * (1.0 - jnp.eye(N, dtype=A.dtype))
                    mixed = mix_flat(A_off, wire, impl=cfg.mix_impl,
                                     mesh=mesh, client_axes=ca) \
                        + diag[:, None] * flat
                else:
                    mixed = mix_flat(A, flat, impl=cfg.mix_impl,
                                     mesh=mesh, client_axes=ca)
            else:
                mixed = _compress.mix_compressed(
                    comp, A, flat, payload, dec, impl=cfg.mix_impl,
                    mesh=mesh, client_axes=ca)
        aux = dict(aux, adj=new_adj,
                   comm=aux["comm"].at[t].set(comm_t.astype(jnp.int32)))
        if ef:
            aux["ef"] = new_ef
        if hist_len:
            aux["graph_hist"] = aux["graph_hist"].at[t % hist_len].set(
                new_adj)
        return mixed, aux

    return aggregate


def _make_dpfl_aggregate_sparse(engine: FLEngine, cfg: DPFLConfig,
                                reward_fn, budget: int, hist_len: int):
    """The neighbor-list counterpart of `_make_dpfl_aggregate`
    (DESIGN.md §12): the graph rides in aux as (N, B) int32 lists
    (``aux["nbr"]`` = current C_k, ``aux["omega_nbr"]`` = Omega), the GGC
    refresh probes only the <= B candidates per client, Eq.-4 mixes by
    gathering selected peer rows (`mix_flat_sparse` /
    `sparse_mix_compressed` — never a dense (N, N) operator), and the
    comm counters sum realized list lengths (`count_neighbor_downloads`,
    integer-identical to the dense accounting). Participation and
    compression semantics are unchanged from §9/§11: absent clients keep
    their previous lists and their row weights collapse to e_k; peers
    exchange C(x+e) and receivers mix decoded payloads with the self
    term exact."""
    p = engine.p
    mesh, ca = engine.mesh, engine.client_axes
    part = cfg.participation is not None
    comp = _compress.normalize(cfg.compression)
    ef = comp is not None and _compress.uses_ef(comp)
    adv = cfg.adversary
    fr = _adversary.free_rider_active(adv)
    rule = _mix_rule(cfg)

    # bare @exchange_site: this aggregate charges its own bytes — the
    # aux["comm"] counters below (fedlint F2 verifies the body does)
    @exchange_site
    def aggregate(flat, aux, t, prev=None):
        nbr = aux["nbr"]
        omega = aux["omega_nbr"]
        active = aux["part"][t] if part else None
        # peer-visible upload table (free riders swap in stale/noise
        # rows); static-gated so fraction=0.0 keeps the old trace
        wire = _adversary.wire_view(
            adv, flat, aux["adv"]["sched"][t],
            aux["adv"]["key"], t) if fr else flat
        if comp is None:
            probe_w, payload, dec, new_ef = wire, None, None, None
        else:
            payload, dec, new_ef = _compress.compress_exchange(
                comp, wire, aux["ef"] if ef else None,
                jax.random.fold_in(aux["k_comp"], t),
                mesh=mesh, client_axes=ca)
            probe_w = dec
            if ef and part:
                # an absent client transmits nothing: its residual holds
                new_ef = jnp.where(active[:, None], new_ef, aux["ef"])
        if cfg.random_graph:
            new_nbr = nbr  # Omega is the (fixed, random) graph
            comm_t = count_neighbor_downloads(nbr, active)
        else:
            refresh = (t % cfg.refresh_period) == 0
            comm_t = jnp.where(
                refresh, count_neighbor_downloads(omega, active),
                count_neighbor_downloads(nbr, active))

            def do_refresh(f):
                refreshed = all_clients_graph_sparse(
                    jax.random.fold_in(aux["k_graph"], 1000 + t), f, p,
                    omega, reward_fn, budget, mix_impl=cfg.mix_impl,
                    mesh=mesh, client_axes=ca, active=active)
                if part:
                    # absent clients keep their previous C_k lists
                    refreshed = jnp.where(active[:, None], refreshed, nbr)
                return refreshed

            with jax.named_scope("round.refresh"):
                new_nbr = jax.lax.cond(refresh, do_refresh,
                                       lambda f: nbr, probe_w)
        # recv = peer-visible model table row k gathers from (decoded
        # payloads under compression, the wire table under free-riding)
        recv = dec if comp is not None else wire
        if rule == "trimmed":
            p_un, w_un = sparse_eq4_unnormalized(new_nbr, p,
                                                 active=active)
            mixed = _robust.trimmed_mix_sparse(p_un, w_un, new_nbr, flat,
                                               recv, cfg.trim_frac)
        else:
            self_w, nbr_w = sparse_mixing_weights(new_nbr, p,
                                                  active=active)
            if rule == "clipped":
                N = flat.shape[0]
                safe = jnp.clip(new_nbr, 0, N - 1)
                gamma = _robust.clip_factors_sparse(
                    recv[safe], flat, prev, cfg.clip_mult)
                self_w, nbr_w = _robust.clipped_sparse_weights(
                    self_w, nbr_w, gamma)
            if comp is None:
                mixed = mix_flat_sparse(
                    self_w, nbr_w, new_nbr, flat,
                    peers=wire if fr else None,
                    impl=cfg.mix_impl, mesh=mesh, client_axes=ca)
            else:
                mixed = _compress.sparse_mix_compressed(
                    comp, self_w, nbr_w, new_nbr, flat, payload, dec,
                    impl=cfg.mix_impl, mesh=mesh, client_axes=ca)
        aux = dict(aux, nbr=new_nbr,
                   comm=aux["comm"].at[t].set(comm_t.astype(jnp.int32)))
        if ef:
            aux["ef"] = new_ef
        if hist_len:
            aux["graph_hist"] = aux["graph_hist"].at[t % hist_len].set(
                new_nbr)
        return mixed, aux

    return aggregate


def _dpfl_aux_specs(engine: FLEngine, hist_len: int,
                    participation: bool = False, comp=None,
                    sparse: bool = False, adversary: bool = False):
    """PartitionSpecs for the DPFL aux pytree on the client mesh: the
    graph (adjacency rows or neighbor lists), Omega, graph history, the
    participation/attack schedules and the error-feedback residuals
    shard their client axis; the graph/codec/adversary keys and the comm
    counters replicate."""
    if engine.mesh is None:
        return None
    ca = tuple(engine.client_axes)
    if sparse:
        specs = {"nbr": PSpec(ca, None), "omega_nbr": PSpec(ca, None),
                 "k_graph": PSpec(), "comm": PSpec()}
    else:
        specs = {"adj": PSpec(ca, None), "omega": PSpec(ca, None),
                 "k_graph": PSpec(), "comm": PSpec()}
    if hist_len:
        specs["graph_hist"] = PSpec(None, ca, None)
    if participation:
        specs["part"] = PSpec(None, ca)
    if comp is not None:
        specs["k_comp"] = PSpec()
        if _compress.uses_ef(comp):
            specs["ef"] = PSpec(ca, None)
    if adversary:
        specs["adv"] = {"sched": PSpec(None, ca), "key": PSpec()}
    return specs


def _cached_round_step(engine: FLEngine, cfg: DPFLConfig, budget: int,
                       hist_len: int, donate: bool = True):
    """Fetch-or-build the compiled DPFL round_step. Memoized on the engine
    keyed by the static knobs (incl. the client mesh); every run-varying
    array rides in RoundState, so repeated runs (sweeps, benchmarks,
    serving refreshes) reuse the compiled executable with zero retracing.
    ``donate`` (default on) aliases the input state's buffers into the
    outputs instead of double-buffering the (N, P) stacks; the initial
    state must be donation-safe (`init_round_state` de-aliases it)."""
    cache = getattr(engine, "_dpfl_round_step_cache", None)
    if cache is None:
        cache = engine._dpfl_round_step_cache = {}
    part = cfg.participation is not None
    comp = _compress.normalize(cfg.compression)
    sparse = _sparse(cfg)
    adv = cfg.adversary
    key = (cfg.tau_train, cfg.refresh_period, cfg.random_graph,
           cfg.graph_impl, cfg.mix_impl, budget, hist_len, part, comp,
           sparse, engine.mesh, engine.client_axes, donate,
           adv, _mix_rule(cfg), cfg.trim_frac, cfg.clip_mult)
    if key not in cache:
        reward_fn = engine.make_reward_fn()
        make_agg = (_make_dpfl_aggregate_sparse if sparse
                    else _make_dpfl_aggregate)
        aggregate = make_agg(engine, cfg, reward_fn, budget, hist_len)
        cache[key] = make_round_step(
            engine, tau=cfg.tau_train, aggregate=aggregate,
            local_train=(_adversary.make_adv_local_train(engine, adv)
                         if adv is not None else None),
            post_train=(_adversary.make_post_train(adv)
                        if adv is not None else None),
            hist_len=hist_len,
            aux_specs=_dpfl_aux_specs(engine, hist_len, part, comp,
                                      sparse, adv is not None),
            participation_key="part" if part else None,
            donate=donate)
    return cache[key]


def run_dpfl(engine: FLEngine, cfg: DPFLConfig) -> DPFLResult:
    """Algorithm 1 on the compiled round engine."""
    N = engine.data.n_clients
    budget = cfg.budget if cfg.budget is not None else N - 1
    reward_fn = engine.make_reward_fn()

    # ---- preprocess (Alg. 1 lines 1-5)
    omega, flat, k_graph, k_train = _preprocess(engine, cfg, reward_fn,
                                                budget)
    sparse = _sparse(cfg)
    result = DPFLResult(
        test_acc=None,
        omega=(_nbr_to_adj_np(np.asarray(omega), N) if sparse
               else np.asarray(omega)))
    result.comm_preprocess = _comm_preprocess(cfg, N, budget)

    # ---- training loop (Alg. 1 lines 6-12): one compiled round_step
    hist_len = _hist_len(cfg)
    if sparse:
        aux = {"nbr": omega, "omega_nbr": omega, "k_graph": k_graph,
               "comm": jnp.zeros((cfg.rounds,), jnp.int32)}
        if hist_len:
            aux["graph_hist"] = jnp.full(
                (hist_len, N, _nbr_width(N, budget)), -1, jnp.int32)
    else:
        aux = {"adj": omega, "omega": omega, "k_graph": k_graph,
               "comm": jnp.zeros((cfg.rounds,), jnp.int32)}
        if hist_len:
            aux["graph_hist"] = jnp.zeros((hist_len, N, N), bool)
    if cfg.participation is not None:
        sched = schedule_for_data(cfg.participation, cfg.rounds,
                                  engine.data)
        aux["part"] = jnp.asarray(sched)
        result.participation = np.asarray(sched)
    comp = _compress.normalize(cfg.compression)
    if comp is not None:
        aux["k_comp"] = _comp_base_key(cfg.seed)
        if _compress.uses_ef(comp):
            aux["ef"] = jnp.zeros_like(flat)
    if cfg.adversary is not None:
        sched_adv = _adversary.attack_schedule(cfg.adversary, cfg.rounds, N)
        aux["adv"] = {"sched": jnp.asarray(sched_adv),
                      "key": _adversary.adv_base_key(cfg.adversary.seed)}
        result.malicious = _adversary.malicious_mask(cfg.adversary, N)
    round_step = _cached_round_step(engine, cfg, budget, hist_len)
    state = init_round_state(flat, k_train, hist_len=hist_len, aux=aux)
    if engine.mesh is not None:
        # the jit's in_shardings cannot re-lay-out committed arrays, so
        # place the initial state on the client mesh explicitly
        state = shard_round_state(
            state, engine.mesh, engine.client_axes,
            aux_specs=_dpfl_aux_specs(engine, hist_len,
                                      cfg.participation is not None,
                                      comp, sparse,
                                      cfg.adversary is not None))

    def flush_histories(st, k):
        # the ONLY host transfers: every hist_len rounds + once at the
        # end. Sparse graph history comes off device as (N, B) lists and
        # is converted host-side so DPFLResult.graph_history always holds
        # (N, N) adjacencies (graph_stats, figures, tests)
        result.val_acc_history.extend(np.asarray(st.val_hist[:k]))
        hist = np.asarray(st.aux["graph_hist"][:k])
        if sparse:
            hist = [_nbr_to_adj_np(h, N) for h in hist]
        result.graph_history.extend(hist)

    state = run_rounds(
        round_step, state, cfg.rounds,
        on_flush=flush_histories if hist_len else None,
        flush_every=hist_len if (hist_len and cfg.history_every) else 0)

    result.comm_downloads = [int(c) for c in np.asarray(state.aux["comm"])]
    probes = round_step.counts.get("ggc.probes", 0)
    result.ggc_probes = [probes if t % cfg.refresh_period == 0 else 0
                         for t in range(cfg.rounds)]
    _fill_comm_bytes(result, cfg, engine.n_params)
    best = engine.unflatten(state.best_flat)
    test_acc, _ = engine.eval_test(best)
    result.test_acc = np.asarray(test_acc)
    result.best_flat = np.asarray(state.best_flat)
    return result


def run_dpfl_reference(engine: FLEngine, cfg: DPFLConfig) -> DPFLResult:
    """The original host-driven round loop (per-round dispatches, host-side
    comm accounting). Kept as the equivalence oracle for the compiled
    engine — `tests/test_round_engine.py` asserts matching comm counters —
    and as the old path in `benchmarks/perf_hillclimb.py --dpfl`."""
    N = engine.data.n_clients
    budget = cfg.budget if cfg.budget is not None else N - 1
    reward_fn = engine.make_reward_fn()
    p = engine.p

    omega, flat, k_graph, k_train = _preprocess(engine, cfg, reward_fn,
                                                budget)
    sparse = _sparse(cfg)
    stacked = engine.unflatten(flat)
    best_val = jnp.full((N,), -jnp.inf)
    best_flat = engine.flatten(stacked)
    result = DPFLResult(
        test_acc=None,
        omega=(_nbr_to_adj_np(np.asarray(omega), N) if sparse
               else np.asarray(omega)))
    result.comm_preprocess = _comm_preprocess(cfg, N, budget)
    adj = omega
    sched = None
    if cfg.participation is not None:
        sched = schedule_for_data(cfg.participation, cfg.rounds,
                                  engine.data)
        result.participation = np.asarray(sched)
    comp = _compress.normalize(cfg.compression)
    use_ef = comp is not None and _compress.uses_ef(comp)
    ef = jnp.zeros_like(flat) if use_ef else None
    k_comp = _comp_base_key(cfg.seed) if comp is not None else None
    adv = cfg.adversary
    rule = _mix_rule(cfg)
    fr = _adversary.free_rider_active(adv)
    sched_adv = flip_y = train_y = adv_key = None
    if adv is not None:
        # same host schedules / PRNG streams as the engine path
        sched_adv = _adversary.attack_schedule(adv, cfg.rounds, N)
        adv_key = _adversary.adv_base_key(adv.seed)
        result.malicious = _adversary.malicious_mask(adv, N)
        if adv.attack == "label_flip":
            train_y = engine.train_data[1]
            flip_y = jnp.asarray(_adversary.label_permutation(
                adv, engine.data.n_classes))[train_y]

    for t in range(cfg.rounds):
        prev_flat = flat
        adv_row = (jnp.asarray(sched_adv[t]) if adv is not None else None)
        if flip_y is not None:
            # data-level attack: attacking rows train on deranged labels
            ys = jnp.where(adv_row[:, None], flip_y, train_y)
            stacked, _ = engine.local_train_with_labels(
                stacked, jax.random.fold_in(k_train, t),
                epochs=cfg.tau_train, ys=ys)
        else:
            stacked, _ = engine.local_train(
                stacked, jax.random.fold_in(k_train, t),
                epochs=cfg.tau_train)
        flat = engine.flatten(stacked)
        active = None
        if sched is not None:
            # absent clients hold their round-start params
            active = jnp.asarray(sched[t])
            flat = jnp.where(active[:, None], flat, prev_flat)
        if adv is not None:
            # model poisoning after the hold (identity for label_flip)
            flat = _adversary.poison_update(adv, flat, prev_flat, adv_row)
        wire = (_adversary.wire_view(adv, flat, adv_row, adv_key, t)
                if fr else flat)
        probe_w, payload, dec = wire, None, None
        if comp is not None:
            # peers exchange the codec payload of C(x + e); the refresh
            # probes and the mix both consume it (DESIGN.md §11)
            payload, dec, new_ef = _compress.compress_exchange(
                comp, wire, ef, jax.random.fold_in(k_comp, t))
            probe_w = dec
            if use_ef:
                ef = new_ef if active is None else \
                    jnp.where(active[:, None], new_ef, ef)
        refresh = (not cfg.random_graph) and (t % cfg.refresh_period == 0)
        count_graph = omega if (refresh or cfg.random_graph) else adj
        if sparse:
            result.comm_downloads.append(
                int(count_neighbor_downloads(count_graph, active)))
        elif active is None:
            result.comm_downloads.append(
                int(np.asarray(count_graph).sum()) - N)
        else:
            result.comm_downloads.append(
                int(_realized_downloads(count_graph, active)))
        probes = 0
        if cfg.random_graph:
            adj = omega
        elif refresh:
            refresh_fn = _cached_refresh(engine, cfg, reward_fn, budget)
            refreshed = refresh_fn(
                jax.random.fold_in(k_graph, 1000 + t), probe_w, p, omega,
                active)
            adj = refreshed if active is None else \
                jnp.where(active[:, None], refreshed, adj)
            probes = refresh_fn.counts.get("ggc.probes", 0)
        result.ggc_probes.append(probes)
        recv = dec if comp is not None else wire
        if sparse:
            if rule == "trimmed":
                p_un, w_un = sparse_eq4_unnormalized(adj, p,
                                                     active=active)
                flat = _robust.trimmed_mix_sparse(p_un, w_un, adj, flat,
                                                  recv, cfg.trim_frac)
            else:
                self_w, nbr_w = sparse_mixing_weights(adj, p,
                                                      active=active)
                if rule == "clipped":
                    safe = jnp.clip(adj, 0, N - 1)
                    gamma = _robust.clip_factors_sparse(
                        recv[safe], flat, prev_flat, cfg.clip_mult)
                    self_w, nbr_w = _robust.clipped_sparse_weights(
                        self_w, nbr_w, gamma)
                if comp is None:
                    flat = mix_flat_sparse(self_w, nbr_w, adj, flat,
                                           peers=wire if fr else None,
                                           impl=cfg.mix_impl)
                else:
                    flat = _compress.sparse_mix_compressed(
                        comp, self_w, nbr_w, adj, flat, payload, dec,
                        impl=cfg.mix_impl)
        elif rule == "trimmed":
            w_un = eq4_weights_unnormalized(adj, p, active=active)
            flat = _robust.trimmed_mix_dense(w_un, flat, recv,
                                             cfg.trim_frac)
        else:
            A = mixing_matrix(adj, p, active=active)
            if rule == "clipped":
                gamma = _robust.clip_factors(recv, flat, prev_flat,
                                             cfg.clip_mult)
                A = _robust.clipped_matrix(A, gamma)
            if comp is None:
                if fr:
                    diag = jnp.diagonal(A)
                    A_off = A * (1.0 - jnp.eye(N, dtype=A.dtype))
                    flat = mix_flat(A_off, wire, impl=cfg.mix_impl) \
                        + diag[:, None] * flat
                else:
                    flat = mix_flat(A, flat, impl=cfg.mix_impl)
            else:
                flat = _compress.mix_compressed(comp, A, flat, payload,
                                                dec, impl=cfg.mix_impl)
        stacked = engine.unflatten(flat)

        val_acc, val_loss = engine.eval_val(stacked)
        improved = val_acc > best_val
        best_val = jnp.where(improved, val_acc, best_val)
        best_flat = jnp.where(improved[:, None], flat, best_flat)
        if cfg.track_history:
            result.val_acc_history.append(np.asarray(val_acc))
            result.graph_history.append(
                _nbr_to_adj_np(np.asarray(adj), N) if sparse
                else np.asarray(adj))

    _fill_comm_bytes(result, cfg, engine.n_params)
    best = engine.unflatten(best_flat)
    test_acc, _ = engine.eval_test(best)
    result.test_acc = np.asarray(test_acc)
    result.best_flat = np.asarray(best_flat)
    return result


def dpfl_round_step(engine: FLEngine, cfg: DPFLConfig):
    """The compiled/cached DPFL ``round_step`` for (engine, cfg) — the
    exact program `run_dpfl` dispatches each round. Public so dry-run and
    benchmark harnesses lower/compile the SAME code path instead of
    reimplementing a round (launch/fl_dryrun.py)."""
    N = engine.data.n_clients
    budget = cfg.budget if cfg.budget is not None else N - 1
    hist_len = _hist_len(cfg)
    return _cached_round_step(engine, cfg, budget, hist_len)


def abstract_round_state(engine: FLEngine, cfg: DPFLConfig) -> RoundState:
    """ShapeDtypeStruct skeleton of the DPFL RoundState — lets callers
    ``dpfl_round_step(...).lower(abstract_round_state(...))`` without
    running preprocessing (the 512-device dry-run)."""
    N = engine.data.n_clients
    P_ = engine.n_params
    hist_len = _hist_len(cfg)
    key_t = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)

    if _sparse(cfg):
        budget = cfg.budget if cfg.budget is not None else N - 1
        B = _nbr_width(N, budget)
        aux = {"nbr": sds((N, B), jnp.int32),
               "omega_nbr": sds((N, B), jnp.int32),
               "k_graph": key_t, "comm": sds((cfg.rounds,), jnp.int32)}
        if hist_len:
            aux["graph_hist"] = sds((hist_len, N, B), jnp.int32)
    else:
        aux = {"adj": sds((N, N), jnp.bool_),
               "omega": sds((N, N), jnp.bool_),
               "k_graph": key_t, "comm": sds((cfg.rounds,), jnp.int32)}
        if hist_len:
            aux["graph_hist"] = sds((hist_len, N, N), jnp.bool_)
    if cfg.participation is not None:
        aux["part"] = sds((cfg.rounds, N), jnp.bool_)
    comp = _compress.normalize(cfg.compression)
    if comp is not None:
        aux["k_comp"] = key_t
        if _compress.uses_ef(comp):
            aux["ef"] = sds((N, P_))
    if cfg.adversary is not None:
        aux["adv"] = {"sched": sds((cfg.rounds, N), jnp.bool_),
                      "key": key_t}
    return RoundState(
        t=sds((), jnp.int32), key=key_t, flat=sds((N, P_)),
        best_val=sds((N,)), best_flat=sds((N, P_)),
        val_hist=sds((hist_len, N)) if hist_len else None, aux=aux)


def _hist_len(cfg: DPFLConfig) -> int:
    if not cfg.track_history:
        return 0
    return (min(cfg.history_every, cfg.rounds)
            if cfg.history_every else cfg.rounds)


def graph_stats(result: DPFLResult) -> dict:
    out = {}
    if result.omega is not None:
        out["initial_sparsity"] = _sparsity(result.omega)
        out["initial_symmetry"] = _symmetry(result.omega)
    if result.graph_history:
        out["final_sparsity"] = _sparsity(result.graph_history[-1])
        out["final_symmetry"] = _symmetry(result.graph_history[-1])
    return out
