"""Device-resident FL round engine (DESIGN.md §8).

One federated round — local train -> aggregate -> eval -> best-model
tracking — is a single jitted ``round_step(state) -> state`` over a
`RoundState` pytree that never leaves the device: flattened client params,
best-on-validation tracking, the collaboration adjacency and comm counters
all live in ``state``; the driving python loop only re-dispatches the same
compiled program, so there are no per-round host syncs, no per-round
``np.asarray`` blocking transfers and no flatten/unflatten churn across
dispatch boundaries. Histories are preallocated device buffers pulled off
device only at the end (or every K rounds, to bound device memory).

When the engine carries a mesh (``FLEngine.shard_clients``), the same
round_step runs SPMD over the client axis: ``flat`` / ``best_flat`` /
``val_hist`` and the caller-specified ``aux`` leaves carry a
`NamedSharding` over the client mesh axes (threaded through the jit as
``in_shardings``/``out_shardings``), local training and evaluation stay
shard-local, and the only cross-client collectives are the Eq.-4 mixing
matmul and the GGC refresh (DESIGN.md §8, mesh layout).

Both the DPFL driver (`repro.core.dpfl.run_dpfl`) and every Table-1
baseline — including APFL and Ditto, whose personal/global side models
ride in ``aux`` — run on this engine via `repro.fl.baselines._loop`, so
all workloads exercise the same compiled path.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import inspect
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..analysis.guards import allow_transfers, no_transfer


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["t", "key", "flat", "best_val", "best_flat", "val_hist",
                 "aux"],
    meta_fields=[])
@dataclasses.dataclass
class RoundState:
    """Everything one federated round reads and writes, as one pytree.

    t:         () int32 — round counter (device-side; PRNG streams fold it)
    key:       base PRNG key; round t trains with fold_in(key, t)
    flat:      (N, P) client-stacked flattened params
    best_val:  (N,) best validation accuracy seen per client
    best_flat: (N, P) params at each client's best_val
    val_hist:  (K, N) rolling validation-accuracy buffer, or None
    aux:       method-specific pytree (DPFL: adjacency, comm counters,
               candidate graph, graph-refresh key, graph history;
               APFL: personal models; Ditto: personal models)

    All run-specific arrays (keys, graphs, counters) live HERE rather than
    as closure constants, so a cached `round_step` retraces/recompiles
    nothing across runs with the same static config.
    """
    t: jax.Array
    key: jax.Array
    flat: jax.Array
    best_val: jax.Array
    best_flat: jax.Array
    val_hist: Any
    aux: Any


def dealias_state(state: RoundState) -> RoundState:
    """Copy any leaf that shares its buffer with an earlier leaf.

    Initial states naturally alias (``best_flat`` starts as ``flat``, aux
    side models start from the same stack, aux keys reuse ``state.key``).
    A donating ``round_step`` (`make_round_step(donate=True)`) would then
    hand the SAME underlying buffer to XLA twice, which is a runtime error
    ("Attempt to donate the same buffer twice"), so every leaf must own its
    storage. Idempotent; a one-time O(state) cost per run."""
    seen = set()

    def visit(x):
        if isinstance(x, jax.Array):
            if id(x) in seen:
                return jnp.copy(x)
            seen.add(id(x))
        return x

    return jax.tree.map(visit, state)


def init_round_state(flat, key, *, hist_len: int = 0, aux=None) -> RoundState:
    """Fresh state from client-stacked flattened params (N, P). Every array
    leaf gets its own storage (a one-time copy), so the state is
    donation-safe twice over: no two leaves share a buffer (see
    `dealias_state`) and a donating run never consumes the CALLER's
    ``flat``/``key``/aux arrays."""
    N = flat.shape[0]

    def own(x):
        return jnp.copy(x) if isinstance(x, jax.Array) else x

    return jax.tree.map(own, RoundState(
        t=jnp.int32(0),
        key=key,
        flat=flat,
        # explicit dtype: a weak-typed fill would give the initial state
        # a different jit signature than the step's (strong) output and
        # force a second compile at round 1 (recompile_sentinel caught
        # this — DESIGN.md §13)
        best_val=jnp.full((N,), -jnp.inf, jnp.float32),
        best_flat=flat,
        val_hist=(jnp.zeros((hist_len, N), jnp.float32)
                  if hist_len else None),
        aux={} if aux is None else aux))


def _is_pspec(x) -> bool:
    return isinstance(x, P)


def round_state_shardings(mesh, client_axes, *, hist_len: int = 0,
                          aux=None, aux_specs=None) -> RoundState:
    """The `RoundState`-shaped pytree of `NamedSharding`s for a client mesh.

    flat/best_flat shard rows over ``client_axes`` (e.g. ('pod', 'data')),
    best_val shards its only axis, val_hist shards axis 1; t/key replicate.
    ``aux_specs`` (a pytree of `PartitionSpec` matching ``aux``) places the
    method-specific leaves; with ``aux`` given instead, every aux leaf
    replicates; with neither, the aux position is a single replicated
    sharding usable as a jit in/out_shardings pytree *prefix* (but not for
    `jax.device_put`, which needs the exact tree).
    """
    ca = tuple(client_axes)

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    if aux_specs is not None:
        aux_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), aux_specs,
                              is_leaf=_is_pspec)
    elif aux is not None:
        aux_sh = jax.tree.map(lambda _: ns(), aux)
    else:
        aux_sh = ns()
    return RoundState(
        t=ns(), key=ns(),
        flat=ns(ca, None),
        best_val=ns(ca),
        best_flat=ns(ca, None),
        val_hist=ns(None, ca) if hist_len else None,
        aux=aux_sh)


def shard_round_state(state: RoundState, mesh, client_axes,
                      aux_specs=None) -> RoundState:
    """`device_put` a concrete state onto its mesh shardings (the jit's
    ``in_shardings`` cannot re-lay-out arrays committed to a different
    device set, so the initial state is placed explicitly)."""
    sh = round_state_shardings(mesh, client_axes,
                               hist_len=0 if state.val_hist is None else 1,
                               aux=state.aux, aux_specs=aux_specs)
    return jax.device_put(state, sh)


def _touches_exchange_site(fn, depth: int = 2) -> bool:
    """True when ``fn`` is a registered ``@exchange_site`` or (within two
    levels of globals/closure references) calls one. Runtime mirror of
    fedlint rule F1 — intentionally forgiving: wrappers around registered
    mixers pass; only an aggregate that mixes through entirely
    unregistered code trips the `make_round_step` warning."""
    from ..analysis.registry import is_exchange_site
    if is_exchange_site(fn):
        return True
    if isinstance(fn, functools.partial):
        return _touches_exchange_site(fn.func, depth)
    code = getattr(fn, "__code__", None)
    if depth == 0 or code is None:
        return False
    cands = []
    glb = getattr(fn, "__globals__", {})
    for name in code.co_names:
        v = glb.get(name)
        if callable(v):
            cands.append(v)
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        if callable(v):
            cands.append(v)
    return any(_touches_exchange_site(c, depth - 1) for c in cands)


def _accepts(fn, name: str) -> bool:
    """True when ``fn``'s signature has a parameter called ``name``
    (aggregates optionally take ``prev``, local-train hooks optionally
    take ``aux``/``t`` — arity-detected so every existing callable keeps
    its old calling convention)."""
    try:
        return name in inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False


def make_round_step(engine, *, tau: int,
                    aggregate: Optional[Callable] = None,
                    local_train: Optional[Callable] = None,
                    post_train: Optional[Callable] = None,
                    eval_flat: Optional[Callable] = None,
                    hist_len: int = 0,
                    aux_specs=None,
                    participation_key: Optional[str] = None,
                    donate: bool = False):
    """Compile one federated round into ``round_step(state) -> state``.

    tau:         local epochs per round (static)
    aggregate:   (flat, aux, t) -> (flat, aux) — the traced communication
                 step (mixing matmul, graph refresh, comm accounting).
                 Default: no communication (local-only). An aggregate
                 whose signature has a ``prev`` parameter additionally
                 receives the round-start panel (``prev=state.flat``) —
                 the clipped mix rule's reference point (DESIGN.md §15).
    local_train: override of engine.train_fn(stacked, key, epochs). A
                 hook whose signature has an ``aux`` parameter is called
                 as ``local_train(stacked, key, epochs, aux=, t=)`` — the
                 data-level attack hook reads its per-round schedule from
                 ``aux["adv"]`` (DESIGN.md §15).
    post_train:  optional (flat, prev, aux, t) -> flat transform of the
                 trained panel, applied AFTER the participation hold and
                 BEFORE the aggregate's barrier — model poisoning
                 (DESIGN.md §15) rewrites the attacker's own rows here,
                 so an absent attacker still holds its round-start params
                 and every mix path sees the poisoned panel.
    hist_len:    >0 writes val accuracy into state.val_hist[t % hist_len]
    aux_specs:   pytree of `PartitionSpec` for state.aux when the engine
                 carries a mesh (default: aux replicates)
    participation_key: aux key holding a (rounds, N) bool availability
                 schedule (DESIGN.md §9). Round t trains everyone (the
                 vmapped update stays SPMD-uniform) but absent clients
                 HOLD their round-start params via `jnp.where` on the
                 flattened update; the same row is available to
                 ``aggregate`` (restricted mixing, realized-comm
                 counting) through aux. An all-ones schedule selects the
                 trained params everywhere — bitwise-identical to the
                 full-participation path on a fixed device layout.

    donate:      donate the input `RoundState` buffers to the call
                 (``donate_argnums=(0,)``). Every state leaf round-trips
                 with identical shape/dtype/sharding, so XLA aliases the
                 buffers in place of double-buffering the (N, P) stacks —
                 see `repro.analysis.guards.donation_report`. The input
                 state is consumed: callers must rebind (``state =
                 round_step(state)``, which `run_rounds` does) and initial
                 states must not share buffers across leaves
                 (`init_round_state` de-aliases; DESIGN.md §13).

    When ``engine.mesh`` is set (`FLEngine.shard_clients`), the jit is
    built with `round_state_shardings` as ``in_shardings``/``out_shardings``
    so the client axis stays sharded across rounds with no resharding at
    dispatch boundaries. The step is an `FLEngine.jit`: the client data
    enters the compiled program as arguments that are never donated.
    """
    lt = local_train if local_train is not None else engine.train_fn
    if aggregate is not None and not _touches_exchange_site(aggregate):
        warnings.warn(
            f"round_step aggregate {getattr(aggregate, '__name__', '?')!r}"
            f" is not a registered @exchange_site and references none — "
            f"its cross-client traffic is invisible to fedlint/commaudit "
            f"(declare it with repro.analysis.registry.exchange_site)",
            stacklevel=2)
    agg = aggregate if aggregate is not None else \
        (lambda flat, aux, t: (flat, aux))
    lt_takes_aux = _accepts(lt, "aux")
    agg_takes_prev = _accepts(agg, "prev")

    def round_step(state: RoundState) -> RoundState:
        # named scopes mark the round's phases in the compiled program's
        # op metadata (``op_name="jit(round_step)/round.train/..."``), so a
        # profile attributes each device op to one phase; they change no
        # arithmetic
        t = state.t
        with jax.named_scope("round.train"):
            stacked = engine.unflatten(state.flat)
            kt = jax.random.fold_in(state.key, t)
            if lt_takes_aux:
                stacked, _ = lt(stacked, kt, epochs=tau, aux=state.aux, t=t)
            else:
                stacked, _ = lt(stacked, kt, epochs=tau)
            flat = engine.flatten(stacked)
            if participation_key is not None:
                # absent clients hold their round-start params; the
                # schedule is client-sharded, so the select stays
                # shard-local
                m = state.aux[participation_key][t]
                flat = jnp.where(m[:, None], flat, state.flat)
            if post_train is not None:
                # after the hold: an absent attacker's row is its
                # round-start params either way, so poisoning composes
                # with participation
                flat = post_train(flat, state.flat, state.aux, t)
        # barriers: keep the train -> aggregate -> eval stages fusion-
        # isolated so the fused round tracks the staged host loop (and the
        # mesh-sharded build tracks the single-device one) as closely as
        # XLA allows — cross-stage fusion reorders fp accumulation, which
        # the greedy graph decisions amplify (DESIGN.md §8)
        flat = jax.lax.optimization_barrier(flat)
        # the aggregate's GGC refresh scopes itself ``round.refresh``
        with jax.named_scope("round.mix"):
            if agg_takes_prev:
                flat, aux = agg(flat, state.aux, t, prev=state.flat)
            else:
                flat, aux = agg(flat, state.aux, t)
        flat = jax.lax.optimization_barrier(flat)
        with jax.named_scope("round.eval"):
            ev = eval_flat(flat, aux) if eval_flat is not None else flat
            val_acc, _ = engine.eval_val_fn(engine.unflatten(ev))
            improved = val_acc > state.best_val
            val_hist = state.val_hist
            if hist_len:
                val_hist = val_hist.at[t % hist_len].set(val_acc)
            return RoundState(
                t=t + 1,
                key=state.key,
                flat=flat,
                best_val=jnp.where(improved, val_acc, state.best_val),
                best_flat=jnp.where(improved[:, None], ev, state.best_flat),
                val_hist=val_hist,
                aux=aux)

    dn = (0,) if donate else ()
    if engine.mesh is None:
        return engine.jit(round_step, donate_argnums=dn)
    sh = round_state_shardings(engine.mesh, engine.client_axes,
                               hist_len=hist_len, aux_specs=aux_specs)
    return engine.jit(round_step, in_shardings=(sh,), out_shardings=sh,
                      donate_argnums=dn)


def run_rounds(round_step, state: RoundState, rounds: int,
               on_flush: Optional[Callable] = None,
               flush_every: int = 0,
               guard_transfers: bool = True) -> RoundState:
    """Dispatch ``rounds`` compiled steps. The loop itself performs no host
    transfers — enforced, not just by convention: the dispatch loop runs
    inside `repro.analysis.guards.no_transfer`, so any hidden host sync or
    implicit transfer raises instead of silently serializing the rounds
    (``guard_transfers=False`` opts out). ``on_flush(state, done)`` (if
    given) is invoked every ``flush_every`` rounds — inside an
    `allow_transfers` escape, since pulling history buffers off device is
    its purpose — and once more at the end, outside the guarded region.

    On the profiler's host plane each dispatch is a ``dpfl.round`` step
    span (``step_num`` = its index in this call); with the profiler off
    it costs one `jax.profiler.TraceAnnotation` enter and exit."""
    guard = no_transfer() if guard_transfers else contextlib.nullcontext()
    last = 0
    with guard:
        for t in range(rounds):
            with jax.profiler.StepTraceAnnotation("dpfl.round", step_num=t):
                state = round_step(state)
            if flush_every and on_flush is not None and \
                    (t + 1) % flush_every == 0 and t + 1 < rounds:
                with allow_transfers():
                    on_flush(state, t + 1 - last)
                last = t + 1
    if on_flush is not None and rounds > last:
        on_flush(state, rounds - last)
    return state
