"""The unified greedy-decision kernel and the compiled round engine:
(1) `greedy_decision_step` (through all three GGC entry points) must
reproduce the literal Algorithm-2 oracle selection-for-selection;
(2) the jitted `round_step` loop must reproduce the original host-driven
round loop — comm counters, graph history and best-model tracking."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import DPFLConfig, run_dpfl, run_dpfl_reference
from repro.core.graph import (all_clients_bggc, make_bggc, make_ggc,
                              make_ggc_heterogeneous, make_ggc_naive)
from repro.data import make_federated_classification
from repro.fl.engine import FLEngine
from repro.fl.round_engine import (init_round_state, make_round_step,
                                   run_rounds)
from repro.models.classifier import MLP


_TOY_N = 6


def _toy():
    key = jax.random.PRNGKey(3)
    flat_w = jax.random.normal(key, (_TOY_N, 12))
    p = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1),
                                  (_TOY_N,))) + 0.1
    p = p / p.sum()
    target = jax.random.normal(jax.random.fold_in(key, 2), (12,))

    def reward(fw, k):
        return -jnp.sum((fw - target) ** 2) - 0.05 * k * jnp.sum(fw ** 2)

    return flat_w, p, reward


_TOY = _toy()
# compile caches across hypothesis examples: the unified kernel compiles
# ONCE (its budget is traced — the tentpole's point); the literal oracle
# and the batched BGGC bake the budget in, so one compile per budget.
_UNIFIED = jax.jit(lambda key, ki, c, w, pp, b: make_ggc_heterogeneous(
    _TOY[2], _TOY_N)(key, ki, c, w, pp, b))
_ORACLES, _BGGCS, _GGCS = {}, {}, {}


@settings(max_examples=6, deadline=None)
@given(budget=st.integers(1, 5), seed=st.integers(0, 1000))
def test_unified_kernel_matches_naive_all_variants(budget, seed):
    """Property: for any (budget, seed), the shared decision kernel —
    exercised as static-budget GGC, batched BGGC, and traced-budget
    heterogeneous GGC — selects exactly what the recompute-from-scratch
    Algorithm-2 oracle selects (Theorem 1 by construction)."""
    flat_w, p, reward = _TOY
    if budget not in _ORACLES:
        _ORACLES[budget] = jax.jit(make_ggc_naive(reward, budget))
        _GGCS[budget] = jax.jit(make_ggc(reward, budget))
        _BGGCS[budget] = jax.jit(make_bggc(reward, budget))
    for k in range(_TOY_N):
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 7), k)
        cand = jnp.ones(_TOY_N, bool)
        want = np.asarray(_ORACLES[budget](key, jnp.int32(k), cand,
                                           flat_w, p))
        for name, got in [
                ("ggc", _GGCS[budget](key, jnp.int32(k), cand, flat_w, p)),
                ("bggc", _BGGCS[budget](key, jnp.int32(k), cand, flat_w, p)),
                ("heterogeneous", _UNIFIED(key, jnp.int32(k), cand, flat_w,
                                           p, jnp.int32(budget)))]:
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=name)


@pytest.fixture(scope="module")
def small_setting():
    data = make_federated_classification(
        seed=5, n_clients=6, n_clusters=2, partition="pathological",
        classes_per_client=3, feature_dim=8, n_train=16, n_val=16,
        n_test=16, noise=2.0, assign_level="cluster")
    return FLEngine(MLP(8, 16, 10), data, lr=0.05, batch_size=8)


@pytest.mark.parametrize("refresh_period", [1, 2])
def test_round_step_comm_matches_host_loop(small_setting, refresh_period):
    """Regression: the device-side comm counters of the compiled round
    loop equal the old python-loop host accounting, round for round."""
    eng = small_setting
    cfg = DPFLConfig(rounds=4, tau_init=2, tau_train=1, budget=3, seed=0,
                     refresh_period=refresh_period)
    new = run_dpfl(eng, cfg)
    ref = run_dpfl_reference(eng, cfg)
    assert new.comm_downloads == ref.comm_downloads
    assert new.comm_preprocess == ref.comm_preprocess
    for a, b in zip(new.graph_history, ref.graph_history):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(new.val_acc_history, ref.val_acc_history):
        np.testing.assert_allclose(a, b, atol=1e-6)
    np.testing.assert_allclose(new.test_acc, ref.test_acc, atol=1e-6)


def test_bggc_preprocess_counts_both_phases(small_setting):
    """Comm-accounting audit (vs the paper's cost model): `make_bggc`
    streams every peer in BOTH Algorithm-3 phases — once accumulating the
    shrink-set sum w^Y, once for the batched decisions (a client holds at
    most B_c models, so the decision batches must be re-received) — so
    preprocessing charges 2(N-1) downloads per client, identically for
    the compiled engine and the host reference."""
    eng = small_setting
    cfg = DPFLConfig(rounds=1, tau_init=1, tau_train=1, budget=3, seed=0)
    new = run_dpfl(eng, cfg)
    ref = run_dpfl_reference(eng, cfg)
    N = _TOY_N
    assert new.comm_preprocess == ref.comm_preprocess == 2 * N * (N - 1)


def test_random_graph_comm_accounting(small_setting):
    """Fig.-3 ablation comm accounting: preprocessing only downloads the
    `budget` sampled peers per client (N * budget, NOT the BGGC's
    N * (N-1)), and the compiled engine agrees with the host reference
    round for round."""
    eng = small_setting
    cfg = DPFLConfig(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
                     random_graph=True)
    new = run_dpfl(eng, cfg)
    ref = run_dpfl_reference(eng, cfg)
    N = _TOY_N
    assert new.comm_preprocess == ref.comm_preprocess == N * 3
    assert new.comm_downloads == ref.comm_downloads
    np.testing.assert_allclose(new.test_acc, ref.test_acc, atol=1e-6)
    # a budget larger than the peer count cannot download more than N-1
    cfg_big = DPFLConfig(rounds=1, tau_init=1, tau_train=1, budget=N + 3,
                         seed=0, random_graph=True)
    big = run_dpfl(eng, cfg_big)
    assert big.comm_preprocess == N * (N - 1)


def test_vmapped_bggc_matches_sequential_loop(small_setting):
    """The compiled all-clients BGGC (one traced program) selects exactly
    what a python loop of N single-client calls selects — same
    fold_in(key, k) streams, bitwise-identical Omega."""
    eng = small_setting
    N = _TOY_N
    reward = eng.make_reward_fn()
    # BGGC runs on tau_init-trained clients (Alg. 1 line 3); same-init
    # untrained clients would make every marginal gain exactly zero and
    # the coin-flip stream pure fp noise
    stacked = eng.init_clients(jax.random.PRNGKey(7))
    stacked, _ = eng.local_train(stacked, jax.random.PRNGKey(8), epochs=2)
    flat = eng.flatten(stacked)
    full_mask = jnp.ones((N, N), bool)
    k_graph = jax.random.PRNGKey(11)
    for budget in (2, 4):
        bggc = eng.jit(make_bggc(reward, budget))
        loop = jnp.stack([
            bggc(jax.random.fold_in(k_graph, k), jnp.int32(k),
                 full_mask[k], flat, eng.p)
            for k in range(N)])
        vmapped = eng.jit(lambda kk, f, b=budget: all_clients_bggc(
            kk, f, eng.p, full_mask, reward, b))(k_graph, flat)
        np.testing.assert_array_equal(np.asarray(vmapped), np.asarray(loop),
                                      err_msg=f"budget={budget}")


def test_apfl_ditto_on_engine_match_host_loop(small_setting):
    """Regression for the APFL/Ditto engine port: the compiled round_step
    reproduces the original host-driven loops (federated/global branch in
    state.flat, personal models in aux) to fp tolerance."""
    from repro.fl.baselines import (_global_avg, _prox_engine, run_apfl,
                                    run_ditto)
    eng = small_setting
    rounds, tau, seed = 2, 1, 0
    p = eng.p
    key = jax.random.PRNGKey(seed)

    # --- original APFL host loop (pre-port reference)
    alpha = 0.5
    stacked = eng.init_clients(key)
    v_flat = eng.flatten(stacked)
    best_val = jnp.full((_TOY_N,), -jnp.inf)
    best_flat = v_flat
    for t in range(rounds):
        stacked, _ = eng.local_train(stacked, jax.random.fold_in(key, t),
                                     epochs=tau)
        w_flat = _global_avg(eng.flatten(stacked), p)
        stacked = eng.unflatten(w_flat)
        mix = alpha * v_flat + (1 - alpha) * w_flat
        pers, _ = eng.local_train(eng.unflatten(mix),
                                  jax.random.fold_in(key, 7000 + t),
                                  epochs=tau)
        v_flat = eng.flatten(pers)
        mix = alpha * v_flat + (1 - alpha) * w_flat
        val_acc, _ = eng.eval_val(eng.unflatten(mix))
        improved = val_acc > best_val
        best_val = jnp.where(improved, val_acc, best_val)
        best_flat = jnp.where(improved[:, None], mix, best_flat)
    acc, _ = eng.eval_test(eng.unflatten(best_flat))
    got = run_apfl(eng, rounds=rounds, tau=tau, seed=seed, alpha=alpha)
    np.testing.assert_allclose(got["test_acc"], np.asarray(acc), atol=1e-6)

    # --- original Ditto host loop (pre-port reference)
    lam = 0.75
    glob = eng.init_clients(key)
    pers_flat = eng.flatten(glob)
    lt_prox = _prox_engine(eng, lam)
    best_val = jnp.full((_TOY_N,), -jnp.inf)
    best_flat = pers_flat
    for t in range(rounds):
        glob, _ = eng.local_train(glob, jax.random.fold_in(key, t),
                                  epochs=tau)
        g_flat = _global_avg(eng.flatten(glob), p)
        glob = eng.unflatten(g_flat)
        pers, _ = lt_prox(eng.unflatten(pers_flat),
                          jax.random.fold_in(key, 5000 + t),
                          epochs=tau, ref_flat=g_flat)
        pers_flat = eng.flatten(pers)
        val_acc, _ = eng.eval_val(eng.unflatten(pers_flat))
        improved = val_acc > best_val
        best_val = jnp.where(improved, val_acc, best_val)
        best_flat = jnp.where(improved[:, None], pers_flat, best_flat)
    acc, _ = eng.eval_test(eng.unflatten(best_flat))
    got = run_ditto(eng, rounds=rounds, tau=tau, seed=seed, lam=lam)
    np.testing.assert_allclose(got["test_acc"], np.asarray(acc), atol=1e-6)


def test_no_history_run_is_device_resident(small_setting):
    """track_history=False: same counters/accuracy, nothing accumulated
    on the host during the loop."""
    eng = small_setting
    kw = dict(rounds=4, tau_init=2, tau_train=1, budget=3, seed=0)
    full = run_dpfl(eng, DPFLConfig(**kw))
    lean = run_dpfl(eng, DPFLConfig(**kw, track_history=False))
    assert lean.comm_downloads == full.comm_downloads
    np.testing.assert_allclose(lean.test_acc, full.test_acc, atol=1e-6)
    assert lean.val_acc_history == [] and lean.graph_history == []


def test_history_chunked_flush_equals_oneshot(small_setting):
    """history_every=K (bounded device buffers, periodic pulls) must
    reconstruct the same per-round history as the one-shot pull."""
    eng = small_setting
    kw = dict(rounds=5, tau_init=2, tau_train=1, budget=3, seed=0)
    one = run_dpfl(eng, DPFLConfig(**kw))
    chunked = run_dpfl(eng, DPFLConfig(**kw, history_every=2))
    assert len(chunked.graph_history) == 5
    for a, b in zip(one.graph_history, chunked.graph_history):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(one.val_acc_history, chunked.val_acc_history):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_generic_round_engine_local_only(small_setting):
    """The baselines' engine path: a local-only round_step tracks the
    best-on-validation model and advances the device-side round counter."""
    eng = small_setting
    key = jax.random.PRNGKey(0)
    flat0 = eng.flatten(eng.init_clients(key))
    step = make_round_step(eng, tau=1)
    state = run_rounds(step, init_round_state(flat0, key), 3)
    assert int(state.t) == 3
    assert state.flat.shape == flat0.shape
    assert bool(jnp.all(jnp.isfinite(state.best_val)))
    # best_val is the running max of the (recorded) evaluations
    acc, _ = eng.eval_val_fn(eng.unflatten(state.best_flat))
    assert bool(jnp.all(acc <= state.best_val + 1e-6))


def test_donating_round_step_bitwise_equals_nondonating(small_setting):
    """`make_round_step(donate=True)` must be a pure memory optimization:
    the donating step's results are BITWISE identical to the plain step's
    across a multi-round run, every `RoundState` leaf is donatable (same
    path/shape/dtype on output), and the donated input is consumed."""
    from repro.analysis.guards import donation_report
    from repro.fl.baselines import _global_avg

    eng = small_setting

    def agg(flat, aux, t):
        return _global_avg(flat, eng.p), aux

    key = jax.random.PRNGKey(11)
    flat0 = eng.flatten(eng.init_clients(key))
    step_n = make_round_step(eng, tau=1, aggregate=agg)
    step_d = make_round_step(eng, tau=1, aggregate=agg, donate=True)

    # static audit: every state leaf round-trips shape/dtype-identical,
    # so donation aliases the whole state in place of double-buffering
    rep = donation_report(step_n, init_round_state(flat0, key))
    assert rep["blocked"] == []
    assert rep["donatable_bytes"] > 0

    out_n = run_rounds(step_n, init_round_state(flat0, key), 4)
    out_d = run_rounds(step_d, init_round_state(flat0, key), 4)
    flat_n = jax.tree_util.tree_flatten_with_path(out_n)[0]
    flat_d = jax.tree_util.tree_flatten_with_path(out_d)[0]
    assert [p for p, _ in flat_n] == [p for p, _ in flat_d]
    for (path, a), (_, b) in zip(flat_n, flat_d):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=jax.tree_util.keystr(path))

    # donation consumes the input buffers — rebinding is mandatory,
    # which `run_rounds` does (state = round_step(state))
    s_in = init_round_state(flat0, key)
    out = step_d(s_in)
    assert s_in.flat.is_deleted()
    assert not out.flat.is_deleted()


def test_init_round_state_dealiases_aliased_leaves(small_setting):
    """Initial states naturally alias (best_flat starts as flat; aux side
    models / graph keys reuse the same arrays). `init_round_state` must
    de-alias them — donating one underlying buffer twice is a runtime
    error — and a donating step over such a state must run."""
    eng = small_setting
    key = jax.random.PRNGKey(0)
    flat0 = eng.flatten(eng.init_clients(key))
    st = init_round_state(flat0, key, aux={"side": flat0, "gkey": key})
    leaves = jax.tree_util.tree_leaves(st)
    assert len({id(x) for x in leaves}) == len(leaves)
    step = make_round_step(eng, tau=1, donate=True)
    out = step(st)
    assert int(out.t) == 1


def test_round_step_takes_client_data_as_undonated_arguments():
    """The client data enter the compiled round_step as arguments, never
    as constants folded into the program: the lowered program does not
    grow with the dataset, and a donating step donates the state only."""
    sizes = []
    for n in (16, 256):
        data = make_federated_classification(
            seed=0, n_clients=4, feature_dim=16, n_train=n, n_val=n,
            n_test=8)
        eng = FLEngine(MLP(16, 32, 10), data, lr=0.05, batch_size=8)
        key = jax.random.PRNGKey(0)
        state = init_round_state(eng.flatten(eng.init_clients(key)), key)
        lowered = make_round_step(eng, tau=1, donate=True).lower(state)
        (data_info, state_info), _ = lowered.args_info
        assert not any(a.donated for a in jax.tree.leaves(data_info))
        assert all(a.donated for a in jax.tree.leaves(state_info))
        sizes.append(len(lowered.as_text()))
    # 2 x 4 x 240 x 16 more fp32 values would add >100 KB as constants
    assert abs(sizes[1] - sizes[0]) < 0.01 * sizes[0], sizes


@pytest.mark.parametrize("wrap", ["jit", "vmap"])
def test_client_data_refuses_a_trace_engine_jit_did_not_start(
        small_setting, wrap):
    """A plain ``jax.jit`` (or any other trace) over a fn that reads the
    client data would fold the dataset into the program as constants:
    the read raises there, and the same fn runs under `FLEngine.jit`."""
    eng = small_setting
    reward = eng.make_reward_fn()
    flat = eng.flatten(eng.init_clients(jax.random.PRNGKey(0)))
    ks = jnp.arange(_TOY_N)
    traced = {"jit": jax.jit(lambda f: reward(f[0], 0)),
              "vmap": lambda f: jax.vmap(reward)(f, ks)}[wrap]
    with pytest.raises(RuntimeError, match="engine.jit"):
        traced(flat)
    want = eng.jit(jax.vmap(reward))(flat, ks)
    np.testing.assert_array_equal(
        np.asarray(eng.jit(lambda f: traced(f))(flat)).reshape(-1),
        np.asarray(want)[:1 if wrap == "jit" else _TOY_N])
