"""Budget-sparse neighbor representation (DESIGN.md §12): the (N, B)
neighbor-list layout must be a pure re-encoding of the dense (N, N) masks
— greedy decisions BITWISE identical (the sparse scan's skipped
non-candidates are exact no-ops of the dense scan), mixing weights and
comm counters integer/row-exact, the gather-based sparse mix kernel equal
to its oracle — and the sparse round engine must agree with the sparse
host reference on comm counts and bytes for every codec, with
participation and compression composed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CompressionConfig, DPFLConfig, ParticipationConfig,
                        run_dpfl, run_dpfl_reference)
from repro.core import dpfl as dpfl_mod
from repro.core.graph import (adjacency_from_neighbors, all_clients_bggc,
                              all_clients_bggc_sparse, all_clients_graph,
                              all_clients_graph_sparse,
                              count_neighbor_downloads, mixing_matrix,
                              neighbors_from_adjacency,
                              sparse_mixing_weights)
from repro.data import make_federated_classification
from repro.fl.engine import FLEngine
from repro.kernels import ops, ref
from repro.models.classifier import MLP


# ------------------------------------------------------ representation


@settings(max_examples=20, deadline=None)
@given(n=st.integers(2, 12), budget=st.integers(1, 6),
       seed=st.integers(0, 1000))
def test_neighbor_list_adjacency_round_trip(n, budget, seed):
    """Property: for any adjacency whose rows keep <= budget off-diagonal
    peers (the constrained-greedy invariant), mask -> list -> mask is the
    identity (with the forced diagonal), and the realized-download count
    is the off-diagonal edge count — the two layouts cannot disagree."""
    rng = np.random.default_rng(seed)
    adj = np.zeros((n, n), bool)
    for k in range(n):
        others = np.setdiff1d(np.arange(n), [k])
        take = rng.integers(0, min(budget, n - 1) + 1)
        adj[k, rng.choice(others, take, replace=False)] = True
    adj |= np.eye(n, dtype=bool)
    idx = neighbors_from_adjacency(jnp.asarray(adj), budget)
    back = adjacency_from_neighbors(idx, n)
    np.testing.assert_array_equal(np.asarray(back), adj)
    assert int(count_neighbor_downloads(idx)) == int(
        adj.sum() - np.trace(adj))
    # slots are ascending global ids with -1 padding at the tail
    iv = np.asarray(idx)
    for row in iv:
        real = row[row >= 0]
        assert list(real) == sorted(real)
        assert (row[len(real):] == -1).all()


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 10), budget=st.integers(1, 5),
       seed=st.integers(0, 1000), restrict=st.booleans())
def test_sparse_mixing_weights_match_dense_rows(n, budget, seed, restrict):
    """Property: (self_w, nbr_w) scattered back to a dense row equals the
    `mixing_matrix` row (p-weighted, renormalized, forced diagonal),
    including the §9 active-restricted form; rows always sum to 1."""
    rng = np.random.default_rng(seed)
    adj = np.zeros((n, n), bool)
    for k in range(n):
        others = np.setdiff1d(np.arange(n), [k])
        take = rng.integers(0, min(budget, n - 1) + 1)
        adj[k, rng.choice(others, take, replace=False)] = True
    p = jnp.asarray(rng.uniform(0.1, 1.0, n), jnp.float32)
    active = jnp.asarray(rng.uniform(size=n) < 0.7) if restrict else None
    idx = neighbors_from_adjacency(jnp.asarray(adj | np.eye(n, dtype=bool)),
                                   budget)
    self_w, nbr_w = sparse_mixing_weights(idx, p, active=active)
    A = np.asarray(mixing_matrix(jnp.asarray(adj | np.eye(n, dtype=bool)),
                                 p, active=active))
    dense_rows = np.diag(np.asarray(self_w))
    iv, wv = np.asarray(idx), np.asarray(nbr_w)
    for k in range(n):
        for b in range(iv.shape[1]):
            if iv[k, b] >= 0:
                dense_rows[k, iv[k, b]] += wv[k, b]
    np.testing.assert_allclose(dense_rows, A, atol=1e-6)
    np.testing.assert_allclose(dense_rows.sum(axis=1), 1.0, atol=1e-6)


# ------------------------------------------------------------- kernel


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("shape", [(6, 3, 40), (16, 4, 2100), (5, 7, 33)])
def test_sparse_graph_mix_matches_oracle(impl, shape):
    """The gather-based kernel equals the einsum oracle through the ops
    dispatch — pad paths (P % block != 0), sentinel slots, duplicate
    indices (which ADD), and B > N all covered."""
    N, B, P = shape
    key = jax.random.PRNGKey(sum(shape))
    W = jax.random.normal(key, (N, P))
    peers = jax.random.normal(jax.random.fold_in(key, 9), (N, P))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (N, B), -1, N)
    nw = jax.random.normal(jax.random.fold_in(key, 2), (N, B))
    sw = jax.random.normal(jax.random.fold_in(key, 3), (N,))
    for ix in (idx, jnp.zeros((N, B), jnp.int32),          # duplicates add
               jnp.full((N, B), -1, jnp.int32)):          # all-sentinel
        got = ops.sparse_graph_mix(sw, nw, ix, W, (peers,), impl=impl)
        want = ref.sparse_graph_mix_ref(sw, nw, ix, W, peers)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


# --------------------------------------------------- greedy decisions


def _small_engine():
    data = make_federated_classification(
        seed=5, n_clients=6, n_clusters=2, partition="pathological",
        classes_per_client=3, feature_dim=8, n_train=16, n_val=16,
        n_test=16, noise=2.0, assign_level="cluster")
    return FLEngine(MLP(8, 16, 10), data, lr=0.05, batch_size=8)


@pytest.fixture(scope="module")
def small_setting():
    return _small_engine()


def _trained_flat(eng, epochs=2):
    st_ = eng.init_clients(jax.random.PRNGKey(7))
    st_, _ = eng.local_train(st_, jax.random.PRNGKey(8), epochs=epochs)
    return eng.flatten(st_)


def test_sparse_ggc_bitwise_matches_dense(small_setting):
    """The sparse scan visits only candidate slots, yet selects BITWISE
    what the dense all-N scan selects: skipped non-candidates are exact
    no-ops and the per-candidate fold_in streams are identical."""
    eng = small_setting
    N = 6
    flat = _trained_flat(eng)
    reward = eng.make_reward_fn()
    rng = np.random.default_rng(0)
    for budget in (2, 4):
        cand = np.zeros((N, N), bool)
        for k in range(N):
            others = np.setdiff1d(np.arange(N), [k])
            cand[k, rng.choice(others, min(budget, N - 1),
                               replace=False)] = True
        candj = jnp.asarray(cand)
        dense = eng.jit(lambda f, c, b=budget: all_clients_graph(
            jax.random.PRNGKey(1), f, eng.p, c, reward, b))(flat, candj)
        sp = eng.jit(lambda f, c, b=budget: all_clients_graph_sparse(
            jax.random.PRNGKey(1), f, eng.p,
            neighbors_from_adjacency(c, b), reward, b))(flat, candj)
        np.testing.assert_array_equal(
            np.asarray(dense | jnp.eye(N, dtype=bool)),
            np.asarray(adjacency_from_neighbors(sp, N)),
            err_msg=f"budget={budget}")


def test_sparse_ggc_active_matches_dense_restriction(small_setting):
    """§9 composition: restricting candidates via ``active=`` equals the
    dense path's pre-masked candidate set, selection for selection (for
    the available clients — absent rows are the caller's jnp.where)."""
    eng = small_setting
    N = 6
    flat = _trained_flat(eng)
    reward = eng.make_reward_fn()
    cand = jnp.asarray(~np.eye(N, dtype=bool))
    active = jnp.asarray(np.array([1, 0, 1, 1, 0, 1], bool))
    dense = eng.jit(lambda f, c, a: all_clients_graph(
        jax.random.PRNGKey(2), f, eng.p, c & a[None, :], reward, 3))(
            flat, cand, active)
    sp = eng.jit(lambda f, c, a: all_clients_graph_sparse(
        jax.random.PRNGKey(2), f, eng.p,
        neighbors_from_adjacency(c, N - 1), reward, 3, active=a))(
            flat, cand, active)
    d = np.asarray(dense | jnp.eye(N, dtype=bool))
    s = np.asarray(adjacency_from_neighbors(sp, N))
    act = np.asarray(active)
    np.testing.assert_array_equal(d[act], s[act])


def test_sparse_bggc_bitwise_matches_dense(small_setting):
    """Preprocessing: the list-emitting BGGC selects exactly what the
    dense full-candidacy BGGC selects."""
    eng = small_setting
    N = 6
    flat = _trained_flat(eng)
    reward = eng.make_reward_fn()
    for budget in (2, 4):
        dense = eng.jit(lambda f, b=budget: all_clients_bggc(
            jax.random.PRNGKey(11), f, eng.p, jnp.ones((N, N), bool),
            reward, b))(flat)
        sp = eng.jit(lambda f, b=budget: all_clients_bggc_sparse(
            jax.random.PRNGKey(11), f, eng.p, reward, b))(flat)
        np.testing.assert_array_equal(
            np.asarray(dense | jnp.eye(N, dtype=bool)),
            np.asarray(adjacency_from_neighbors(sp, N)),
            err_msg=f"budget={budget}")


# ------------------------------------------------------- round engine


CODECS = [None, CompressionConfig(codec="identity"),
          CompressionConfig(codec="topk", topk_frac=0.3),
          CompressionConfig(codec="int8", quant_bits=8)]


@pytest.mark.parametrize("comp", CODECS,
                         ids=["none", "identity", "topk", "int8"])
def test_sparse_engine_matches_reference_every_codec(small_setting, comp):
    """Acceptance invariant: the compiled sparse engine and the sparse
    host reference agree on comm counts AND wire bytes for every codec
    (integer-exact — both derive from realized list lengths), and on
    graph history and accuracy."""
    eng = small_setting
    cfg = DPFLConfig(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
                     graph_repr="sparse", compression=comp)
    new = run_dpfl(eng, cfg)
    ref_ = run_dpfl_reference(eng, cfg)
    assert new.comm_downloads == ref_.comm_downloads
    assert new.comm_bytes == ref_.comm_bytes
    assert new.comm_preprocess == ref_.comm_preprocess
    assert new.comm_bytes_preprocess == ref_.comm_bytes_preprocess
    for a, b in zip(new.graph_history, ref_.graph_history):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(new.test_acc, ref_.test_acc, atol=1e-6)


def test_sparse_random_graph_matches_dense(small_setting):
    """Decision-free path: the random Omega is the same peer set in both
    layouts, so comm counters are integer-identical and accuracy agrees
    to fp tolerance (the mix reduces in a different order — §12)."""
    eng = small_setting
    kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
              random_graph=True)
    dense = run_dpfl(eng, DPFLConfig(**kw))
    sp = run_dpfl(eng, DPFLConfig(**kw, graph_repr="sparse"))
    assert dense.comm_downloads == sp.comm_downloads
    assert dense.comm_preprocess == sp.comm_preprocess
    assert dense.comm_bytes == sp.comm_bytes
    np.testing.assert_array_equal(dense.omega, sp.omega)
    for a, b in zip(dense.graph_history, sp.graph_history):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(dense.test_acc, sp.test_acc, atol=1e-6)


def test_sparse_participation_composes(small_setting):
    """§9 composition: sparse engine == sparse reference under partial
    participation (+ compression), and the rate=1.0 schedule reproduces
    the schedule-free sparse path bitwise on a single device."""
    eng = small_setting
    cfg = DPFLConfig(
        rounds=4, tau_init=2, tau_train=1, budget=3, seed=0,
        graph_repr="sparse",
        participation=ParticipationConfig(rate=0.5, model="bernoulli"),
        compression=CompressionConfig(codec="topk", topk_frac=0.25))
    new = run_dpfl(eng, cfg)
    ref_ = run_dpfl_reference(eng, cfg)
    assert new.comm_downloads == ref_.comm_downloads
    assert new.comm_bytes == ref_.comm_bytes
    np.testing.assert_allclose(new.test_acc, ref_.test_acc, atol=1e-6)

    kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
              graph_repr="sparse")
    free = run_dpfl(eng, DPFLConfig(**kw))
    full = run_dpfl(eng, DPFLConfig(
        **kw, participation=ParticipationConfig(rate=1.0)))
    assert free.comm_downloads == full.comm_downloads
    np.testing.assert_array_equal(free.test_acc, full.test_acc)


def test_sparse_rejects_naive_graph_impl(small_setting):
    with pytest.raises(ValueError, match="sparse"):
        run_dpfl(small_setting,
                 DPFLConfig(rounds=1, tau_init=1, graph_impl="naive",
                            graph_repr="sparse"))


def test_sparse_budget_at_least_n(small_setting):
    """Regression: budget >= N (more than N-1 possible peers) must clamp
    the emitted list width to N-1 — the engine sizes every (N, B) buffer
    with that clamp, and unclamped BGGC lists crashed the history
    write."""
    eng = small_setting
    cfg = DPFLConfig(rounds=2, tau_init=1, tau_train=1, budget=7, seed=0,
                     graph_repr="sparse")
    new = run_dpfl(eng, cfg)
    ref_ = run_dpfl_reference(eng, cfg)
    assert new.comm_downloads == ref_.comm_downloads
    dense = run_dpfl(eng, DPFLConfig(rounds=2, tau_init=1, tau_train=1,
                                     budget=7, seed=0))
    assert new.comm_preprocess == dense.comm_preprocess
    np.testing.assert_array_equal(new.omega, dense.omega)


# ------------------------------------------- dense refresh scanning lists


@pytest.mark.parametrize("case", ["full", "participation", "naive"])
def test_dense_list_scan_bitwise_matches_full_scan(small_setting, case):
    """`all_clients_graph(..., width=w)` on BGGC-built masks (at most w
    peers a row) selects bitwise what the full N-candidate scan selects,
    with full candidacy and under a participation restriction, and
    counts 4·N·w probes against 4·N². The naive oracle ignores the bound
    and keeps its full scan."""
    eng = small_setting
    N, budget = 6, 3
    width = dpfl_mod._nbr_width(N, budget)
    flat = _trained_flat(eng)
    reward = eng.make_reward_fn()
    omega = eng.jit(lambda f: all_clients_bggc(
        jax.random.PRNGKey(11), f, eng.p, jnp.ones((N, N), bool), reward,
        budget))(flat)
    assert int(jnp.max(omega.sum(1))) - 1 <= width
    cand = omega
    if case == "participation":
        cand = omega & jnp.asarray([1, 0, 1, 1, 0, 1], bool)[None, :]
    impl = "naive" if case == "naive" else "ggc"

    def build(w):
        return eng.jit(lambda f, c: all_clients_graph(
            jax.random.PRNGKey(3), f, eng.p, c, reward, budget,
            impl=impl, width=w))

    full, lists = build(None), build(width)
    want = np.asarray(full(flat, cand))
    got = np.asarray(lists(flat, cand))
    np.testing.assert_array_equal(got, want)
    assert full.counts["ggc.probes"] == 4 * N * N
    assert lists.counts["ggc.probes"] == (4 * N * N if impl == "naive"
                                          else 4 * N * width)


def _full_scan_refresh(monkeypatch):
    """Make the dense aggregate and `_cached_refresh` run the full
    N-candidate scan, as they did before they passed a row bound."""
    full = dpfl_mod.all_clients_graph

    def full_scan(*args, width=None, **kw):
        return full(*args, **kw)

    monkeypatch.setattr(dpfl_mod, "all_clients_graph", full_scan)


_DENSE_CASES = {
    "full": {},
    "participation": {"participation": ParticipationConfig(
        rate=0.5, model="bernoulli")},
    "topk": {"compression": CompressionConfig(codec="topk",
                                              topk_frac=0.25)},
}


@pytest.mark.parametrize("case", sorted(_DENSE_CASES))
def test_dense_aggregate_list_scan_matches_full_scan(monkeypatch, case):
    """Through the compiled dense round: the refresh scanning each Omega_k
    as a list selects, round for round, what the full scan selects — with
    full participation, under a participation schedule, and probing a
    top-k codec's decoded table — and charges the same downloads."""
    cfg = DPFLConfig(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
                     **_DENSE_CASES[case])
    lists = run_dpfl(_small_engine(), cfg)
    with monkeypatch.context() as m:
        _full_scan_refresh(m)
        full = run_dpfl(_small_engine(), cfg)
    N, width = 6, dpfl_mod._nbr_width(6, 3)
    assert full.ggc_probes == [4 * N * N] * 3
    assert lists.ggc_probes == [4 * N * width] * 3
    assert lists.comm_downloads == full.comm_downloads
    assert lists.comm_bytes == full.comm_bytes
    for a, b in zip(lists.graph_history, full.graph_history):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(lists.test_acc, full.test_acc, atol=1e-6)


@pytest.mark.parametrize("case", ["full", "participation"])
def test_dense_engine_matches_reference_with_list_scan(small_setting, case):
    """The compiled dense round and the host reference loop
    (`_cached_refresh`) both scan lists: identical graphs, probe counts
    and comm counts, and a refresh round charges sum(Omega) - N."""
    eng = small_setting
    cfg = DPFLConfig(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
                     **_DENSE_CASES[case])
    new = run_dpfl(eng, cfg)
    ref_ = run_dpfl_reference(eng, cfg)
    N = 6
    assert new.ggc_probes == ref_.ggc_probes == \
        [4 * N * dpfl_mod._nbr_width(N, 3)] * 3
    assert new.comm_downloads == ref_.comm_downloads
    assert new.comm_bytes == ref_.comm_bytes
    if case == "full":
        assert new.comm_downloads == [int(new.omega.sum()) - N] * 3
    assert len(new.graph_history) == len(ref_.graph_history) == 3
    for a, b in zip(new.graph_history, ref_.graph_history):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(new.test_acc, ref_.test_acc, atol=1e-6)
