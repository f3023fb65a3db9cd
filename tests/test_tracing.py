"""The program's own tracing: the named phase scopes of the compiled
round, the names of the `FLEngine.jit` programs, the host spans of the
round loop and the preprocessing, and the trace-time probe counter."""
import glob
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_cnn import CNNConfig
from repro.core import DPFLConfig, run_dpfl, run_dpfl_reference
from repro.core.dpfl import (_cached_bggc, _cached_refresh,
                             abstract_round_state, dpfl_round_step)
from repro.core.graph import (adjacency_from_neighbors, all_clients_bggc,
                              all_clients_graph, all_clients_graph_sparse,
                              neighbors_from_adjacency)
from repro.data import make_federated_classification
from repro.fl.engine import FLEngine
from repro.models.classifier import MLP, PaperCNN
from repro.roofline.hlo import HloModule, instruction_scopes

PHASES = {"round.train", "round.refresh", "round.mix", "round.eval"}
N = 8


@pytest.fixture(scope="module")
def cnn_engine():
    """The paper's CNN, cut to 16x16 one-channel images, over 8 clients:
    the round has convolutions, dots and fusions in every phase."""
    data = make_federated_classification(
        seed=1, n_clients=N, n_clusters=2, partition="pathological",
        classes_per_client=2, n_train=16, n_val=8, n_test=8,
        image_shape=(16, 16, 1))
    model = PaperCNN(CNNConfig(in_channels=1, image_size=16, n_classes=10,
                               c1=2, c2=3, fc1=8, fc2=8))
    return FLEngine(model, data, batch_size=8)


@pytest.fixture(scope="module")
def mlp_engine():
    data = make_federated_classification(
        seed=5, n_clients=6, n_clusters=2, partition="pathological",
        classes_per_client=3, feature_dim=8, n_train=16, n_val=16,
        n_test=16, noise=2.0, assign_level="cluster")
    return FLEngine(MLP(8, 16, 10), data, lr=0.05, batch_size=8)


def _cfg(graph_repr, **kw):
    return DPFLConfig(**{"rounds": 4, "tau_init": 1, "tau_train": 1,
                         "budget": 3, "graph_repr": graph_repr, **kw})


@pytest.mark.parametrize("graph_repr", ["dense", "sparse"])
def test_every_round_op_maps_to_one_phase(cnn_engine, graph_repr):
    cfg = _cfg(graph_repr)
    step = dpfl_round_step(cnn_engine, cfg)
    text = step.lower(abstract_round_state(cnn_engine, cfg)) \
        .compile().as_text()
    mod = HloModule(text)
    scopes = instruction_scopes(mod, "round.")
    fused = {c for instrs in mod.computations.values() for i in instrs
             if i.opcode == "fusion" for c in i.called}
    ops = [i for comp, instrs in mod.computations.items()
           if comp not in fused for i in instrs
           if i.opcode in ("fusion", "custom-call", "dot", "convolution")]
    unmapped = [i.name for i in ops if scopes[i.name] not in PHASES]
    assert not unmapped
    assert {scopes[i.name] for i in ops} == PHASES
    # the probe forwards of the refresh, not only its bookkeeping
    assert {i.opcode for i in ops if scopes[i.name] == "round.refresh"} \
        >= {"convolution", "dot", "fusion"}


def test_instruction_scopes_inherit_from_consumer_caller_operand():
    text = """HloModule m

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %c = f32[4]{0} copy(%p)
  ROOT %a = f32[4]{0} add(%c, %c), metadata={op_name="jit(f)/round.train/x/add"}
}

%branch (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%q)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %b = f32[4]{0} broadcast(%x), dimensions={0}
  %w = f32[4]{0} while(%b), body=%body, metadata={op_name="jit(f)/round.train/while"}
  %k = f32[4]{0} call(%w), to_apply=%branch, metadata={op_name="jit(f)/round.mix/round.refresh/cond"}
  %o = f32[4]{0} copy(%k)
  ROOT %t = (f32[4]{0}) tuple(%o)
}
"""
    s = instruction_scopes(text, "round.")
    assert s["a"] == "round.train"
    assert s["c"] == "round.train"          # from its consumer
    assert s["b"] == "round.train"          # hoisted: from the while
    assert s["n"] == "round.refresh"        # innermost; from its caller
    assert s["k"] == "round.refresh"
    assert s["o"] == "round.refresh"        # an output copy: its operand


def test_programs_are_named_after_their_functions(cnn_engine):
    cfg = _cfg("dense")
    eng = cnn_engine
    step = dpfl_round_step(eng, cfg)
    assert "module @jit_round_step " in step.lower(
        abstract_round_state(eng, cfg)).as_text()
    flat = jnp.zeros((N, eng.n_params))
    stacked = eng.unflatten(flat)
    key = jax.random.PRNGKey(0)
    assert "module @jit_train_fn " in eng.local_train.lower(
        stacked, key, epochs=1).as_text()
    reward = eng.make_reward_fn()
    omega = jnp.ones((N, N), bool)
    refresh = _cached_refresh(eng, cfg, reward, 3)
    assert "module @jit_refresh " in refresh.lower(
        key, flat, eng.p, omega, None).as_text()
    bggc = _cached_bggc(eng, cfg, reward, 3)
    assert "module @jit_bggc " in bggc.lower(
        key, flat, omega, eng.p).as_text()


def _counted(reward_fn, calls):
    """``reward_fn`` that also counts, on the host, each evaluation it
    makes when the compiled program runs."""
    def reward(flat, k):
        r = reward_fn(flat, k)
        jax.debug.callback(lambda v: calls.append(np.size(v)), r)
        return r
    return reward


@pytest.mark.parametrize("kind", ["dense", "sparse", "bggc",
                                     "dense-list"])
def test_probe_counter_matches_executed_rewards(mlp_engine, kind):
    eng = mlp_engine
    n, budget = eng.data.n_clients, 2
    key = jax.random.PRNGKey(7)
    flat = jax.random.normal(key, (n, eng.n_params)) * 0.1
    omega = jnp.asarray(np.random.default_rng(0).random((n, n)) < 0.5) \
        | jnp.eye(n, dtype=bool)
    calls = []
    reward = _counted(eng.make_reward_fn(), calls)
    if kind == "dense":
        def refresh(key, flat, p, cand):
            return all_clients_graph(key, flat, p, cand, reward, budget)
        args, scan = (omega,), n
    elif kind == "sparse":
        width = 3
        def refresh(key, flat, p, cand):
            return all_clients_graph_sparse(key, flat, p, cand, reward,
                                            budget)
        args, scan = (neighbors_from_adjacency(omega, width),), width
    elif kind == "dense-list":
        width = 3
        def refresh(key, flat, p, cand):
            return all_clients_graph(key, flat, p, cand, reward, budget,
                                     width=width)
        # masks with at most ``width`` peers a row, as the bound promises
        bounded = adjacency_from_neighbors(
            neighbors_from_adjacency(omega, width), n)
        args, scan = (bounded,), width
    else:
        def refresh(key, flat, p, cand):
            return all_clients_bggc(key, flat, p, cand, reward, budget)
        args, scan = (omega,), n
    program = eng.jit(refresh)
    jax.block_until_ready(program(key, flat, eng.p, *args))
    jax.effects_barrier()
    assert program.counts["ggc.probes"] == 4 * n * scan
    assert sum(calls) == program.counts["ggc.probes"]


@pytest.mark.parametrize("graph_repr", ["dense", "sparse"])
def test_result_reports_executed_probes_per_round(mlp_engine, graph_repr):
    eng = mlp_engine
    n, budget = eng.data.n_clients, 2
    cfg = _cfg(graph_repr, budget=budget, refresh_period=2)
    res = run_dpfl(eng, cfg)
    ref = run_dpfl_reference(eng, cfg)
    # both representations scan each Omega_k as a list of budget slots
    per_refresh = 4 * n * budget
    assert res.ggc_probes == [per_refresh, 0, per_refresh, 0]
    assert ref.ggc_probes == res.ggc_probes
    random = run_dpfl(eng, _cfg(graph_repr, budget=budget,
                                random_graph=True))
    assert random.ggc_probes == [0] * 4


def test_round_and_preprocess_spans_on_the_host_plane(mlp_engine, tmp_path):
    """run_dpfl (its round loop under `no_transfer`) while the profiler
    records, flushing its history every two rounds: each dispatch is a
    ``dpfl.round`` step span, the preprocessing three stage spans."""
    cfg = _cfg("dense", rounds=3, history_every=2)
    with jax.profiler.trace(str(tmp_path)):
        res = run_dpfl(mlp_engine, cfg)
    assert len(res.val_acc_history) == 3
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    names = [e.name for e in events]
    with warnings.catch_warnings():
        # reading event stats warns about a builtin type on this jax
        warnings.simplefilter("ignore", DeprecationWarning)
        steps = [dict(e.stats)["step_num"] for e in events
                 if e.name == "dpfl.round"]
    assert sorted(steps) == [0, 1, 2]
    for stage in ("train", "bggc", "mix"):
        assert names.count("dpfl.preprocess." + stage) == 1
