"""Mesh-sharded round engine (DESIGN.md §8): the client-sharded build of
`run_dpfl` must reproduce the single-device engine — exactly on the
decision-free (random-graph) path, and on the robust invariants (Omega,
comm counters, accuracy within noise) when the greedy graph decisions run,
whose a/(a+b) coin flips amplify compilation-dependent fp noise. The
`graph_mix` shard_map row-block path is asserted numerically against the
full-matrix reference. Runs in subprocesses with 8 forced host devices
(conftest keeps the in-process test env on the real single device)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=ROOT, env=env, timeout=1200)


GRAPH_MIX_CODE = r"""
import sys; sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
from repro.kernels import ops
from repro.kernels.ref import graph_mix_ref
from repro.launch.mesh import make_client_mesh

mesh = make_client_mesh(8)
key = jax.random.PRNGKey(0)
for N, P in [(8, 257), (16, 2048), (16, 31)]:
    A = jax.nn.softmax(jax.random.normal(key, (N, N)), axis=1)
    W = jax.random.normal(jax.random.fold_in(key, N), (N, P))
    ref = np.asarray(graph_mix_ref(A, W))
    for impl in ["ref", "interpret"]:
        got = np.asarray(jax.jit(lambda a, w: ops.graph_mix(
            a, w, impl=impl, mesh=mesh, client_axes=("pod", "data")))(A, W))
        err = np.abs(got - ref).max()
        assert err < 1e-5, (N, P, impl, err)
        print("OK", N, P, impl, err)
"""


def test_graph_mix_shard_map_matches_ref():
    """Each shard's row-block of A @ all-gathered W equals the full-matrix
    fp32 reference, for the jnp and the interpreted-Pallas kernels, with
    P both below and above the panel size."""
    r = _run(GRAPH_MIX_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 6


EQUIV_CODE = r"""
import sys; sys.path.insert(0, "src"); sys.path.insert(0, ".")
import numpy as np
from benchmarks.common import standard_setting
from repro.core import DPFLConfig, run_dpfl
from repro.launch.mesh import make_client_mesh

def pair(**kw):
    _, _, e1 = standard_setting(n_clients=8)
    single = run_dpfl(e1, DPFLConfig(**kw))
    _, _, e2 = standard_setting(n_clients=8)
    e2.shard_clients(make_client_mesh(8))
    sharded = run_dpfl(e2, DPFLConfig(**kw))
    return single, sharded

# --- decision-free path (fixed random graph): exact equivalence
kw = dict(rounds=4, tau_init=2, tau_train=1, budget=3, seed=0,
          random_graph=True)
s, h = pair(**kw)
assert s.comm_preprocess == h.comm_preprocess == 8 * 3  # N * budget
assert s.comm_downloads == h.comm_downloads
np.testing.assert_array_equal(s.test_acc, h.test_acc)
for a, b in zip(s.val_acc_history, h.val_acc_history):
    np.testing.assert_array_equal(a, b)
for a, b in zip(s.graph_history, h.graph_history):
    np.testing.assert_array_equal(a, b)
np.testing.assert_array_equal(s.best_flat, h.best_flat)
print("OK random_graph exact")

# --- greedy path: preprocessing Omega, per-round comm (refresh_period=1
# reads |Omega|, which is bitwise-stable) and accuracy within noise
kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0)
s, h = pair(**kw)
np.testing.assert_array_equal(s.omega, h.omega)
assert s.comm_preprocess == h.comm_preprocess == 2 * 8 * 7  # both phases
assert s.comm_downloads == h.comm_downloads
assert abs(s.test_acc.mean() - h.test_acc.mean()) < 0.05
for adj in h.graph_history:
    assert (adj.sum(1) - 1 <= 3).all()  # budget respected on every shard
print("OK ggc robust")
"""


@pytest.mark.slow
def test_sharded_run_dpfl_matches_single_device():
    r = _run(EQUIV_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 2


BASELINE_CODE = r"""
import sys; sys.path.insert(0, "src"); sys.path.insert(0, ".")
import numpy as np
from benchmarks.common import standard_setting
from repro.fl.baselines import run_apfl, run_ditto, run_fedavg, run_fedprox
from repro.launch.mesh import make_client_mesh

for fn in (run_apfl, run_ditto, run_fedavg, run_fedprox):
    _, _, e1 = standard_setting(n_clients=8)
    single = fn(e1, rounds=2, tau=1, seed=0)
    _, _, e2 = standard_setting(n_clients=8)
    e2.shard_clients(make_client_mesh(8))
    sharded = fn(e2, rounds=2, tau=1, seed=0)
    err = np.abs(single["test_acc"] - sharded["test_acc"]).max()
    assert err < 1e-6, (fn.__name__, err)
    print("OK", fn.__name__)
"""


@pytest.mark.slow
def test_sharded_baselines_match_single_device():
    """APFL/Ditto aux side models (v / personal) shard over clients —
    and FedAvg exercises the empty-aux replicated prefix — with the
    engine path reproducing the single-device accuracies (baseline
    rounds are decision-free, so equality is exact). FedProx covers the
    prox-path regression: `_prox_engine._lt` must constrain the client
    axis like `FLEngine.train_fn` (params/data/keys/ref), not silently
    reshard mid-round under a client mesh."""
    r = _run(BASELINE_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 4


PARTICIPATION_CODE = r"""
import sys; sys.path.insert(0, "src"); sys.path.insert(0, ".")
import numpy as np
from benchmarks.common import standard_setting
from repro.core import DPFLConfig, ParticipationConfig, run_dpfl
from repro.launch.mesh import make_client_mesh

def pair(**kw):
    _, _, e1 = standard_setting(n_clients=8)
    single = run_dpfl(e1, DPFLConfig(**kw))
    _, _, e2 = standard_setting(n_clients=8)
    e2.shard_clients(make_client_mesh(8))
    sharded = run_dpfl(e2, DPFLConfig(**kw))
    return single, sharded

# --- decision-free path (fixed random graph) + sampling: exact
pc = ParticipationConfig(rate=0.5, model="bernoulli", seed=2)
kw = dict(rounds=4, tau_init=2, tau_train=1, budget=3, seed=0,
          random_graph=True, participation=pc)
s, h = pair(**kw)
np.testing.assert_array_equal(s.participation, h.participation)
assert s.comm_downloads == h.comm_downloads
np.testing.assert_array_equal(s.test_acc, h.test_acc)
np.testing.assert_array_equal(s.best_flat, h.best_flat)
print("OK participation random_graph exact")

# --- greedy path + sampling: schedule/Omega/comm identical (comm reads
# Omega and the shared schedule on refresh_period=1 rounds), accuracy
# within the documented greedy-noise tolerance (DESIGN.md s8-s9)
for pc in (ParticipationConfig(rate=0.6, model="markov", seed=3),
           ParticipationConfig(rate=0.5, model="cluster", seed=4)):
    kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
              participation=pc)
    s, h = pair(**kw)
    np.testing.assert_array_equal(s.participation, h.participation)
    np.testing.assert_array_equal(s.omega, h.omega)
    assert s.comm_downloads == h.comm_downloads
    assert abs(s.test_acc.mean() - h.test_acc.mean()) < 0.05
    for t, adj in enumerate(h.graph_history):
        absent = ~h.participation[t]
        prev = h.graph_history[t - 1] if t else np.asarray(h.omega)
        np.testing.assert_array_equal(adj[absent], prev[absent])
    print("OK participation ggc robust", pc.model)
"""


@pytest.mark.slow
def test_sharded_participation_matches_single_device():
    """The participation-aware round_step under the 8-device client mesh
    (schedule sharded over clients, restricted mix/refresh, realized-comm
    counters) reproduces the single-device build — exactly on the
    decision-free path, on the robust invariants when the greedy
    decisions run."""
    r = _run(PARTICIPATION_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 3


SPARSE_MIX_CODE = r"""
import sys; sys.path.insert(0, "src")
import numpy as np
import jax, jax.numpy as jnp
from repro.kernels import ops
from repro.kernels.ref import densify_topk, sparse_graph_mix_ref
from repro.launch.mesh import make_client_mesh

for pods in (1, 2):  # single client axis AND the 2D (pod, data) torus
    mesh = make_client_mesh(8, pods=pods)
    ca = ("pod", "data")
    key = jax.random.PRNGKey(pods)
    for N, B, P in [(8, 3, 257), (16, 4, 2048), (16, 6, 31)]:
        W = jax.random.normal(key, (N, P))
        idx = jax.random.randint(jax.random.fold_in(key, 1), (N, B), -1, N)
        nw = jax.random.normal(jax.random.fold_in(key, 2), (N, B))
        sw = jax.random.normal(jax.random.fold_in(key, 3), (N,))
        want = np.asarray(sparse_graph_mix_ref(sw, nw, idx, W, W))
        for impl in ["ref", "interpret"]:
            got = np.asarray(jax.jit(lambda *a: ops.sparse_graph_mix(
                *a, impl=impl, mesh=mesh, client_axes=ca))(sw, nw, idx, W))
            err = np.abs(got - want).max()
            assert err < 1e-5, (pods, N, B, P, impl, err)
            print("OK", pods, N, B, P, impl)
    # compressed parts ride the rotation: the collective moves (vals, idx)
    N, B, P, K = 16, 4, 120, 12
    W = jax.random.normal(key, (N, P))
    idx = jax.random.randint(jax.random.fold_in(key, 4), (N, B), -1, N)
    nw = jax.random.normal(jax.random.fold_in(key, 5), (N, B))
    sw = jax.random.normal(jax.random.fold_in(key, 6), (N,))
    _, tid = jax.lax.top_k(jnp.abs(W), K)
    tv = jnp.take_along_axis(W, tid, axis=1)
    dec = densify_topk(tv, tid.astype(jnp.int32), P)
    want = np.asarray(sparse_graph_mix_ref(sw, nw, idx, W, dec))
    got = np.asarray(jax.jit(lambda sw, nw, idx, W, v, i: ops.sparse_graph_mix(
        sw, nw, idx, W, (v, i), lambda v, i: densify_topk(v, i, P),
        mesh=mesh, client_axes=ca))(sw, nw, idx, W, tv, tid.astype(jnp.int32)))
    assert np.abs(got - want).max() < 1e-5, pods
    print("OK", pods, "topk-parts")
    # int8-style parts: the (N,) fp32 scale rides the rotation as a 1-D
    # P(ca) operand next to the int8 q panel
    q = jnp.round(W * 10).astype(jnp.int8)
    s = jnp.abs(jax.random.normal(jax.random.fold_in(key, 7), (N,)))
    dec8 = q.astype(jnp.float32) * s[:, None]
    want = np.asarray(sparse_graph_mix_ref(sw, nw, idx, W, dec8))
    got = np.asarray(jax.jit(lambda sw, nw, idx, W, q, s: ops.sparse_graph_mix(
        sw, nw, idx, W, (q, s),
        lambda qq, ss: qq.astype(jnp.float32) * ss[:, None],
        mesh=mesh, client_axes=ca))(sw, nw, idx, W, q, s))
    assert np.abs(got - want).max() < 1e-5, pods
    print("OK", pods, "int8-parts")
"""


def test_sparse_mix_rotation_matches_ref():
    """The neighbor-list mix's shard_map path — peer panels rotated
    shard-to-shard via ppermute, only requested rows kept (DESIGN.md
    §12) — equals the single-device oracle on 1D and 2D client meshes,
    for raw, topk and int8 peer parts, under both kernel impls. Jitted,
    as the round engine calls it (an eager shard_map dispatches every
    op per device)."""
    r = _run(SPARSE_MIX_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 16


SPARSE_ENGINE_CODE = r"""
import sys; sys.path.insert(0, "src"); sys.path.insert(0, ".")
import numpy as np
from benchmarks.common import standard_setting
from repro.core import CompressionConfig, DPFLConfig, run_dpfl
from repro.launch.mesh import make_client_mesh

def pair(**kw):
    _, _, e1 = standard_setting(n_clients=8)
    single = run_dpfl(e1, DPFLConfig(**kw))
    _, _, e2 = standard_setting(n_clients=8)
    e2.shard_clients(make_client_mesh(8))
    sharded = run_dpfl(e2, DPFLConfig(**kw))
    return single, sharded

# --- decision-free path: the graph (and so every counter) is layout-
# independent; params agree to fp tolerance (the rotation accumulates
# peer contributions in visit order, not slot order — DESIGN.md s12)
kw = dict(rounds=4, tau_init=2, tau_train=1, budget=3, seed=0,
          random_graph=True, graph_repr="sparse")
s, h = pair(**kw)
assert s.comm_preprocess == h.comm_preprocess == 8 * 3
assert s.comm_downloads == h.comm_downloads
for a, b in zip(s.graph_history, h.graph_history):
    np.testing.assert_array_equal(a, b)
np.testing.assert_allclose(s.test_acc, h.test_acc, atol=1e-5)
print("OK sparse random_graph")

# --- greedy path (+ topk compression): robust invariants per s8/s12
kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
          graph_repr="sparse",
          compression=CompressionConfig(codec="topk", topk_frac=0.3))
s, h = pair(**kw)
np.testing.assert_array_equal(s.omega, h.omega)
assert s.comm_preprocess == h.comm_preprocess == 2 * 8 * 7
assert s.comm_downloads == h.comm_downloads
assert s.comm_bytes == h.comm_bytes
assert abs(s.test_acc.mean() - h.test_acc.mean()) < 0.05
for adj in h.graph_history:
    assert (adj.sum(1) - 1 <= 3).all()  # budget respected on every shard
print("OK sparse ggc robust")
"""


ROBUST_CODE = r"""
import sys; sys.path.insert(0, "src"); sys.path.insert(0, ".")
import numpy as np
from benchmarks.common import standard_setting
from repro.core import AdversaryConfig, DPFLConfig, run_dpfl
from repro.launch.mesh import make_client_mesh

def pair(**kw):
    _, _, e1 = standard_setting(n_clients=8)
    single = run_dpfl(e1, DPFLConfig(**kw))
    _, _, e2 = standard_setting(n_clients=8)
    e2.shard_clients(make_client_mesh(8))
    sharded = run_dpfl(e2, DPFLConfig(**kw))
    return single, sharded

adv = AdversaryConfig(attack="grad_scale", fraction=0.25, seed=7,
                      scale=3.0)

# --- trimmed, decision-free path, dense and sparse: the graph is fixed
# so every counter is layout-independent; the coordinate-wise rank
# selection feeds a sum whose GSPMD reduction order may differ, so
# accuracy gets the greedy-noise tolerance rather than bitwise
for repr_ in ("dense", "sparse"):
    kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
              random_graph=True, graph_repr=repr_, adversary=adv,
              mix_rule="trimmed", trim_frac=0.25)
    s, h = pair(**kw)
    np.testing.assert_array_equal(s.malicious, h.malicious)
    assert s.comm_preprocess == h.comm_preprocess == 8 * 3
    assert s.comm_downloads == h.comm_downloads
    for a, b in zip(s.graph_history, h.graph_history):
        np.testing.assert_array_equal(a, b)
    assert abs(s.test_acc.mean() - h.test_acc.mean()) < 0.05
    print("OK trimmed", repr_)

# --- clipped, greedy path, dense and sparse: preprocessing is clean so
# Omega stays bitwise; comm reads Omega/the schedule; accuracy within
# the documented greedy-noise envelope (DESIGN.md s8/s15)
for repr_ in ("dense", "sparse"):
    kw = dict(rounds=3, tau_init=2, tau_train=1, budget=3, seed=0,
              graph_repr=repr_, adversary=adv,
              mix_rule="clipped", clip_mult=1.5)
    s, h = pair(**kw)
    np.testing.assert_array_equal(s.malicious, h.malicious)
    np.testing.assert_array_equal(s.omega, h.omega)
    assert s.comm_preprocess == h.comm_preprocess == 2 * 8 * 7
    assert s.comm_downloads == h.comm_downloads
    assert abs(s.test_acc.mean() - h.test_acc.mean()) < 0.05
    print("OK clipped", repr_)
"""


@pytest.mark.slow
def test_sharded_robust_mixing_matches_single_device():
    """Trimmed and clipped Eq.-4 mixing under the 8-device client mesh
    with grad_scale attackers: the robust weight computation (peer
    panels, rank selection, norm clipping) composes with the sharded
    mix on both graph representations, reproducing the single-device
    integer invariants and staying inside the accuracy envelope."""
    r = _run(ROBUST_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 4


@pytest.mark.slow
def test_sharded_sparse_engine_matches_single_device():
    """run_dpfl with graph_repr='sparse' under the 8-device client mesh:
    neighbor lists shard over clients, the mix runs the rotation
    exchange, and the refresh probes only shard-local candidate lists —
    matching the single-device sparse build exactly on the integer
    invariants and within the greedy-noise envelope on accuracy."""
    r = _run(SPARSE_ENGINE_CODE)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.count("OK") == 2
