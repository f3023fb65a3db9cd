"""GGC complexity claim (§3.2): per-client cost is O(B_c) reward probes
during training (candidates come from Omega_k, |Omega_k| <= B_c), and O(N)
compute / O(B_c) communication for BGGC preprocessing. We measure wall time
of the vmapped graph build vs N and B_c.

`python -m benchmarks.bench_ggc_scaling --mesh` measures the shard_map
graph build (each shard vmaps only its local k rows against all-gathered
peer panels) vs forced host device count — one subprocess per count, since
--xla_force_host_platform_device_count must precede the jax import.

`python -m benchmarks.bench_ggc_scaling --sparse-sweep` measures
rounds/sec of the full compiled round engine in the dense (N, N) vs the
budget-sparse (N, B) graph representation across N in {32, 128, 512,
1024} (DESIGN.md §12). The decision-free random-graph cells isolate the
Eq.-4 mix — O(N²·P) dense matmul vs O(N·B·P) neighbor-list gather — and
the greedy cells add the GGC refresh, whose sparse scan probes only the
<= B candidates per client. The dense path is skipped above
``--dense-max`` (it is the thing the sweep shows collapsing); results go
to ``benchmarks/results/BENCH_sparse_scaling.json``."""
import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DPFLConfig, run_dpfl
from repro.core.graph import all_clients_graph
from repro.data import make_federated_classification
from repro.fl.engine import FLEngine
from repro.models.classifier import MLP

from .common import Bench

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(bench: Bench):
    for n_clients in (8, 16, 32):
        data = make_federated_classification(
            seed=0, n_clients=n_clients, n_clusters=4, feature_dim=16,
            n_train=16, n_val=16, n_test=16, noise=2.0,
            assign_level="cluster")
        eng = FLEngine(MLP(16, 32, 10), data, lr=0.05, batch_size=8)
        st = eng.init_clients(jax.random.PRNGKey(0))
        flat = eng.flatten(st)
        reward = eng.make_reward_fn()
        for budget in (2, 8):
            # restrict candidates to B_c as in the training loop
            rng = np.random.default_rng(0)
            cand = np.zeros((n_clients, n_clients), bool)
            for k in range(n_clients):
                others = np.setdiff1d(np.arange(n_clients), [k])
                take = min(budget, len(others))
                cand[k, rng.choice(others, take, replace=False)] = True
            candj = jnp.asarray(cand)
            graph = eng.jit(lambda f, c, b=budget: all_clients_graph(
                jax.random.PRNGKey(1), f, eng.p, c, reward, b))

            def build():
                return jax.block_until_ready(graph(flat, candj))

            build()  # compile
            t0 = time.time()
            adj = build()
            bench.record(f"ggc_scaling/N={n_clients}/B={budget}",
                         time.time() - t0,
                         f"edges={int(np.asarray(adj).sum())}")


def _sweep_engine(n_clients: int):
    """A mix-dominated setting for the dense-vs-sparse crossover: tiny
    per-client data (training and eval are O(N) and identical in both
    representations) with a P≈2.8k-param MLP so the Eq.-4 aggregation
    term dominates as N grows."""
    data = make_federated_classification(
        seed=0, n_clients=n_clients, n_clusters=4, feature_dim=32,
        n_train=8, n_val=8, n_test=8, noise=2.0, assign_level="cluster")
    return FLEngine(MLP(32, 64, 10), data, lr=0.05, batch_size=8)


def _time_rounds(engine, cfg_kw, rounds, repeats=3):
    """rounds/sec of `run_dpfl`, preprocessing excluded by subtracting
    the best 0-round run from the best full run (the perf_hillclimb
    protocol, with min-of-repeats on BOTH terms so preprocessing jitter
    cannot drive the difference negative at small N). The timed repeats
    run under a `recompile_sentinel`: the warm run at the same round
    count must leave NOTHING to compile, or the sweep would compare
    compile times, not round throughput."""
    import contextlib

    from repro.analysis.guards import recompile_sentinel
    from repro.core.dpfl import dpfl_round_step

    def best_of(r):
        cfg = DPFLConfig(rounds=r, **cfg_kw)
        run_dpfl(engine, cfg)  # warm compiles at this exact round count
        guard = recompile_sentinel(dpfl_round_step(engine, cfg),
                                   expect_new=0) \
            if r else contextlib.nullcontext()
        best = float("inf")
        with guard:
            for _ in range(repeats):
                t0 = time.perf_counter()
                run_dpfl(engine, cfg)
                best = min(best, time.perf_counter() - t0)
        return best

    pre = best_of(0)
    loop = best_of(rounds) - pre
    return rounds / max(loop, 1e-9)


def sparse_sweep(n_sweep, budget, rounds, dense_max, out_path):
    """Dense vs budget-sparse rounds/sec across N; writes the JSON record
    the README benchmark table cites. Greedy cells (GGC refresh every
    round) are limited to min(dense_max, 128) dense / 512 sparse — the
    O(N²) BGGC preprocessing itself becomes the wall at 1024."""
    cells = []
    print("graph,N,repr,rounds_per_s")
    for n in n_sweep:
        eng = _sweep_engine(n)
        for graph, max_dense, max_sparse in (
                ("random", dense_max, max(n_sweep)),
                ("greedy", min(dense_max, 128), 512)):
            kw = dict(tau_init=1, tau_train=1, budget=budget, seed=0,
                      track_history=False, random_graph=(graph == "random"))
            # small-N rounds are sub-ms: scale the timed loop up so it
            # dwarfs preprocessing jitter (greedy rounds pay N·B probes
            # per refresh, so their loop stays shorter)
            target = 4096 if graph == "random" else 512
            r_eff = min(64, max(rounds, target // n))
            for repr_ in ("dense", "sparse"):
                cap = max_dense if repr_ == "dense" else max_sparse
                if n > cap:
                    print(f"{graph},{n},{repr_},skipped")
                    continue
                rps = _time_rounds(eng, dict(kw, graph_repr=repr_), r_eff)
                cells.append({"graph": graph, "N": n, "repr": repr_,
                              "budget": budget, "rounds": r_eff,
                              "rounds_per_s": rps})
                print(f"{graph},{n},{repr_},{rps:.3f}")
    rec = {"workload": "dpfl_sparse_vs_dense_scaling", "rounds": rounds,
           "budget": budget, "model_params": 32 * 64 + 64 + 64 * 10 + 10,
           "cells": cells}
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    json.dump(rec, open(out_path, "w"), indent=1)
    print(f"wrote {out_path}")


def _mesh_worker(n_clients, budget, devices, repeats=3):
    """Subprocess body of --mesh: time the shard_map graph build on THIS
    process's forced host devices; prints one CSV row."""
    from repro.launch.mesh import make_client_mesh

    assert len(jax.devices()) == devices
    data = make_federated_classification(
        seed=0, n_clients=n_clients, n_clusters=4, feature_dim=16,
        n_train=16, n_val=16, n_test=16, noise=2.0, assign_level="cluster")
    eng = FLEngine(MLP(16, 32, 10), data, lr=0.05, batch_size=8)
    mesh = make_client_mesh(devices) if devices > 1 else None
    if mesh is not None:
        eng.shard_clients(mesh)
    flat = eng.flatten(eng.init_clients(jax.random.PRNGKey(0)))
    reward = eng.make_reward_fn()
    cand = jnp.ones((n_clients, n_clients), bool)
    jf = eng.jit(lambda k, f: all_clients_graph(
        k, f, eng.p, cand, reward, budget, mesh=mesh,
        client_axes=eng.client_axes))
    key = jax.random.PRNGKey(1)
    jax.block_until_ready(jf(key, flat))  # compile
    best = float("inf")
    # the timed loop is pure re-dispatch of one compiled build: fence it
    # against hidden host<->device transfers and fresh compiles
    from repro.analysis.guards import no_transfer, recompile_sentinel
    with no_transfer(), recompile_sentinel(jf, expect_new=0):
        for _ in range(repeats):
            t0 = time.time()
            jax.block_until_ready(jf(key, flat))
            best = min(best, time.time() - t0)
    print(f"ggc_mesh,N={n_clients},B={budget},devices={devices},"
          f"{best * 1e3:.1f}ms")


def _mesh_parent(n_clients, budget, device_counts):
    print("tag,N,B,devices,build_ms")
    for d in device_counts:
        if n_clients % d:
            print(f"ggc_mesh,N={n_clients},B={budget},devices={d},skip")
            continue
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(ROOT, "src"),
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={d}")
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_ggc_scaling",
             "--mesh-worker", "--devices", str(d),
             "--clients", str(n_clients), "--budget", str(budget)],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=2400)
        out = [ln for ln in r.stdout.splitlines()
               if ln.startswith("ggc_mesh,")]
        if r.returncode or not out:
            print(f"ggc_mesh,N={n_clients},B={budget},devices={d},failed")
            sys.stderr.write(r.stdout[-2000:] + r.stderr[-2000:])
            continue
        print(out[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true",
                    help="shard_map graph build vs forced device count")
    ap.add_argument("--mesh-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--budget", type=int, default=4)
    ap.add_argument("--device-counts", default="1,2,4,8")
    ap.add_argument("--sparse-sweep", action="store_true",
                    help="rounds/sec of the dense vs budget-sparse round "
                         "engine across N (DESIGN.md §12); writes "
                         "BENCH_sparse_scaling.json")
    ap.add_argument("--n-sweep", default="32,128,512,1024",
                    help="comma-separated client counts for --sparse-sweep")
    ap.add_argument("--rounds", type=int, default=5,
                    help="timed rounds per --sparse-sweep cell")
    ap.add_argument("--dense-max", type=int, default=1024,
                    help="skip the dense path above this N in "
                         "--sparse-sweep (greedy dense cells cap at 128 "
                         "regardless — O(N²) reward probes per round)")
    ap.add_argument("--smoke", action="store_true",
                    help="with --sparse-sweep: CI-sized sweep "
                         "(N in {16, 32}, 3 rounds)")
    ap.add_argument("--out",
                    default=os.path.join(ROOT, "benchmarks", "results",
                                         "BENCH_sparse_scaling.json"),
                    help="with --sparse-sweep: output JSON path")
    args = ap.parse_args()
    if args.mesh_worker:
        _mesh_worker(args.clients, args.budget, args.devices)
    elif args.mesh:
        counts = tuple(int(d) for d in args.device_counts.split(","))
        _mesh_parent(args.clients, args.budget, counts)
    elif args.sparse_sweep:
        n_sweep = tuple(int(n) for n in args.n_sweep.split(","))
        rounds = args.rounds
        if args.smoke:
            n_sweep, rounds = (16, 32), 3
        sparse_sweep(n_sweep, args.budget, rounds, args.dense_max,
                     args.out)
    else:
        bench = Bench()
        run(bench)
        bench.print_csv()


if __name__ == "__main__":
    main()
