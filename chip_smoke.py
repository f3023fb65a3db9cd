#!/usr/bin/env python3
"""Smoke run of the DPFL round engine on TPU chips.

One chip (the default): 100 clients of CIFAR-10-shaped data (32x32x3, 10
classes; 500 train, 100 validation and 100 test images per client, a
pathological partition with 2 classes per client, generated from
``--seed``) train the paper's CNN (`PaperCNN`, P = 62,006) through
`FLEngine` and `run_dpfl`: BGGC preprocessing and 3 compiled rounds at
budget 10, once with the dense graph and once with neighbor lists. Then
each of the three Pallas graph kernels runs once at the run's shapes and
is compared with its `ref` oracle.

Four chips (``--chips 4``): the same rounds on a ('pod', 'data') client
mesh over four devices, dense and sparse, each compared with the
one-device run by the contract of `tests/test_sharded_engine.py`: comm
counts and bytes exact; on the ``random_graph=True`` path graphs bitwise,
and dense parameters and accuracies bitwise (sparse accuracies within
1e-5, since the peer rotation sums in visit order); on the greedy path
Omega bitwise and mean accuracy within 0.05.

Every failed check raises, so the script exits non-zero. It exits 2
before doing any work when JAX finds no TPU or the graph kernels would
not run as Pallas. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

SIZES = {"n_clients": 100, "n_train": 500, "n_val": 100, "n_test": 100}
BUDGET = 10
ROUNDS = 3
TOPK_FRAC = 0.05
KERNEL_TOL = 1e-4   # max |kernel - oracle|, relative to max |oracle|
ACC_TOL = 0.05      # greedy path: sharded vs one-device mean test accuracy


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """Raise when a smoke check fails (an assert would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def make_engine(seed: int, mesh=None):
    from repro.configs.paper_cnn import CONFIG
    from repro.data import make_federated_classification
    from repro.fl.engine import FLEngine
    from repro.models.classifier import PaperCNN

    data = make_federated_classification(
        seed=seed, n_classes=10, n_clusters=4, partition="pathological",
        classes_per_client=2, image_shape=(32, 32, 3),
        assign_level="cluster", **SIZES)
    return FLEngine(PaperCNN(CONFIG), data, lr=0.01, batch_size=16,
                    mesh=mesh)


def dpfl_config(seed: int, graph_repr: str, random_graph: bool = False):
    from repro.core import DPFLConfig

    return DPFLConfig(rounds=ROUNDS, tau_init=1, tau_train=1,
                      budget=BUDGET, seed=seed, graph_repr=graph_repr,
                      random_graph=random_graph)


def check_comm(res, cfg, n: int) -> list:
    """Comm counts against the host formulas: every round refreshes, so
    it downloads all of Omega off the diagonal; BGGC preprocessing
    downloads 2N(N-1) models (N·budget for the random graph)."""
    per_round = int(res.omega.sum()) - n
    want = [per_round] * cfg.rounds
    check(res.comm_downloads == want, f"comm {res.comm_downloads} != {want}")
    pre = n * min(BUDGET, n - 1) if cfg.random_graph else 2 * n * (n - 1)
    check(res.comm_preprocess == pre,
          f"preprocess comm {res.comm_preprocess} != {pre}")
    return want


def run_one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import abstract_round_state, dpfl_round_step, run_dpfl

    t0 = time.perf_counter()
    engine = make_engine(seed)
    n = engine.data.n_clients
    log(f"setup: {n} clients, P={engine.n_params}, data+engine "
        f"{time.perf_counter() - t0:.1f}s")
    runs = {}
    for repr_ in ("dense", "sparse"):
        cfg = dpfl_config(seed, repr_)
        t0 = time.perf_counter()
        compiled = dpfl_round_step(engine, cfg).lower(
            abstract_round_state(engine, cfg)).compile()
        compile_s = time.perf_counter() - t0
        custom = "tpu_custom_call" in compiled.as_text()
        log(f"{repr_}: round_step compile_s={compile_s:.1f} "
            f"tpu_custom_call={custom}")
        check(custom, "the compiled round_step runs no Pallas kernel")
        t0 = time.perf_counter()
        res = run_dpfl(engine, cfg)
        run_s = time.perf_counter() - t0
        want = check_comm(res, cfg, n)
        check(len(res.val_acc_history) == cfg.rounds, "rounds missing")
        check(np.isfinite(res.test_acc).all(), "non-finite accuracy")
        log(f"{repr_}: rounds={len(res.comm_downloads)} "
            f"mean_test_acc={res.test_acc.mean():.4f} "
            f"comm_downloads={res.comm_downloads} expected={want} "
            f"comm_preprocess={res.comm_preprocess} run_s={run_s:.1f}")
        runs[repr_] = res
    check_kernels(engine, runs["dense"], runs["sparse"])
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def check_kernels(engine, dense, sparse) -> None:
    """One call of each Pallas graph kernel at the run's shapes against
    its `ref` oracle, evaluated at full fp32 matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.graph import (mixing_matrix, neighbors_from_adjacency,
                                  sparse_mixing_weights)
    from repro.kernels import ops, ref

    n, p_dim = engine.data.n_clients, engine.n_params
    W = jnp.asarray(dense.best_flat)
    A = mixing_matrix(jnp.asarray(dense.omega), engine.p)
    nbr = neighbors_from_adjacency(jnp.asarray(sparse.omega), BUDGET)
    self_w, nbr_w = sparse_mixing_weights(nbr, engine.p)
    k = math.ceil(TOPK_FRAC * p_dim)
    _, idx = jax.lax.top_k(jnp.abs(W), k)
    vals = jnp.take_along_axis(W, idx, axis=1)
    A_off = A * (1.0 - jnp.eye(n, dtype=A.dtype))
    # (kernel, oracle, arguments): the arrays enter each jit as arguments,
    # so none is compiled into the program as a constant
    cases = {
        "graph_mix": (ops.graph_mix, ref.graph_mix_ref, (A, W)),
        "sparse_graph_mix": (
            ops.sparse_graph_mix,
            lambda sw, nw, i, w: ref.sparse_graph_mix_ref(sw, nw, i, w, w),
            (self_w, nbr_w, nbr, W)),
        "compressed_graph_mix": (
            lambda a, v, i: ops.compressed_graph_mix(a, v, i, p_dim),
            lambda a, v, i: ref.compressed_graph_mix_ref(a, v, i, p_dim),
            (A_off, vals, idx)),
    }
    shapes = {"graph_mix": f"A{tuple(A.shape)} W{tuple(W.shape)}",
              "sparse_graph_mix": f"N={n} B={nbr.shape[1]} P={p_dim}",
              "compressed_graph_mix": f"N={n} K={k} P={p_dim}"}
    for name, (kernel, oracle, args) in cases.items():
        got = np.asarray(jax.jit(kernel)(*args))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(oracle)(*args))
        err = float(np.abs(got - want).max())
        tol = KERNEL_TOL * max(1.0, float(np.abs(want).max()))
        log(f"kernel {name} {shapes[name]}: max_err={err:.3e} tol={tol:.1e}")
        check(np.isfinite(got).all() and err <= tol,
              f"{name}: max_err {err} > {tol}")


def run_four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.core import abstract_round_state, dpfl_round_step, run_dpfl
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(4)
    single = make_engine(seed)
    sharded = make_engine(seed, mesh=mesh)
    n = single.data.n_clients
    x = sharded.train_data[0]
    devices = {d.id for d in x.sharding.device_set}
    rows = sorted({s.data.shape[0] for s in x.addressable_shards})
    log(f"mesh: {dict(mesh.shape)}; train_x on devices {sorted(devices)} "
        f"with {rows} client rows per shard")
    check(len(devices) == 4 and rows == [n // 4],
          f"train_x on {devices} with {rows} rows per shard")
    failed = []

    def same(a, b) -> bool:
        return all(np.array_equal(x, y) for x, y in zip(a, b, strict=True))

    def comm(r):
        return (r.comm_downloads, r.comm_bytes, r.comm_preprocess,
                r.comm_bytes_preprocess)

    for repr_ in ("dense", "sparse"):
        for random_graph in (True, False):
            path = "random_graph" if random_graph else "greedy"
            cfg = dpfl_config(seed, repr_, random_graph)
            t0 = time.perf_counter()
            s = run_dpfl(single, cfg)
            t1 = time.perf_counter()
            h = run_dpfl(sharded, cfg)
            t2 = time.perf_counter()
            check_comm(s, cfg, n)
            acc_err = float(np.abs(s.test_acc - h.test_acc).max())
            checks = {"comm_exact": comm(s) == comm(h),
                      "omega_bitwise": same([s.omega], [h.omega])}
            if random_graph:
                checks["graphs_bitwise"] = same(s.graph_history,
                                                h.graph_history)
                if repr_ == "dense":
                    checks["params_acc_bitwise"] = same(
                        [s.test_acc, s.best_flat, *s.val_acc_history],
                        [h.test_acc, h.best_flat, *h.val_acc_history])
                else:
                    # the rotation sums peers in visit order, not slot
                    # order: graphs bitwise, accuracy to fp tolerance
                    checks["acc_within_1e-5"] = acc_err <= 1e-5
            else:
                gap = abs(float(s.test_acc.mean() - h.test_acc.mean()))
                checks[f"acc_gap_{gap:.4f}_below_{ACC_TOL}"] = gap < ACC_TOL
                checks["within_budget"] = all(
                    (adj.sum(1) - 1 <= BUDGET).all()
                    for adj in h.graph_history)
            # every comparison is logged before the run fails, so one
            # four-chip run reports all of them
            failed += [f"{repr_} {path} {k}" for k, ok in checks.items()
                       if not ok]
            param_err = float(np.abs(s.best_flat - h.best_flat).max())
            log(f"{repr_} {path}: 1-device acc={s.test_acc.mean():.4f} "
                f"4-device acc={h.test_acc.mean():.4f} "
                f"max_acc_diff={acc_err:.3e} max_param_diff={param_err:.3e} "
                f"comm_downloads={h.comm_downloads} "
                f"comm_bytes={h.comm_bytes} "
                + " ".join(f"{k}={ok}" for k, ok in checks.items())
                + f" run_s 1-device={t1 - t0:.1f} 4-device={t2 - t1:.1f}")
    for repr_ in ("dense", "sparse"):
        # the state the compiled step hands back is split over the four
        # devices too (this lowering reuses the compile of the run above)
        cfg = dpfl_config(seed, repr_)
        out = dpfl_round_step(sharded, cfg).lower(
            abstract_round_state(sharded, cfg)).compile().output_shardings
        shards = out.flat.shard_shape((n, sharded.n_params))
        log(f"{repr_}: round_step state.flat on "
            f"{len(out.flat.device_set)} devices, shard {shards}")
        check(len(out.flat.device_set) == 4 and shards[0] == n // 4,
              f"state.flat shard {shards}")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    log(f"peak_bytes_in_use per device={peaks}")
    check(not failed, f"sharded run differs from the one-device run: "
                      f"{failed}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPUs, "
             f"JAX found {len(devices)}")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.kernels.ops import resolve_impl
    from repro.launch.compile_cache import use_compile_cache

    impl = resolve_impl(None)
    if impl != "pallas":
        fail(f"graph kernels resolve to {impl!r}, not 'pallas' "
             f"(REPRO_KERNEL_IMPL={os.environ.get('REPRO_KERNEL_IMPL')!r})")
    log(f"kernel implementation: {impl}")
    log(f"compile cache: {use_compile_cache()}")
    if args.chips == 1:
        run_one_chip(args.seed)
    else:
        run_four_chips(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
