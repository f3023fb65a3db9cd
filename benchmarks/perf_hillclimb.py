"""§Perf hillclimbing harness: lowers variant configurations for the three
chosen (arch x shape) pairs and records roofline terms per iteration.

  PYTHONPATH=src python -m benchmarks.perf_hillclimb [--pair h1|h2|h3]
  PYTHONPATH=src python -m benchmarks.perf_hillclimb --dpfl [--rounds R]

Pairs (chosen from the baseline table; rationale in EXPERIMENTS.md §Perf):
  h1: kimi-k2-1t-a32b x decode_32k  (worst roofline fraction, memory-bound)
  h2: granite-20b     x train_4k    (most collective-bound)
  h3: qwen3-4b        x train_4k multi-pod (paper-representative: DPFL
      cross-pod mixing dominates the collective term)

--dpfl benchmarks the DPFL round loop itself: rounds/sec of the original
host-driven python loop (`run_dpfl_reference`, per-round dispatches +
np.asarray comm syncs) vs the compiled device-resident round engine
(`run_dpfl`, one jitted round_step) — the ISSUE-1 tentpole win.

--dpfl --mesh benchmarks the mesh-sharded engine: rounds/sec of the SAME
compiled round_step with the client axis sharded over 1/2/4/8 forced host
devices (each count runs in a subprocess so XLA_FLAGS lands before the
jax import) — the ISSUE-2 tentpole scaling mode.
"""
import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = "benchmarks/results/perf"

# (pair, arch, shape, mesh, tag, opts)
VARIANTS = [
    # --- H1: memory-bound MoE decode ---
    ("h1", "kimi-k2-1t-a32b", "decode_32k", "single", "h1_base", {}),
    ("h1", "kimi-k2-1t-a32b", "decode_32k", "single", "h1_seqshard",
     {"cache_seq_shard": True}),
    # --- H2: collective-bound dense train ---
    ("h2", "granite-20b", "train_4k", "single", "h2_base", {}),
    ("h2", "granite-20b", "train_4k", "single", "h2_bf16grad",
     {"grad_dtype": "bfloat16"}),
    ("h2", "granite-20b", "train_4k", "single", "h2_zero1", {"zero1": True}),
    ("h2", "granite-20b", "train_4k", "single", "h2_remat_none",
     {"remat": "none"}),
    ("h2", "granite-20b", "train_4k", "single", "h2_parallel_zero1",
     {"parallel_block": True, "zero1": True}),
    # --- H3: DPFL mixing on the pod axis ---
    ("h3", "qwen3-4b", "train_4k", "multi", "h3_mix_every_step", {}),
    ("h3", "qwen3-4b", "train_4k", "multi", "h3_no_mix", {"mix": False}),
    ("h3", "qwen3-4b", "train_4k", "multi", "h3_fedavg_global",
     {"fedavg_global": True}),
]


def run_variant(arch, shape, mesh, tag, opts):
    fn = os.path.join(OUT, f"{arch}_{shape}_{mesh}_{tag}.json")
    if os.path.exists(fn):
        return json.load(open(fn))
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--out", OUT, "--tag", tag,
         "--opts", json.dumps(opts)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=2400)
    if not os.path.exists(fn):
        raise RuntimeError(f"{tag} failed:\n{r.stdout[-2000:]}\n"
                           f"{r.stderr[-2000:]}")
    return json.load(open(fn))


def bench_dpfl_rounds(rounds=10, n_clients=16, repeats=2, out=None,
                      graph_repr="dense"):
    """rounds/sec: host-driven reference loop vs compiled round engine.
    Preprocessing (shared) is excluded by timing whole runs minus a
    0-round run; track_history=False keeps the new path device-resident.
    Writes the ``BENCH_dpfl.json`` summary for the bench trajectory
    (``out`` overrides the path — the CI regression gate writes a fresh
    copy next to the committed one and compares via
    `benchmarks.check_regression`). ``graph_repr="sparse"`` benchmarks
    the budget-sparse neighbor-list engine (DESIGN.md §12; the committed
    baseline stays dense — `bench_ggc_scaling --sparse-sweep` is the
    dense-vs-sparse crossover harness)."""
    import contextlib

    from repro.analysis.guards import recompile_sentinel
    from repro.core import DPFLConfig, run_dpfl, run_dpfl_reference
    from repro.core.dpfl import dpfl_round_step
    from benchmarks.common import standard_setting

    _, _, engine = standard_setting(n_clients=n_clients)
    kw = dict(tau_init=2, tau_train=2, budget=4, seed=0,
              track_history=False, graph_repr=graph_repr)
    cfg = DPFLConfig(rounds=rounds, **kw)

    def time_path(fn, label, step=None):
        # warm at the FULL round count: aux comm counters are shaped
        # (rounds,), so warming at rounds=1 would leave a hidden
        # recompile inside the timed region (tracelint T-hygiene)
        fn(engine, cfg)
        t0 = time.perf_counter()
        fn(engine, DPFLConfig(rounds=0, **kw))
        pre = time.perf_counter() - t0
        # the engine path times pure re-dispatch: its round_step must not
        # gain a single cache entry across the timed repeats (the host
        # reference loop has no compiled step to pin down)
        guard = recompile_sentinel(step, expect_new=0) \
            if step is not None else contextlib.nullcontext()
        best = float("inf")
        with guard:
            for _ in range(repeats):
                t0 = time.perf_counter()
                fn(engine, cfg)
                best = min(best, time.perf_counter() - t0 - pre)
        rps = rounds / best
        print(f"dpfl,{label},ok,{best:.3f},{rps:.3f},,,,")
        return rps

    print("pair,tag,status,loop_s,rounds_per_s,,,,")
    ref = time_path(run_dpfl_reference, "host_loop")
    new = time_path(run_dpfl, "round_engine",
                    step=dpfl_round_step(engine, cfg))
    print(f"dpfl,speedup,ok,,{new / ref:.2f}x,,,,")
    results_dir = os.path.join(ROOT, "benchmarks", "results")
    os.makedirs(results_dir, exist_ok=True)
    fn = out or os.path.join(results_dir, "BENCH_dpfl.json")
    json.dump({"workload": "dpfl_round_loop", "rounds": rounds,
               "clients": n_clients, "graph_repr": graph_repr,
               "host_loop_rounds_per_s": ref,
               "round_engine_rounds_per_s": new,
               "speedup": new / ref},
              open(fn, "w"), indent=1)
    print(f"wrote {fn}")


def bench_dpfl_mesh_worker(rounds, n_clients, devices, repeats=2,
                           graph_repr="dense"):
    """Subprocess body of --dpfl --mesh: run_dpfl on the client-sharded
    engine over the forced host devices of THIS process; prints one CSV
    row. Preprocessing is excluded like bench_dpfl_rounds."""
    import time as _time

    import jax

    from benchmarks.common import standard_setting
    from repro.analysis.guards import recompile_sentinel
    from repro.core import DPFLConfig, run_dpfl
    from repro.core.dpfl import dpfl_round_step
    from repro.launch.mesh import make_client_mesh

    assert len(jax.devices()) == devices, \
        f"expected {devices} forced host devices, got {len(jax.devices())}"
    _, _, engine = standard_setting(n_clients=n_clients)
    if devices > 1:
        engine.shard_clients(make_client_mesh(devices))
    kw = dict(tau_init=2, tau_train=2, budget=4, seed=0,
              track_history=False, graph_repr=graph_repr)
    cfg = DPFLConfig(rounds=rounds, **kw)
    run_dpfl(engine, cfg)  # warm at the full round count (see time_path)
    t0 = _time.perf_counter()
    run_dpfl(engine, DPFLConfig(rounds=0, **kw))
    pre = _time.perf_counter() - t0
    best = float("inf")
    with recompile_sentinel(dpfl_round_step(engine, cfg), expect_new=0):
        for _ in range(repeats):
            t0 = _time.perf_counter()
            run_dpfl(engine, cfg)
            best = min(best, _time.perf_counter() - t0 - pre)
    print(f"dpfl_mesh,devices={devices},ok,{best:.3f},"
          f"{rounds / best:.3f},,,,")


def bench_dpfl_mesh(rounds=10, n_clients=16, device_counts=(1, 2, 4, 8),
                    graph_repr="dense"):
    """rounds/sec of the mesh-sharded round engine vs device count. Each
    count runs in a subprocess because --xla_force_host_platform_device_count
    must be set before jax imports. Returns the number of failed counts."""
    print("pair,tag,status,loop_s,rounds_per_s,,,,")
    failed = 0
    for d in device_counts:
        if n_clients % d:
            print(f"dpfl_mesh,devices={d},skip(n_clients%d),,,,,,")
            continue
        env = dict(os.environ,
                   PYTHONPATH=os.path.join(ROOT, "src"),
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={d}")
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.perf_hillclimb",
             "--dpfl-mesh-worker", "--devices", str(d),
             "--rounds", str(rounds), "--clients", str(n_clients),
             "--graph-repr", graph_repr],
            cwd=ROOT, env=env, capture_output=True, text=True,
            timeout=2400)
        out = [ln for ln in r.stdout.splitlines()
               if ln.startswith("dpfl_mesh,")]
        if r.returncode or not out:
            print(f"dpfl_mesh,devices={d},failed,,,,,,")
            sys.stderr.write(r.stdout[-2000:] + r.stderr[-2000:])
            failed += 1
            continue
        print(out[-1])
    return failed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default="")
    ap.add_argument("--dpfl", action="store_true",
                    help="benchmark DPFL rounds/sec old-vs-new round loop")
    ap.add_argument("--mesh", action="store_true",
                    help="with --dpfl: rounds/sec of the client-sharded "
                         "engine vs forced host device count")
    ap.add_argument("--device-counts", default="1,2,4,8",
                    help="comma-separated device counts for --mesh")
    ap.add_argument("--dpfl-mesh-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--devices", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="with --dpfl: the committed BENCH_dpfl.json "
                         "sizes (rounds=8, clients=12) — what the CI "
                         "regression gate runs")
    ap.add_argument("--out", default=None,
                    help="with --dpfl: override the BENCH_dpfl.json path")
    ap.add_argument("--graph-repr", default="dense",
                    choices=["dense", "sparse"],
                    help="with --dpfl: collaboration-graph layout of the "
                         "benchmarked engine (DESIGN.md §12)")
    args = ap.parse_args()
    if args.dpfl_mesh_worker:
        bench_dpfl_mesh_worker(args.rounds, args.clients, args.devices,
                               graph_repr=args.graph_repr)
        return
    if args.dpfl:
        if args.smoke:
            args.rounds, args.clients = 8, 12
        if args.mesh:
            counts = tuple(int(d) for d in args.device_counts.split(","))
            if bench_dpfl_mesh(rounds=args.rounds, n_clients=args.clients,
                               device_counts=counts,
                               graph_repr=args.graph_repr):
                sys.exit(1)
        else:
            bench_dpfl_rounds(rounds=args.rounds, n_clients=args.clients,
                              out=args.out, graph_repr=args.graph_repr)
        return
    os.makedirs(OUT, exist_ok=True)
    print("pair,tag,status,compute_s,memory_s,collective_s,dominant,"
          "coll_bytes,args_bytes")
    for pair, arch, shape, mesh, tag, opts in VARIANTS:
        if args.pair and pair != args.pair:
            continue
        rec = run_variant(arch, shape, mesh, tag, opts)
        if rec["status"] != "ok":
            print(f"{pair},{tag},{rec['status']},,,,,,")
            continue
        rl = rec["roofline"]
        pd = rec["per_device"]
        print(f"{pair},{tag},ok,{rl['compute_s']:.4f},{rl['memory_s']:.4f},"
              f"{rl['collective_s']:.4f},{rl['dominant']},"
              f"{pd['collective_bytes']:.3e},"
              f"{rec['memory']['argument_bytes']}")


if __name__ == "__main__":
    main()
