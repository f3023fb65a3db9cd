#!/usr/bin/env python3
"""Chip benchmark of the compiled DPFL round.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One run of one cell of ``BENCHMARK.json``, on the chips of the machine it
starts on (no fallback: without a TPU, with fewer chips than the cell
asks for, with a device missing from ``peaks.json`` or with graph
kernels that would not run as Pallas it exits 2 and prints no result):

1. the cell's configuration (``configs/<config>.json``) and traffic mix
   (``traffic/<traffic>.json``) are read by name, and the federated data
   is made from ``--seed`` on the device (`data.py`);
2. ``FLEngine(PaperCNN(model), data)`` is built and the DPFL preprocessing
   (Alg. 1 lines 1-5, `repro.core.dpfl._preprocess`) runs twice: the
   first call compiles, the second is timed (``preprocess_s``);
3. the `RoundState` that `run_dpfl` builds is handed to
   `dpfl_round_step`, the compiled round that `run_dpfl` dispatches; its
   first rounds are recorded for the correctness check;
4. the window: rounds through `run_rounds`, each ended by
   ``block_until_ready``, until ``--seconds`` have passed; ``setup_s`` is
   process start to the window's first round;
5. with ``--trace 1`` the window runs under the profiler, followed by one
   call each of local training, the GGC refresh and the Eq.-4 mix at the
   run's shapes, and the per-layer metrics are read from the trace by
   the readers in ``metrics/``;
6. the recorded rounds are compared with the plain reference
   (`reference.py`, `check.py`) once the program's state is freed.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared, with its limit); the numbers
compared are also the last lines of stderr.

``--plant`` (calibration only; the benchmark's runs never pass it)
replaces the program's recorded rounds with a control or a fault:
``control`` is the reference in bfloat16 making its own decisions,
``half_batch`` trains on half of every minibatch, ``unchanged`` returns
the state unchanged, ``altered`` swaps one client's chosen and rejected
candidates in every refresh, where the refresh makes them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)

# the round counter's cap: it sizes the comm and history buffers, so the
# window may not reach it
ROUNDS_CAP = 2048
CHECKED_ROUNDS = 3
# how far a device operation may start outside the host span of its call
SLACK_NS = 10e6
PLANTS = ("control", "half_batch", "unchanged", "altered")


class Refusal(Exception):
    """The run cannot measure what the cell asks for here."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_path: str = None) -> dict:
    """The cell ``workload`` of BENCHMARK.json with its configuration,
    traffic mix, limits and the metrics it reports."""
    bench = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refusal(f"no workload {workload!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def reports(m):
        return "workloads" not in m or workload in m["workloads"]

    limits_path = os.path.join(HERE, "limits", workload + ".json")
    return {
        "name": workload, "chips": w["chips"],
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          w["traffic"] + ".json")),
        "limits": (load_json(limits_path)["limits"]
                   if os.path.exists(limits_path) else None),
        "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
        "per_layer": [m for m in bench["per_layer"] if reports(m)],
    }


def require_chips(jax, chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refusal(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise Refusal(f"the cell needs {chips} TPUs, JAX found "
                      f"{len(devices)}")
    return devices[:chips]


def device_peak(kind: str) -> dict:
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks:
        raise Refusal(f"device kind {kind!r} is not in peaks.json "
                      f"(have {sorted(peaks)})")
    return peaks[kind]


def require_pallas() -> None:
    from repro.kernels.ops import resolve_impl

    impl = resolve_impl(None)
    if impl != "pallas":
        raise Refusal(f"graph kernels resolve to {impl!r}, not 'pallas'")


def use_cache(jax) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``, a fixed path (the path is
    part of each entry's key). Every program is kept, however fast it
    compiled, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_reader(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ the system


def dpfl_config(cell: dict, seed: int):
    from repro.core import DPFLConfig

    tr, train = cell["traffic"], cell["config"]["training"]
    unsupported = {k: tr.get(k) for k in ("codec", "participation",
                                          "adversary")
                   if tr.get(k) is not None}
    if tr.get("mix_rule", "weighted") != "weighted":
        unsupported["mix_rule"] = tr["mix_rule"]
    if tr.get("random_graph"):
        unsupported["random_graph"] = True
    if unsupported:
        raise Refusal(f"the reference does not cover {unsupported}")
    return DPFLConfig(rounds=ROUNDS_CAP, tau_init=train["tau_init"],
                      tau_train=tr["tau_train"], budget=train["budget"],
                      refresh_period=tr["refresh_period"], seed=seed,
                      graph_repr=tr["graph_repr"])


def make_engine(cell: dict, data, plant=None):
    from repro.configs.paper_cnn import CNNConfig
    from repro.fl.engine import FLEngine
    from repro.models.classifier import PaperCNN, xent_loss

    train = cell["config"]["training"]
    model = PaperCNN(CNNConfig(**cell["config"]["model"]))
    kw = {}
    if plant == "half_batch":
        def half(params, batch):
            m = batch["y"].shape[0] // 2
            return xent_loss(model, params, {"x": batch["x"][:m],
                                             "y": batch["y"][:m]})
        kw["loss_fn"] = half
    return FLEngine(model, data, lr=train["lr"],
                    momentum=train["momentum"],
                    weight_decay=train["weight_decay"],
                    batch_size=train["batch_size"], **kw)


def initial_state(engine, cfg, omega, flat, k_graph, k_train):
    """The `RoundState` that `run_dpfl` builds after the preprocessing
    (full participation, no codec, no adversary)."""
    import jax.numpy as jnp

    from repro.core.dpfl import _hist_len, _nbr_width, _sparse
    from repro.fl.round_engine import init_round_state

    n = engine.data.n_clients
    hist_len = _hist_len(cfg)
    comm = jnp.zeros((cfg.rounds,), jnp.int32)
    if _sparse(cfg):
        aux = {"nbr": omega, "omega_nbr": omega, "k_graph": k_graph,
               "comm": comm,
               "graph_hist": jnp.full((hist_len, n, _nbr_width(
                   n, cfg.budget)), -1, jnp.int32)}
    else:
        aux = {"adj": omega, "omega": omega, "k_graph": k_graph,
               "comm": comm,
               "graph_hist": jnp.zeros((hist_len, n, n), bool)}
    return init_round_state(flat, k_train, hist_len=hist_len, aux=aux)


def graph_key(cfg) -> str:
    return "nbr" if cfg.graph_repr == "sparse" else "adj"


@contextlib.contextmanager
def altered_refresh():
    """While open, the program's GGC refresh (`repro.core.dpfl`'s dense
    and sparse one) alters one client's answer where it makes it, before
    the round mixes: the client with the most peers takes the candidates
    it turned down instead (at most B of them, in id order)."""
    import jax.numpy as jnp

    from repro.core import dpfl

    dense, sparse = dpfl.all_clients_graph, dpfl.all_clients_graph_sparse

    def dense_altered(key, flat, p, omega, reward_fn, budget, **kw):
        adj = dense(key, flat, p, omega, reward_fn, budget, **kw)
        eye = jnp.eye(adj.shape[0], dtype=bool)
        k = jnp.argmax((adj & ~eye).sum(1))
        other = omega[k] & ~adj[k] & ~eye[k]
        other = other & (jnp.cumsum(other) <= budget)
        return adj.at[k].set(other | eye[k])

    def sparse_altered(key, flat, p, omega, reward_fn, budget, **kw):
        nbr = sparse(key, flat, p, omega, reward_fn, budget, **kw)
        k = jnp.argmax((nbr >= 0).sum(1))
        chosen = (omega[k][:, None] == nbr[k][None, :]).any(1)
        big = jnp.iinfo(nbr.dtype).max
        other = jnp.sort(jnp.where((omega[k] >= 0) & ~chosen, omega[k],
                                   big))[:nbr.shape[1]]
        return nbr.at[k].set(jnp.where(other == big, -1, other))

    dpfl.all_clients_graph = dense_altered
    dpfl.all_clients_graph_sparse = sparse_altered
    try:
        yield
    finally:
        dpfl.all_clients_graph = dense
        dpfl.all_clients_graph_sparse = sparse


# ------------------------------------------------------------------ run


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, peak,
             devices, plant=None, **kw) -> dict:
    """One run of ``cell``; returns the result line's dict."""
    with (altered_refresh() if plant == "altered"
          else contextlib.nullcontext()):
        return _run_cell(cell, seed, seconds, trace, peak, devices, plant,
                         **kw)


def _run_cell(cell, seed, seconds, trace, peak, devices, plant, keep=None):
    import jax
    import numpy as np

    from repro.core.dpfl import (_cached_refresh, _preprocess,
                                 dpfl_round_step)
    from repro.fl.round_engine import run_rounds
    from repro.analysis.guards import recompile_sentinel

    import check
    import data as data_mod
    import reference
    import trace_reduce

    conf, tr = cell["config"], cell["traffic"]
    dep, train = conf["deployment"], conf["training"]
    model = conf["model"]

    # ---- set-up: data, engine, preprocessing, the first rounds
    data = data_mod.make_data(dep, seed)
    engine = make_engine(cell, data, plant)
    cfg = dpfl_config(cell, seed)
    budget = cfg.budget
    reward_fn = engine.make_reward_fn()
    jax.block_until_ready(_preprocess(engine, cfg, reward_fn, budget))
    t0 = time.perf_counter()
    omega, flat, k_graph, k_train = jax.block_until_ready(
        _preprocess(engine, cfg, reward_fn, budget))
    preprocess_s = time.perf_counter() - t0
    log(f"preprocess_s={preprocess_s:.4f}")

    step = dpfl_round_step(engine, cfg)
    gk = graph_key(cfg)
    width = max(1, min(budget, dep["n_clients"] - 1))
    omega_h = np.asarray(omega)
    state = initial_state(engine, cfg, omega, flat, k_graph, k_train)
    rec = {"S0": np.asarray(state.flat), "S": [], "val": [], "graphs": []}
    for t in range(CHECKED_ROUNDS):
        if plant == "unchanged":
            # the step hands its state back unchanged but for the counter
            state = dataclasses.replace(state, t=state.t + 1)
            state = jax.block_until_ready(state)
        elif t == 0:
            # the first call traces and compiles. It is made outside
            # run_rounds' no_transfer guard: the trace reads the client
            # weights engine.p, a device array, as a constant, which is
            # a device-to-host transfer on a TPU
            state = jax.block_until_ready(step(state))
        else:
            state = jax.block_until_ready(run_rounds(step, state, 1))
        rec["S"].append(np.asarray(state.flat))
        rec["val"].append(np.asarray(state.val_hist[t]))
        rec["graphs"].append(check.as_lists(state.aux[gk], width))
    rec["best"] = np.asarray(state.best_val)

    probes = {}
    if trace:
        probes = warm_probes(engine, cfg, reward_fn, state, omega, gk,
                             _cached_refresh)
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)

    # ---- the window
    done = 0
    with recompile_sentinel(step, max_new=1 << 30) as compiles:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t_first = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("round.dispatch"):
                    state = run_rounds(step, state, 1)
                with jax.profiler.TraceAnnotation("round.wait"):
                    state = jax.block_until_ready(state)
                done += 1
                window_s = time.perf_counter() - t_first
                if window_s >= seconds:
                    break
    setup_s = t_first - T_START
    t_end = int(state.t)
    if t_end >= ROUNDS_CAP:
        raise RuntimeError(f"the window reached the round cap "
                           f"{ROUNDS_CAP}: raise ROUNDS_CAP")
    rounds_per_s = done / window_s
    log(f"window: {done} rounds in {window_s:.4f}s, setup_s={setup_s:.4f}")

    run = {"window_compiles": compiles.new_compiles(), "peak": peak,
           "rounds": done, "rounds_per_s": rounds_per_s}
    breakdown = None
    if trace:
        for name, call in probes.items():
            # idle gaps between the calls tell them apart in the trace
            time.sleep(4 * SLACK_NS * 1e-9)
            with jax.profiler.TraceAnnotation("chipbench." + name):
                jax.block_until_ready(call())
        jax.profiler.stop_trace()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)

    # ---- every round's download counter, then the state is freed
    counters = np.asarray(state.aux["comm"][:t_end])
    hist = [check.as_lists(g, width)
            for g in np.asarray(state.aux["graph_hist"][:t_end])]
    omega_l = check.as_lists(omega_h, width)
    comm_gaps = [abs(int(c) - w) for c, w in zip(
        counters, check.expected_comm(omega_l, hist, cfg.refresh_period))]
    broken = [check.broken_lists(omega_l, g, budget) for g in hist]
    window_graphs = hist[t_end - done:]
    last_graph = hist[-1]
    del state, step, engine, probes, reward_fn, flat, omega
    if trace:
        tr_data = trace_reduce.load(
            trace_reduce.find_xplane(trace_dir),
            span_names={"chipbench.window", "round.dispatch",
                        "round.wait", "chipbench.train_call",
                        "chipbench.refresh_call", "chipbench.mix_call"})
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.update(layer_inputs(cell, tr_data, omega_h, width,
                                window_graphs, last_graph))
        lo, hi = trace_reduce.span(tr_data, "chipbench.window")
        ops = tr_data.devices[0] if tr_data.devices else []
        breakdown = {"device_ops": trace_reduce.top_ops(ops, lo, hi),
                     "idle_gaps": trace_reduce.idle_gaps(
                         ops, tr_data.spans, lo, hi)}

    # ---- correctness, with the program's state freed
    ref_data = {k: getattr(data, k) for k in ("train_x", "train_y",
                                              "val_x", "val_y")}
    args = dict(model=model, opt=train, data=ref_data, p=data.p, seed=seed,
                omega=omega_l, rounds=CHECKED_ROUNDS, budget=budget,
                tau_init=train["tau_init"], tau=tr["tau_train"],
                period=cfg.refresh_period)
    if plant == "control":
        import jax.numpy as jnp

        rec = reference.follow(graphs=None, dt=jnp.bfloat16,
                               prec=reference.DEFAULT, **args)
    t0 = time.perf_counter()
    prec = reference.PRECISIONS[conf["matmul_precision"]]
    ref = reference.follow(graphs=rec["graphs"], prec=prec, **args)
    log(f"reference_s={time.perf_counter() - t0:.4f}")
    if keep is not None:
        keep.update(rec=rec, ref=ref, args=args, prec=prec)
    values = {"comm": max(comm_gaps), "graph": sum(broken),
              **check.numbers(reference.leaf_slices(model), rec, ref)}
    correct, checks = check.verdict(values, cell["limits"])

    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"rounds_per_s": rounds_per_s, "preprocess_s": preprocess_s,
               "peak_hbm_gib": peak_bytes / 2 ** 30, "setup_s": setup_s}
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    if trace:
        device["busy_s"] = run["busy_s"]
        device["window_s"] = run["window_s"]
    out = {"correct": bool(correct), "attempted": done,
           "failed": sum(g > 0 or b > 0 for g, b in zip(
               comm_gaps[t_end - done:], broken[t_end - done:])),
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def warm_probes(engine, cfg, reward_fn, state, omega, gk, cached_refresh):
    """One call each of local training, the GGC refresh and the Eq.-4
    mix, made as the round makes them, at the run's shapes; each is run
    once here so that it is compiled before the profiler starts."""
    import jax

    from repro.core.graph import (mix_flat, mix_flat_sparse,
                                  mixing_matrix, sparse_mixing_weights)

    flat = state.flat + 0.0
    graph = state.aux[gk] + 0
    key = jax.random.PRNGKey(cfg.seed)
    stacked = engine.unflatten(flat)
    refresh = cached_refresh(engine, cfg, reward_fn, cfg.budget)
    # the mix as the aggregate calls it, with its weights made beforehand
    if gk == "nbr":
        self_w, nbr_w = sparse_mixing_weights(graph, engine.p)
        mix_fn = jax.jit(lambda a, b, i, w: mix_flat_sparse(
            a, b, i, w, impl=cfg.mix_impl))
        mix_args = (self_w, nbr_w, graph, flat)
    else:
        a = mixing_matrix(graph, engine.p)
        mix_fn = jax.jit(lambda a, w: mix_flat(a, w, impl=cfg.mix_impl))
        mix_args = (a, flat)
    calls = {
        "train_call": lambda: engine.local_train(stacked, key,
                                                 epochs=cfg.tau_train),
        "refresh_call": lambda: refresh(key, flat, engine.p, omega, None),
        "mix_call": lambda: mix_fn(*mix_args),
    }
    for call in calls.values():
        jax.block_until_ready(call())
    return calls


def layer_inputs(cell, tr_data, omega, width, window_graphs, last_graph):
    """What the per-layer readers read: spans, busy time, and the
    algorithm's work of a round and of the mix call."""
    import numpy as np

    import check
    import flops
    import trace_reduce

    conf = cell["config"]
    model, dep, train = conf["model"], conf["deployment"], conf["training"]
    lo, hi = trace_reduce.span(tr_data, "chipbench.window")
    n_params = flops.cnn_params(model)
    omega_l = check.as_lists(omega, width)
    period = cell["traffic"]["refresh_period"]
    n_done = len(window_graphs)
    t0 = CHECKED_ROUNDS
    per_round = [flops.round_flops(model, dep, train, cell["traffic"],
                                   omega_l, g,
                                   refresh=((t0 + i) % period == 0))
                 for i, g in enumerate(window_graphs)]
    calls = {}
    for name in ("train_call", "refresh_call", "mix_call"):
        s, e = trace_reduce.span(tr_data, "chipbench." + name)
        calls[name] = sum(trace_reduce.call_ns(ops, s, e, SLACK_NS)
                          for ops in tr_data.devices) * 1e-9 / \
            max(1, len(tr_data.devices))
    busy = trace_reduce.mean_busy_ns(tr_data, lo, hi) * 1e-9
    return {
        "trace": tr_data, "busy_s": busy, "window_s": (hi - lo) * 1e-9,
        "traced_rounds": n_done,
        "round_flops": float(np.mean(per_round)) if per_round else None,
        "call_s": calls,
        "mix_flops": flops.mix_flops(n_params, last_graph),
        "mix_bytes": flops.mix_bytes(n_params, last_graph),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", choices=PLANTS, default=None)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        import jax

        devices = require_chips(jax, cell["chips"])
        peak = device_peak(devices[0].device_kind)
        log(f"compile cache: {use_cache(jax)}")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        require_pallas()
        dpfl_config(cell, args.seed)
    except (Refusal, FileNotFoundError, KeyError, ImportError) as e:
        log(f"run.py: {type(e).__name__}: {e}")
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), peak,
                   devices, args.plant)
    for name, (value, limit) in out["checks"].items():
        log(f"check {name}: {value!r} limit {limit!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
