#!/usr/bin/env python3
"""Device time of the compiled DPFL round by phase, on the chip.

    python chipbench/phases.py --workload <name> --seed <n> --seconds <s>

Builds a cell of ``BENCHMARK.json`` as ``run.py`` does (same data,
engine, preprocessing and `RoundState`; the same refusals), then:

1. runs the second preprocessing under a profiler trace of its own and
   reads the device time of the programs that each of the program's
   stage spans ``dpfl.preprocess.{train,bggc,mix}`` enqueued (for
   ``bggc``, of the ``jit_bggc`` executions alone): the three split
   ``preprocess_s``;
2. after two rounds, runs a window of rounds under the profiler, each
   dispatched through `run_rounds` and waited for as ``run.py``'s window
   does (spans ``chipbench.window``, ``round.dispatch``, ``round.wait``;
   the program adds ``dpfl.round``);
3. attributes each device op of the window's ``jit_round_step``
   executions to the program's phase scope (``round.train``,
   ``round.refresh``, ``round.mix``, ``round.eval``; `trace_align`,
   `repro.roofline.hlo.instruction_scopes` of the compiled step), puts
   the idle gaps on the host's clock, and sets the reward probes the
   refresh executes (``program.counts["ggc.probes"]``) against those the
   algorithm needs (4·Σ|Ω_k∖{k}|).

Prints one JSON line: ``metrics`` (``round.<phase>_ms`` per round of the
window, ``round.refresh_ms`` per refreshing round,
``preprocess.{train,bggc,mix}_ms``, ``refresh.probe_ratio``), the
window's device time per round and ``breakdown`` (``phases`` with each phase's largest ops,
``clock_offset_us``, ``idle_gaps_aligned`` beside ``trace_reduce``'s
``idle_gaps``). A program without the scopes, names or counter reads
``None`` where they are missing.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import run

PHASES = ("round.train", "round.refresh", "round.mix", "round.eval")
SPANS = {"chipbench.window", "round.dispatch", "round.wait", "dpfl.round"}
# the preprocessing's stage spans -> the program whose executions count
# (None: every program the span enqueued)
STAGES = {"dpfl.preprocess.train": None, "dpfl.preprocess.bggc": "jit_bggc",
          "dpfl.preprocess.mix": None}


def traced(fn, prefix: str):
    """``fn()`` under the profiler; returns (its result, the .xplane.pb
    path, the trace directory)."""
    import jax

    import trace_reduce

    trace_dir = tempfile.mkdtemp(prefix=prefix)
    jax.profiler.start_trace(trace_dir)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, trace_reduce.find_xplane(trace_dir), trace_dir


def preprocess_ms(tr, tl) -> dict:
    """``preprocess.<stage>_ms`` for each stage span of `STAGES` in a
    trace of the preprocessing (`trace_reduce.load`, its last occurrence;
    `trace_align.load_timeline`): the device time of the executions the
    span enqueued, in ms averaged over devices; None where the trace has
    no such span or execution."""
    import trace_align
    import trace_reduce

    out = {}
    for name, program in STAGES.items():
        runs = []
        if name in tr.spans and tl.modules:
            s, e = trace_reduce.span(tr, name)
            runs = [iv for mods in tl.modules for iv in
                    trace_align.enqueued_in(mods, tl.enqueues, program, s, e)]
        out["preprocess." + name.rsplit(".", 1)[1] + "_ms"] = (
            sum(b - a for a, b in runs) / len(tl.modules) * 1e-6
            if runs else None)
    return out


def measure(cell: dict, seed: int, seconds: float) -> dict:
    """Steps 1-3 of the module's docstring for ``cell`` (`run.load_cell`)
    on this process's first device; returns the result line's dict."""
    import jax
    import numpy as np

    from repro.core.dpfl import _preprocess, dpfl_round_step
    from repro.fl.round_engine import run_rounds
    from repro.roofline.hlo import instruction_scopes

    import data as data_mod
    import flops
    import trace_align
    import trace_reduce

    dep = cell["config"]["deployment"]
    data = data_mod.make_data(dep, seed)
    engine = run.make_engine(cell, data)
    cfg = run.dpfl_config(cell, seed)
    reward_fn = engine.make_reward_fn()
    jax.block_until_ready(_preprocess(engine, cfg, reward_fn, cfg.budget))
    (omega, flat, k_graph, k_train), pre_path, pre_dir = traced(
        lambda: jax.block_until_ready(
            _preprocess(engine, cfg, reward_fn, cfg.budget)),
        "phases-preprocess-")
    pre = preprocess_ms(trace_reduce.load(pre_path, span_names=set(STAGES)),
                        trace_align.load_timeline(pre_path))
    shutil.rmtree(pre_dir, ignore_errors=True)

    step = dpfl_round_step(engine, cfg)
    state = run.initial_state(engine, cfg, omega, flat, k_graph, k_train)
    # the first call compiles outside run_rounds' no_transfer guard, as
    # in run.py
    state = jax.block_until_ready(step(state))
    state = jax.block_until_ready(run_rounds(step, state, 1))
    t_first_round = int(state.t)

    def window():
        nonlocal state
        done = 0
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("round.dispatch"):
                    state = run_rounds(step, state, 1)
                with jax.profiler.TraceAnnotation("round.wait"):
                    state = jax.block_until_ready(state)
                done += 1
                if time.perf_counter() - t0 >= seconds:
                    return done, time.perf_counter() - t0

    (done, window_s), path, trace_dir = traced(window, "phases-window-")
    scopes = instruction_scopes(step.lower(state).compile().as_text(),
                                "round.")
    probes = getattr(step, "counts", {}).get("ggc.probes")
    period = cfg.refresh_period
    refreshes = sum((t_first_round + i) % period == 0 for i in range(done))
    needed = 4 * int(flops.peers_per_client(np.asarray(omega)).sum())

    tr = trace_reduce.load(path, span_names=SPANS)
    tl = trace_align.load_timeline(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    out = window_breakdown(tr, tl, scopes, done, refreshes)
    out["metrics"].update(pre)
    out["metrics"]["refresh.probe_ratio"] = (probes / needed
                                             if probes and needed else None)
    out.update(rounds=done, rounds_per_s=done / window_s,
               probes_per_refresh=probes, needed_per_refresh=needed)
    return out


def window_breakdown(tr, tl, scopes: dict, done: int, refreshes: int,
                     n_top: int = 5) -> dict:
    """The window's ``round.<phase>_ms`` (under ``metrics``) and its
    device time, from the window's trace (`trace_reduce.load`, with the
    span ``chipbench.window``), its timeline (`trace_align.load_timeline`)
    and the step's instruction scopes; ``done`` rounds were dispatched
    in the window, ``refreshes`` of them refresh the graph."""
    import trace_align
    import trace_reduce

    metrics = {p + "_ms": None for p in PHASES}
    if not tr.devices or not tl.modules:
        return {"metrics": metrics}
    lo, hi = trace_reduce.span(tr, "chipbench.window")
    ops, mods = tr.devices[0], tl.modules[0]
    offset = trace_align.clock_offset_ns(mods, tl.enqueues) or 0.0
    rounds = trace_align.module_intervals(mods, "jit_round_step",
                                          lo - offset, hi - offset)
    n = len(rounds)
    phases = trace_align.phase_ns(ops, rounds, scopes, n_top=n_top)
    for p in PHASES:
        if p in phases and n:
            per = n if p != "round.refresh" else max(1, refreshes)
            metrics[p + "_ms"] = phases[p]["ns"] * 1e-6 / per
    busy = trace_reduce.busy_ns(ops, lo - offset, hi - offset)
    in_rounds = sum(trace_reduce.busy_ns(ops, s, e) for s, e in rounds)
    unscoped = phases.get(trace_align.UNSCOPED, {"ns": 0.0})["ns"]
    return {
        "metrics": metrics,
        "round_executions": n,
        "refreshing_rounds": refreshes,
        "busy_ms_per_round": busy * 1e-6 / done,
        "round_busy_ms_per_round": in_rounds * 1e-6 / n if n else None,
        "phase_sum_ms_per_round": (sum(v["ns"] for v in phases.values())
                                   * 1e-6 / n if n else None),
        "unscoped_share": unscoped / busy if busy else None,
        "breakdown": {
            "phases": {p: {"s": v["ns"] * 1e-9, "top": v["top"]}
                       for p, v in phases.items()},
            "clock_offset_us": offset * 1e-3,
            "idle_gaps_aligned": trace_align.idle_gaps_aligned(
                ops, tr.spans, lo, hi, offset),
            "idle_gaps": trace_reduce.idle_gaps(ops, tr.spans, lo, hi)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        cell = run.load_cell(args.workload)
        import jax

        devices = run.require_chips(jax, cell["chips"])
        run.device_peak(devices[0].device_kind)
        run.use_cache(jax)
        sys.path.insert(0, os.path.join(run.ROOT, "src"))
        run.require_pallas()
        run.dpfl_config(cell, args.seed)
    except (run.Refusal, FileNotFoundError, KeyError, ImportError) as e:
        run.log(f"phases.py: {type(e).__name__}: {e}")
        return 2
    out = measure(cell, args.seed, args.seconds)
    out.update(workload=args.workload, seed=args.seed,
               device={"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
