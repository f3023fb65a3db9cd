#!/usr/bin/env python3
"""Readings of the numbers that decide ``correct``, for setting a cell's
limits (``limits/<cell>.json``): many seeds and plants in one process.

    python3 chipbench/calibrate.py <cell> [--out readings.jsonl] \\
        <seed>:<plant> ...

``plant`` is ``none`` (a sound run) or one of `run.py`'s ``--plant``
choices (the control and the faults). Each job is one `run.run_cell` at
the cell's own size with a 2-second window, and prints one JSON line:
the seed, the plant, the numbers compared and ``detail``: the per-leaf
terms of ``change`` and ``panel``, ``panel`` after each round (round 0:
the panel round 1 starts from) and the five worst clients' terms of
``client``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def detail(slices: dict, got: dict, want: dict) -> dict:
    """The terms behind ``check.numbers``' worst-leaf and worst-client
    readings, for finding where a gap comes from."""
    import numpy as np

    import check

    def f64(a):
        return np.asarray(a, np.float64)

    d_want = f64(want["S"][-1]) - f64(want["S0"])
    n_want = check.leaf_norms(d_want, slices)
    scale = np.maximum(n_want, np.median(n_want))
    n_got = check.leaf_norms(f64(got["S"][-1]) - f64(got["S0"]), slices)
    by_round = [float(np.max(check.leaf_norms(f64(a) - f64(b), slices)
                             / scale))
                for a, b in zip([got["S0"]] + got["S"],
                                [want["S0"]] + want["S"])]
    moved = np.linalg.norm(d_want, axis=1)
    clients = (np.linalg.norm(f64(got["S"][-1]) - f64(want["S"][-1]),
                              axis=1) / np.maximum(moved, np.median(moved)))
    worst = np.argsort(clients)[::-1][:5]
    return {
        "leaf_change": dict(zip(slices, (np.abs(n_got - n_want)
                                         / scale).tolist())),
        "leaf_panel": dict(zip(slices, (check.leaf_norms(
            f64(got["S"][-1]) - f64(want["S"][-1]), slices)
            / scale).tolist())),
        "panel_by_round": by_round,
        "worst_clients": [[int(k), float(clients[k])] for k in worst],
        "client_quantiles": np.quantile(clients, [0.5, 0.75, 0.9, 1.0])
        .tolist(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell")
    ap.add_argument("jobs", nargs="+", help="seed:plant")
    ap.add_argument("--out")
    ap.add_argument("--sensitivity", action="store_true",
                    help="also follow sound runs with the reference from "
                         "an initial panel scaled by 1 + 1e-7 (one float32 "
                         "step), and read the reference against itself "
                         "(``sensitivity``)")
    args = ap.parse_args(argv)
    cell = run.load_cell(args.cell)
    import jax

    devices = run.require_chips(jax, cell["chips"])
    peak = run.device_peak(devices[0].device_kind)
    run.use_cache(jax)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.require_pallas()
    import check
    import reference

    for job in args.jobs:
        seed, plant = job.split(":")
        t0 = time.perf_counter()
        keep = {}
        try:
            out = run.run_cell(cell, int(seed), 2.0, False, peak, devices,
                               None if plant == "none" else plant,
                               keep=keep)
            slices = reference.leaf_slices(keep["args"]["model"])
            rec = {"seed": int(seed), "plant": plant,
                   "checks": out["checks"], "correct": out["correct"],
                   "detail": detail(slices, keep["rec"], keep["ref"])}
        except Exception as e:  # one failed job does not end the others
            traceback.print_exc()
            rec = {"seed": int(seed), "plant": plant, "error": repr(e)}
        if args.sensitivity and plant == "none" and "error" not in rec:
            init = reference.init_panel
            reference.init_panel = lambda *a: init(*a) * (1 + 1e-7)
            try:
                moved = reference.follow(graphs=keep["rec"]["graphs"],
                                         prec=keep["prec"], **keep["args"])
            finally:
                reference.init_panel = init
            rec["sensitivity"] = {
                "checks": check.numbers(slices, moved, keep["ref"]),
                "detail": detail(slices, moved, keep["ref"])}
        rec["s"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
