"""`phases.py`'s reduction of a window to per-phase device time, on
made-up events: two executions of ``jit_round_step`` on a device clock
2 ns behind the host's, one execution of another program."""
import pytest

import phases
import trace_align as ta
import trace_reduce as tr

OPS = sorted(
    [(10 + d, 40 + d, "%fusion.1 = f32[8] fusion(%a)") for d in (0, 90)]
    + [(40 + d, 70 + d, "%fusion.2 = f32[8] fusion(%a)") for d in (0, 90)]
    + [(70 + d, 78 + d, "%graph_mix.3 = f32[8] custom-call(%a)")
       for d in (0, 90)]
    + [(78 + d, 80 + d, "%copy.9 = f32[8] copy(%a)") for d in (0, 90)]
    + [(180, 190, "%fusion.1 = f32[8] fusion(%a)")])
SCOPES = {"fusion.1": "round.train", "fusion.2": "round.refresh",
          "graph_mix.3": "round.mix"}


def test_window_breakdown_by_phase():
    trace = tr.Trace(devices=[OPS], spans={
        "chipbench.window": [(5, 200)],
        "round.wait": [(20, 84), (110, 174)],
        "dpfl.round": [(10, 13), (83, 103)]})
    tl = ta.Timeline(
        modules=[[(10, 80, "jit_round_step", 1), (100, 170,
                                                 "jit_round_step", 2),
                  (180, 190, "jit_train_fn", 3)]],
        enqueues={1: 12, 2: 101, 3: 181})
    out = phases.window_breakdown(trace, tl, SCOPES, done=2, refreshes=1)
    m = out["metrics"]
    assert m["round.train_ms"] == pytest.approx(30e-6)
    # per refreshing round: one of the two refreshes
    assert m["round.refresh_ms"] == pytest.approx(60e-6)
    assert m["round.mix_ms"] == pytest.approx(8e-6)
    assert m["round.eval_ms"] is None
    assert out["round_executions"] == 2
    assert out["breakdown"]["clock_offset_us"] == pytest.approx(2e-3)
    assert out["breakdown"]["phases"]["unscoped"]["s"] == \
        pytest.approx(4e-9)
    # the window on the device's clock [3, 198] holds both rounds and the
    # other program: busy 70 + 70 + 10
    assert out["busy_ms_per_round"] == pytest.approx(75e-6)
    assert out["phase_sum_ms_per_round"] == pytest.approx(70e-6)
    assert out["round_busy_ms_per_round"] == pytest.approx(70e-6)
    assert out["unscoped_share"] == pytest.approx(4 / 150)
    # on the host's clock the device idles in [82, 102) between the
    # rounds, mostly inside the second dispatch
    gaps = out["breakdown"]["idle_gaps_aligned"]
    assert gaps[0] == ["dpfl.round", pytest.approx(20e-9)]
    assert [label for label, _ in gaps[1:]] == ["chipbench.window"] * 3


def test_window_breakdown_without_device_planes():
    out = phases.window_breakdown(tr.Trace(), ta.Timeline(), {}, 1, 1)
    assert out == {"metrics": {p + "_ms": None for p in phases.PHASES}}


def test_preprocess_stages_take_what_their_span_enqueued():
    # the train span enqueues two programs, the bggc span jit_bggc and a
    # stray one, the mix span one program that runs after the span ended
    trace = tr.Trace(devices=[[]], spans={
        "dpfl.preprocess.train": [(0, 10)],
        "dpfl.preprocess.bggc": [(10, 20)],
        "dpfl.preprocess.mix": [(20, 22)]})
    tl = ta.Timeline(
        modules=[[(2, 8, "jit_init", 1), (8, 30, "jit_train_fn", 2),
                  (30, 70, "jit_bggc", 3), (70, 71, "jit_other", 4),
                  (71, 75, "jit_graph_mix", 5)]],
        enqueues={1: 1, 2: 5, 3: 12, 4: 15, 5: 21})
    assert phases.preprocess_ms(trace, tl) == {
        "preprocess.train_ms": pytest.approx(28e-6),
        "preprocess.bggc_ms": pytest.approx(40e-6),
        "preprocess.mix_ms": pytest.approx(4e-6)}
    assert phases.preprocess_ms(tr.Trace(), ta.Timeline()) == {
        "preprocess.train_ms": None, "preprocess.bggc_ms": None,
        "preprocess.mix_ms": None}
