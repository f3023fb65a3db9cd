"""One clock for device and host, and device time by phase: the
``XLA Modules`` line and the ``run_id`` offset on the small trace
recorded on a TPU v5e chip (``data/v5e_small.xplane.pb``: three bf16
1024x1024 matmuls, each its own execution of one program, inside the
host span ``probe.matmul``, then one `graph_mix` program inside
``probe.mix``), and the attribution on made-up events."""
import os

import pytest

import trace_align as ta
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")


def test_modules_line_and_run_id_offset_on_recorded_trace():
    tl = ta.load_timeline(RECORDED)
    assert len(tl.modules) == 1
    mods = tl.modules[0]
    assert [r for *_, r in mods] == [9, 10, 11, 12]
    assert {name for _, _, name, _ in mods} == {"jit__lambda"}
    assert set(tl.enqueues) >= {9, 10, 11, 12}
    # on the device's stamps each program starts before the host began
    # to enqueue it: the two clocks differ by 1.4-1.5 ms
    for s, _, _, r in mods:
        assert 1.3e6 < tl.enqueues[r] - s < 1.6e6
    offset = ta.clock_offset_ns(mods, tl.enqueues)
    assert 1.3e6 <= offset <= 1.6e6
    # aligned, every op of a call lies inside the host span around it
    t = tr.load(RECORDED, span_names={"probe.matmul", "probe.mix"})
    (ms, me), (xs, xe) = tr.span(t, "probe.matmul"), tr.span(t, "probe.mix")
    ops = t.devices[0]
    assert ops[0][0] < ms        # unaligned: before its span
    inside = [(s + offset, e + offset) for s, e, _ in ops]
    assert all(ms <= s and e <= me or xs <= s and e <= xe
               for s, e in inside)
    # the work each span dispatched, by the run_id of its enqueue
    mat = ta.enqueued_in(mods, tl.enqueues, "jit__lambda", ms, me)
    mix = ta.enqueued_in(mods, tl.enqueues, "jit__lambda", xs, xe)
    assert len(mat) == 3 and len(mix) == 1
    assert ta.enqueued_in(mods, tl.enqueues, None, ms, me) == mat
    assert ta.module_intervals(mods, "jit__lambda") == mat + mix
    assert ta.module_intervals(mods, "jit_other") == []
    assert ta.clock_offset_ns(mods, {}) is None


def test_names():
    assert ta.program_name("jit_round_step(123456)") == "jit_round_step"
    assert ta.instr_name("%fusion.12 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "fusion.12"
    assert ta.instr_name("%copy-start.3 = (f32[8]) copy-start(%a)") \
        == "copy-start.3"


def test_phase_attribution_from_instruction_scopes():
    ops = sorted([
        (0, 100, "%while.1 = (f32[8]) while(%t), body=%b"),
        (10, 40, "%fusion.1 = f32[8] fusion(%a)"),
        (50, 90, "%fusion.2 = f32[8] fusion(%a)"),
        (95, 99, "%copy.7 = f32[8] copy(%a)"),
        (110, 130, "%graph_mix.3 = f32[8] custom-call(%a)"),
        (200, 220, "%fusion.1 = f32[8] fusion(%a)"),   # a second run
        (300, 310, "%fusion.9 = f32[8] fusion(%a)"),   # another program
    ])
    scopes = {"fusion.1": "round.train", "fusion.2": "round.refresh",
              "graph_mix.3": "round.mix", "while.1": "round.train"}
    ph = ta.phase_ns(ops, [(0, 150), (200, 250)], scopes)
    assert set(ph) == {"round.train", "round.refresh", "round.mix",
                       "unscoped"}
    # the enclosing while is not counted beside its body
    assert ph["round.train"]["ns"] == 30 + 20
    assert ph["round.refresh"]["ns"] == 40
    assert ph["round.mix"]["ns"] == 20
    assert ph["unscoped"]["ns"] == 4
    assert ph["round.train"]["top"] == [["fusion", pytest.approx(50e-9)]]
    assert ph["unscoped"]["top"] == [["copy", pytest.approx(4e-9)]]
    assert ta.phase_ns(ops, [], scopes) == {}


def test_aligned_gaps_take_the_narrowest_covering_span():
    # device ops on a clock 5 behind the host's
    ops = [(0, 20, "a"), (35, 60, "b"), (95, 100, "c")]
    spans = {"window": [(0, 120)],
             "dpfl.round": [(24, 38), (62, 98)],
             "round.dispatch": [(22, 40), (61, 99)],
             "round.wait": [(40, 66)]}
    gaps = ta.idle_gaps_aligned(ops, spans, 0, 120, offset=5)
    # on the host's clock the device is idle in [0, 5), [25, 40),
    # [65, 100) and [105, 120): each gap takes the span that is innermost
    # over most of it, not the window that covers it all
    assert gaps[0] == ["dpfl.round", pytest.approx(35e-9)]
    assert ["dpfl.round", pytest.approx(15e-9)] in gaps
    assert ["window", pytest.approx(15e-9)] in gaps
    assert gaps[-1] == ["window", pytest.approx(5e-9)]
    assert sum(g for _, g in gaps) == pytest.approx(70e-9)
    # the unaligned gaps of trace_reduce name the widest span
    assert {label for label, _ in tr.idle_gaps(ops, spans, 0, 120)} \
        == {"window"}
    assert ta.idle_gaps_aligned(ops, {}, 0, 120, 5)[0][0] == "no host span"
