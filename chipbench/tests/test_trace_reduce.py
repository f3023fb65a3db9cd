"""The trace reduction: interval arithmetic on made-up events, and the
loader on a small trace recorded on a TPU v5e chip
(``data/v5e_small.xplane.pb``: three bf16 1024x1024 matmuls inside the
host span ``probe.matmul``, then one `graph_mix` kernel inside
``probe.mix``)."""
import os


import pytest

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")

OPS = sorted([(0, 10, "a"), (5, 15, "b"), (20, 30, "a"), (40, 45, "c"),
              (44, 50, "a")])


def test_busy_is_a_union_of_intervals():
    # [0,15] u [20,30] u [40,50]: overlaps count once
    assert tr.busy_ns(OPS, 0, 60) == 15 + 10 + 10
    # clipped to the window
    assert tr.busy_ns(OPS, 8, 42) == 7 + 10 + 2
    assert tr.busy_ns(OPS, 31, 39) == 0
    assert tr.merged(OPS, 0, 60) == [[0, 15], [20, 30], [40, 50]]


def test_enclosing_ops_are_not_counted_twice():
    ops = sorted([(0, 100, "while"), (10, 40, "fusion.1"),
                  (50, 90, "fusion.2"), (120, 130, "copy")])
    assert tr.leaf_ops(ops) == [(10, 40, "fusion.1"), (50, 90, "fusion.2"),
                                (120, 130, "copy")]
    assert tr.top_ops(ops, 0, 200) == [["fusion", pytest.approx(70e-9)],
                                       ["copy", pytest.approx(10e-9)]]
    assert tr.busy_ns(ops, 0, 200) == 110


def test_mean_busy_averages_devices():
    t = tr.Trace(devices=[OPS, [(0, 60, "x")]])
    assert tr.mean_busy_ns(t, 0, 60) == (35 + 60) / 2
    assert tr.mean_busy_ns(tr.Trace(), 0, 60) == 0.0


def test_per_kernel_sums():
    assert tr.kernel_ns(OPS, 0, 60) == {"a": 10 + 10 + 6, "b": 10, "c": 5}
    # an op belongs to the interval it starts in
    assert tr.kernel_ns(OPS, 19, 41) == {"a": 10, "c": 5}
    top = tr.top_ops(OPS, 0, 60, n=2)
    assert [name for name, _ in top] == ["a", "b"]
    assert top[0][1] == pytest.approx(26e-9)


def test_attribution_by_host_interval():
    spans = {"train": [(0, 16)], "mix": [(18, 52)], "window": [(0, 60)]}
    s, e = spans["mix"][0]
    assert tr.busy_ns(OPS, s, e) == 10 + 10
    gaps = tr.idle_gaps(OPS, spans, 0, 60)
    # the longest gap [30,40] lies inside "mix" and "window": the
    # narrower span names it; the tail [50,60] only inside "window"
    assert gaps[0] == ["mix", pytest.approx(10e-9)]
    assert ["window", pytest.approx(10e-9)] in gaps
    assert sum(g for _, g in gaps) == pytest.approx(25e-9)


def test_call_time_within_slack():
    # device clock behind the host's: the call's ops start before its span
    ops = [(95, 100, "a"), (100, 104, "b"), (300, 310, "c")]
    assert tr.call_ns(ops, 98, 120, slack=5) == 9
    assert tr.call_ns(ops, 98, 120, slack=1) == 4
    assert tr.short_name("%graph_mix.12 = f32[16,4096] custom-call(...)") \
        == "graph_mix"
    assert tr.short_name("%fusion = f32[] fusion(...)") == "fusion"


def test_recorded_v5e_trace():
    t = tr.load(RECORDED, span_names={"probe.matmul", "probe.mix"})
    assert len(t.devices) == 1
    ops = t.devices[0]
    slack = 5e6
    (ms, me), (xs, xe) = tr.span(t, "probe.matmul"), tr.span(t, "probe.mix")
    # the device's clock runs about 1.25 ms behind the host's here: the
    # first matmul starts before its host span, but within the slack
    assert ops[0][0] < ms < ops[0][0] + slack
    mat = tr.call_ns(ops, ms, me, slack)
    mix = tr.call_ns(ops, xs, xe, slack)
    # three matmuls of 2*1024^3 bf16 FLOPs: at least what the 197 TFLOP/s
    # peak allows, and less than the host span
    assert 3 * 2 * 1024 ** 3 / 197e12 * 1e9 <= mat < me - ms
    assert 0 < mix < xe - xs
    # the whole trace's device time is the two calls' and nothing else
    assert tr.busy_ns(ops, 0, float("inf")) == pytest.approx(mat + mix)
    kernels = tr.kernel_ns(ops, xs - slack, xe + slack)
    assert list(kernels) == ["graph_mix"]
    assert kernels["graph_mix"] == pytest.approx(mix)
    assert tr.top_ops(ops, 0, float("inf"), n=1)[0][0] == \
        "convert_reduce_fusion"
