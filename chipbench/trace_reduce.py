"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane. Host spans are the `jax.profiler.TraceAnnotation`
events of the host plane, found by name. Both carry times in nanoseconds;
on a TPU v5e host the device's times run about 1.25 ms behind the host's
(a small trace recorded on the chip, ``tests/data``), so the work of a
call is the device work that starts within ``slack`` of the host span
around it, and the caller leaves idle gaps longer than the slack between
the calls it wants told apart.

- busy time: the union of device-operation intervals inside a window
  (overlapping operations count once), averaged over devices;
- per-kernel time: the summed durations of the operations of one name;
- device time of a call: the busy time inside the host span around it;
- idle gaps: the stretches inside a window with no device operation,
  each labelled by the host span that covers most of it.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"


@dataclass
class Trace:
    # per device: sorted (start_ns, end_ns, name) of every operation
    devices: list = field(default_factory=list)
    # host spans: name -> sorted [(start_ns, end_ns)]
    spans: dict = field(default_factory=dict)


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a `jax.profiler.start_trace` dir."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str, span_names=None) -> Trace:
    """Read device operations and host spans from ``path``. Only host
    events whose name is in ``span_names`` are kept (all, when None)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            if ops:
                tr.devices.append(sorted(ops))
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if span_names is None or e.name in span_names:
                        tr.spans.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    for v in tr.spans.values():
        v.sort()
    return tr


def merged(ops, lo: float, hi: float):
    """The union of the operation intervals clipped to [lo, hi], as
    sorted disjoint (start, end) pairs."""
    out = []
    for s, e, _ in ops:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(ops, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some operation ran."""
    return sum(e - s for s, e in merged(ops, lo, hi))


def mean_busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """`busy_ns` averaged over the trace's devices."""
    if not tr.devices:
        return 0.0
    return sum(busy_ns(ops, lo, hi) for ops in tr.devices) / len(tr.devices)


def call_ns(ops, lo: float, hi: float, slack: float) -> float:
    """Device time of the call made inside the host span [lo, hi]: the
    union of the operations that start within ``slack`` of it."""
    return busy_ns([o for o in ops if lo - slack <= o[0] < hi + slack],
                   lo - slack, float("inf"))


def short_name(name: str) -> str:
    """An HLO operation's name without its text or number:
    ``%graph_mix.1 = f32[...] custom-call(...)`` -> ``graph_mix``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def kernel_ns(ops, lo: float, hi: float) -> dict:
    """short name -> summed duration of the operations of that name that
    start inside [lo, hi]."""
    out = {}
    for s, e, name in ops:
        if lo <= s < hi:
            key = short_name(name)
            out[key] = out.get(key, 0.0) + (e - s)
    return out


def leaf_ops(ops):
    """The operations that enclose no other: a ``while`` or a
    ``conditional`` is listed beside the operations of its body, and
    counting both would count the body twice."""
    out = []
    for i, (s, e, name) in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt[0] < e and nxt[1] <= e):
            out.append((s, e, name))
    return out


def top_ops(ops, lo: float, hi: float, n: int = 10):
    """The ``n`` operation names with the most device time in [lo, hi],
    as [name, seconds]; enclosing operations are left out."""
    k = kernel_ns(leaf_ops(ops), lo, hi)
    return [[name, ns * 1e-9] for name, ns in
            sorted(k.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, spans: dict, lo: float, hi: float, n: int = 10):
    """The ``n`` longest stretches of [lo, hi] without a device operation,
    as [label, seconds]; the label is the host span that overlaps the gap
    most (the narrowest such span on a tie), or ``"no host span"``."""
    busy = merged(ops, lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, label = (0.0, 0.0), "no host span"
        for name, ivs in spans.items():
            for s, e in ivs:
                cover = min(ge, e) - max(gs, s)
                if cover > 0 and (cover, -(e - s)) > best:
                    best, label = (cover, -(e - s)), name
        out.append([label, (ge - gs) * 1e-9])
    return out


def span(tr: Trace, name: str, index: int = -1):
    """The (start, end) of host span ``name``: its last occurrence by
    default. Raises KeyError when the trace has none."""
    return tr.spans[name][index]
