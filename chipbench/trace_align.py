"""Device and host times of a profiler trace on one clock, and device
time by program phase.

`trace_reduce` reads the ``XLA Ops`` line of each TPU plane and the host
spans, each on its own clock. This module adds what ties the two clocks
together and names the work:

- the ``XLA Modules`` line of each TPU plane: one event per execution of
  a compiled program, named ``<program>(<fingerprint>)`` (``jit_round_step``
  for the program `FLEngine.jit` builds from ``round_step``), carrying the
  ``run_id`` of the execution;
- the host's ``DoEnqueueProgram`` events, which carry the same ``run_id``.

A program cannot start on the device before the host began to enqueue
it, so ``host = device + offset`` with the offset at least
``enqueue start - module start`` for every pair: the largest such
difference is the estimate (`clock_offset_ns`). On the chip trace in
``tests/data`` the device's stamps run 1.4 to 1.5 ms behind the host's;
on TPU v5e traces of the benchmark's cells they agreed within 40 us. The
offset is measured anew for each trace.

Phases: a compiled program's device ops carry only their HLO instruction
names; `repro.roofline.hlo.instruction_scopes` maps those names to the
program's `jax.named_scope` phases from the compiled HLO text, and
`phase_ns` sums each phase's device time over the executions of one
program (names are unique within a module, not across modules).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import trace_reduce

MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
UNSCOPED = "unscoped"


@dataclass
class Timeline:
    # per device: sorted (start_ns, end_ns, program, run_id) of every
    # program execution
    modules: list = field(default_factory=list)
    # run_id -> host time (ns) at which its first enqueue began
    enqueues: dict = field(default_factory=dict)


def program_name(event_name: str) -> str:
    """``jit_round_step(1234)`` -> ``jit_round_step``."""
    return event_name.split("(", 1)[0]


def instr_name(op_name: str) -> str:
    """An ``XLA Ops`` event's HLO instruction name:
    ``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return op_name.split(" = ", 1)[0].lstrip("%").strip()


def load_timeline(path: str) -> Timeline:
    """The program executions of each TPU plane and the host's enqueue
    times, by ``run_id``."""
    from jax.profiler import ProfileData

    tl = Timeline()
    with warnings.catch_warnings():
        # event stats are a builtin type that warns on some jax versions
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
                mods = []
                for line in plane.lines:
                    if line.name == MODULES_LINE:
                        mods += [(e.start_ns, e.start_ns + e.duration_ns,
                                  program_name(e.name),
                                  dict(e.stats).get("run_id"))
                                 for e in line.events]
                if mods:
                    tl.modules.append(sorted(mods))
            elif plane.name.startswith(trace_reduce.HOST_PREFIX):
                for line in plane.lines:
                    for e in line.events:
                        if e.name != ENQUEUE:
                            continue
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            tl.enqueues[run_id] = min(
                                e.start_ns,
                                tl.enqueues.get(run_id, float("inf")))
    return tl


def clock_offset_ns(modules, enqueues: dict):
    """Host time minus device time, from the program executions whose
    enqueue the host trace holds: the largest ``enqueue start - module
    start`` (a program starts after its enqueue began). None without a
    pair."""
    gaps = [enqueues[r] - s for s, _, _, r in modules if r in enqueues]
    return max(gaps) if gaps else None


def module_intervals(modules, program: str, lo=float("-inf"),
                     hi=float("inf")):
    """The (start, end) of the executions of ``program`` that start in
    [lo, hi) on the device's clock."""
    return [(s, e) for s, e, name, _ in modules
            if name == program and lo <= s < hi]


def enqueued_in(modules, enqueues: dict, program, lo, hi):
    """The (start, end), device clock, of the executions of ``program``
    (of every program, when None) whose enqueue began inside the host
    span [lo, hi]: the device work a host span dispatched, wherever it
    ran."""
    return [(s, e) for s, e, name, r in modules
            if program in (None, name) and lo <= enqueues.get(r, -1) <= hi]


def phase_ns(ops, intervals, scopes: dict, n_top: int = 3) -> dict:
    """Device time by phase of the ops inside ``intervals`` (the
    executions of one program): phase -> {"ns", "top"}, where ``top``
    lists the phase's ``n_top`` operation names (`short_name`) with the
    most time, as [name, seconds]. An op's phase is ``scopes[its
    instruction name]`` (`instruction_scopes`), or `UNSCOPED`. Enclosing
    ops (``while``, ``conditional``) are left out, as in `top_ops`."""
    ivs = sorted(intervals)
    leaves = trace_reduce.leaf_ops(ops)
    by_phase, kernels = {}, {}
    i = 0
    for s, e, name in leaves:
        while i < len(ivs) and ivs[i][1] <= s:
            i += 1
        if i == len(ivs):
            break
        if s < ivs[i][0]:
            continue
        phase = scopes.get(instr_name(name)) or UNSCOPED
        by_phase[phase] = by_phase.get(phase, 0.0) + (e - s)
        k = kernels.setdefault(phase, {})
        short = trace_reduce.short_name(name)
        k[short] = k.get(short, 0.0) + (e - s)
    return {phase: {"ns": ns,
                    "top": [[name, t * 1e-9] for name, t in sorted(
                        kernels[phase].items(), key=lambda kv: -kv[1])
                        [:n_top]]}
            for phase, ns in by_phase.items()}


def _innermost_cover(gs, ge, spans: dict) -> dict:
    """span name -> nanoseconds of [gs, ge] in which it is the narrowest
    span covering the instant."""
    cover = [(s, e, name) for name, ivs in spans.items()
             for s, e in ivs if s < ge and e > gs]
    cuts = sorted({gs, ge} | {t for s, e, _ in cover for t in (s, e)
                              if gs < t < ge})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        inside = [(e - s, name) for s, e, name in cover
                  if s <= a and e >= b]
        if inside:
            name = min(inside)[1]
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def idle_gaps_aligned(ops, spans: dict, lo: float, hi: float,
                      offset: float, n: int = 10):
    """The ``n`` longest stretches of the host window [lo, hi] in which
    no device operation runs, the device's ops moved onto the host clock
    by ``offset`` (`clock_offset_ns`), as [label, seconds]. The label is
    the span that is the narrowest one covering the instant over the
    largest part of the gap, or ``"no host span"``."""
    busy = trace_reduce.merged(
        [(s + offset, e + offset, name) for s, e, name in ops], lo, hi)
    # the complement of the disjoint, sorted busy intervals in [lo, hi]
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    out = []
    for gs, ge in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        cover = _innermost_cover(gs, ge, spans)
        label = max(cover, key=cover.get) if cover else "no host span"
        out.append([label, (ge - gs) * 1e-9])
    return out
