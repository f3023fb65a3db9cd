"""Trace-time counters of the work a compiled program executes.

Traceable code calls :func:`count` while JAX traces it, to record work
that the compiled program then performs on every call: the GGC builders
(`repro.core.graph`) count the reward probes their scans run under
``"ggc.probes"``. A program built by `repro.fl.engine.FLEngine.jit`
collects what its own trace counted into ``program.counts``; outside
:func:`collecting`, :func:`count` does nothing. Counting reads only
static shapes, so it changes no traced arithmetic.
"""
from __future__ import annotations

import contextlib
import contextvars

# the dicts of every open `collecting` region, outermost first
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "repro_trace_counts", default=())


@contextlib.contextmanager
def collecting(counts: dict):
    """Add every :func:`count` made inside the region to ``counts``
    (and to the dicts of the regions around it)."""
    token = _OPEN.set(_OPEN.get() + (counts,))
    try:
        yield counts
    finally:
        _OPEN.reset(token)


def count(name: str, n: int) -> None:
    """Record ``n`` units of work ``name`` in every open region."""
    for counts in _OPEN.get():
        counts[name] = counts.get(name, 0) + int(n)
