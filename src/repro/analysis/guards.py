"""Runtime trace-hygiene guards for the round engine (DESIGN.md §13).

Three tools, all cheap enough to leave on in benchmarks and CI:

* :func:`no_transfer` — a context manager that turns implicit
  host-to-device transfers (committing a numpy array or python scalar to
  device mid-loop — the PR 2 bug class), device-to-device copies, and —
  on accelerator backends — explicit device-to-host pulls (``.item()``,
  ``np.asarray``; guarded at ``disallow_explicit``) into errors. On the
  CPU backend device buffers are host-resident, so device-to-host
  conversions are zero-copy and never trip the guard there; the
  host-to-device direction is the live tripwire in CPU CI.
  :func:`allow_transfers` re-opens a hole (e.g. a history flush) inside a
  guarded region.

* :func:`recompile_sentinel` — asserts that a jitted function gains exactly
  the expected number of new compile-cache entries across a region. The
  primary counter is the function's own dispatch cache (``_cache_size``);
  a global ``jax.log_compiles`` watcher is available via ``watch_logs=True``
  for functions that do not expose a cache.

* :func:`donation_report` / :func:`assert_donatable` — a static audit of
  which ``round_step`` buffers can take ``donate_argnums``: a leaf is
  donatable when the output pytree has a leaf at the same path with the
  same shape/dtype. ``fl.round_engine.make_round_step(donate=True)`` wires
  the donation in; ``fl.round_engine.init_round_state`` de-aliases leaves
  so no underlying buffer is donated twice.
"""
from __future__ import annotations

import contextlib
import logging
import re
from typing import Any, Dict, List, Optional

import jax
import numpy as np


class RecompileError(AssertionError):
    """A guarded region compiled more (or fewer) times than expected."""


class TransferError(RuntimeError):
    """Alias for transfer-guard violations (jax raises its own error type;
    this name exists so callers can document intent)."""


@contextlib.contextmanager
def no_transfer():
    """Fail on host<->device transfers inside the region.

    Implicit host-to-device transfers (committing a fresh numpy/python
    value), device-to-device copies, and — on accelerator backends —
    explicit device-to-host conversions all raise (on CPU, d2h is a
    zero-copy view and never guarded). Wrap the unavoidable host touches
    (history flushes, final result pulls) in :func:`allow_transfers`.
    """
    with jax.transfer_guard_host_to_device("disallow"), \
            jax.transfer_guard_device_to_device("disallow"), \
            jax.transfer_guard_device_to_host("disallow_explicit"):
        yield


@contextlib.contextmanager
def allow_transfers():
    """Re-allow transfers inside a :func:`no_transfer` region."""
    with jax.transfer_guard("allow"):
        yield


class _CompileWatcher(logging.Handler):
    """Counts "Finished tracing + compiling ..." / "Compiling ..." records
    emitted under ``jax.log_compiles`` and remembers the function names."""

    _NAME_RE = re.compile(r"Compiling ([\w<>.-]+)")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: List[str] = []

    def emit(self, record):
        m = self._NAME_RE.search(record.getMessage())
        if m:
            self.names.append(m.group(1))

    @property
    def count(self) -> int:
        return len(self.names)


class _SentinelHandle:
    """Yielded by :func:`recompile_sentinel`; exposes the live counters."""

    def __init__(self, fn, watcher: Optional[_CompileWatcher]):
        self.fn = fn
        self.watcher = watcher
        self.start = self._cache_size()

    def _cache_size(self) -> int:
        if self.fn is not None and hasattr(self.fn, "_cache_size"):
            return self.fn._cache_size()
        return 0

    def new_compiles(self) -> int:
        if self.fn is not None:
            return self._cache_size() - self.start
        return self.watcher.count if self.watcher else 0

    def compiled_names(self) -> List[str]:
        return list(self.watcher.names) if self.watcher else []


@contextlib.contextmanager
def recompile_sentinel(fn=None, *, expect_new: int = 1,
                       max_new: Optional[int] = None,
                       watch_logs: bool = False):
    """Assert the number of fresh compilations inside the region.

    With ``fn`` (a ``jax.jit`` product), counts new entries in its dispatch
    cache — one entry per distinct input shape/dtype/sharding signature, so
    a warmed function running K rounds must add exactly 0 and a cold one
    exactly 1. Note ``fn.lower(...).compile()`` (the AOT path) does NOT
    populate this cache. With ``watch_logs=True`` (or ``fn=None``) a
    ``jax.log_compiles`` log watcher counts every XLA compile instead —
    noisier (it sees constant-folding compiles) but function-agnostic;
    asserts ``<= max_new`` when given, else non-strict.

    Raises :class:`RecompileError` on violation.
    """
    watcher = None
    with contextlib.ExitStack() as stack:
        if fn is None or watch_logs:
            watcher = _CompileWatcher()
            logger = logging.getLogger("jax")
            stack.enter_context(jax.log_compiles())
            logger.addHandler(watcher)
            stack.callback(logger.removeHandler, watcher)
        handle = _SentinelHandle(fn, watcher)
        # an exception from the body propagates here and skips the check
        yield handle
    got = handle.new_compiles()
    limit = max_new if max_new is not None else expect_new
    if fn is not None:
        if max_new is not None:
            if got > max_new:
                raise RecompileError(
                    f"recompile_sentinel: {got} new compile(s) of "
                    f"{getattr(fn, '__name__', fn)!r}, expected at most "
                    f"{max_new}")
        elif got != expect_new:
            raise RecompileError(
                f"recompile_sentinel: {got} new compile(s) of "
                f"{getattr(fn, '__name__', fn)!r}, expected exactly "
                f"{expect_new} — a shape/dtype/weak-type or static-arg "
                f"mismatch is re-triggering compilation")
    elif watcher is not None and watcher.count > limit:
        raise RecompileError(
            f"recompile_sentinel(watch_logs): {watcher.count} compile(s) "
            f"observed (limit {limit}): {watcher.names[:8]}")


def _leaf_paths(tree) -> Dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def donation_report(fn, *args) -> Dict[str, Any]:
    """Static audit (via ``jax.eval_shape`` — nothing executes): which
    leaves of ``args[0]`` could be donated to ``fn``.

    A leaf is *donatable* when the output pytree holds a leaf at the same
    path with identical shape and dtype (XLA can then alias the buffers);
    otherwise it is *blocked*. Returns ``{"donatable": [...], "blocked":
    [...], "donatable_bytes": int}``.
    """
    # a jitted program's own eval_shape (an `FLEngine.jit` step passes
    # its client data there; under `jax.eval_shape` it would refuse)
    out = (fn.eval_shape(*args) if hasattr(fn, "eval_shape")
           else jax.eval_shape(fn, *args))
    in_leaves = _leaf_paths(args[0])
    out_leaves = _leaf_paths(out)
    report = {"donatable": [], "blocked": [], "donatable_bytes": 0}
    for path, leaf in in_leaves.items():
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        peer = out_leaves.get(path)
        if peer is not None and getattr(peer, "shape", ()) == shape and \
                getattr(peer, "dtype", None) == dtype:
            report["donatable"].append(path)
            if shape is not None and dtype is not None:
                n = 1
                for d in shape:
                    n *= int(d)
                report["donatable_bytes"] += n * np.dtype(dtype).itemsize
        else:
            report["blocked"].append(path)
    return report


def assert_donatable(fn, *args):
    """Raise if any leaf of ``args[0]`` could not be donated to ``fn`` —
    the safety check behind ``make_round_step(donate=True)``."""
    rep = donation_report(fn, *args)
    if rep["blocked"]:
        raise AssertionError(
            f"buffers not donatable (shape/dtype changes across the call): "
            f"{rep['blocked']}")
    return rep
