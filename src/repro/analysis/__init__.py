"""Static + runtime analysis tooling for the compiled round engine
(DESIGN.md §13 trace hygiene, §14 federated semantics).

Layers:

* :mod:`repro.analysis.tracelint` — static AST linter for the JAX/Pallas
  pitfalls this codebase has actually hit (rules T1–T6).
* :mod:`repro.analysis.fedlint` — static AST linter for the federated
  semantics the DPFL claims rest on: client isolation, comm accounting,
  codec integrity, participation, mesh axes, dense/sparse boundary
  (rules F1–F6). Shared CLI: ``python -m repro.analysis.lint``.
* :mod:`repro.analysis.registry` — the ``@exchange_site`` decorator
  declaring the legitimate cross-client communication surface that
  fedlint rule F1 checks against.
* :mod:`repro.analysis.guards` — runtime guards: ``no_transfer()``
  regions, ``recompile_sentinel()`` compile-count assertions, and the
  ``donation_report()`` buffer-donation audit.
* :mod:`repro.analysis.commaudit` — compiled-artifact audit: lowers the
  jitted round_step, attributes collective wire bytes from the
  post-SPMD HLO, and reconciles them against the claimed
  ``DPFLResult.comm_bytes``.
* :mod:`repro.analysis.counters` — trace-time counters of a compiled
  program's work (the GGC reward probes), collected per program by
  ``FLEngine.jit`` into ``program.counts``.

The linter layers and the registry are dependency-free (stdlib only) so
the CLI runs without importing jax; ``guards`` and ``commaudit`` import
jax and are therefore loaded lazily via module ``__getattr__``.
"""

_GUARD_EXPORTS = (
    "no_transfer", "allow_transfers", "recompile_sentinel",
    "RecompileError", "TransferError", "donation_report",
)
_REGISTRY_EXPORTS = ("exchange_site", "is_exchange_site", "EXCHANGE_SITES",
                     "ExchangeSite")

__all__ = (["tracelint", "fedlint", "registry", "commaudit", "counters"]
           + list(_GUARD_EXPORTS) + list(_REGISTRY_EXPORTS))


def __getattr__(name):
    import importlib
    if name in ("guards", "tracelint", "fedlint", "registry", "commaudit",
                "counters"):
        return importlib.import_module(f".{name}", __name__)
    if name in _GUARD_EXPORTS:
        mod = importlib.import_module(".guards", __name__)
        return getattr(mod, name)
    if name in _REGISTRY_EXPORTS:
        mod = importlib.import_module(".registry", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
