"""round_compiles: new entries in the dispatch cache of the compiled
round_step over the window (`repro.analysis.guards.recompile_sentinel`);
every shape is warmed before the window, so a sound run reads 0."""


def read(run):
    return run.get("window_compiles")
