"""refresh.call_ms: device time, from the trace, of one GGC refresh of
every client over its candidates, made as the cell's round makes it
(dense scan or neighbor lists) on the run's graph, after the window."""


def read(run):
    s = run.get("call_s", {}).get("refresh_call")
    return None if not s else s * 1e3
