"""train.call_ms: device time, from the trace, of one
``engine.local_train(..., epochs=tau_train)`` call at the cell's shapes
(every client's local epochs of one round), made after the window."""


def read(run):
    s = run.get("call_s", {}).get("train_call")
    return None if not s else s * 1e3
