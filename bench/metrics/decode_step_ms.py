"""Milliseconds per decode step: the engine's own ``decode_s`` over its
``decode_calls`` in the window (dispatch to the host argmax)."""


def read(run):
    e = run.window.engine
    if not e.get("decode_calls"):
        return None
    return 1e3 * e["decode_s"] / e["decode_calls"]
