"""Idle share of the busiest device over the traced part of the window:
1 - union of its op intervals / window (`trace_reduce.reduce`)."""


def read(run, scale=100.0):
    trace = run.get("trace")
    if not trace:
        return None
    return trace["idle_share"] * scale
