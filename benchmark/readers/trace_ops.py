"""Device time of a class of ops per traced statement, busiest device.
args: `what` (`collective`), `scale` (1000 = ms)."""


def read(run, what="collective", scale=1000.0):
    trace = run.get("trace")
    n = len(run.get("traced_statements") or [])
    if not trace or not n:
        return None
    if what != "collective":
        raise ValueError(f"trace_ops knows no class {what!r}")
    return trace["collective_s"] / n * scale
