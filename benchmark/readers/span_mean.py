"""Mean per statement of a span's duration, or of the difference of two.

args: `of` and optionally `minus`: a span name of the runner's `query_trace`
tree (`query`, `analyze`, `optimize`, `execute`, ...), or a list of names of
which the first that a statement's tree holds is taken (the local runner
calls its execution span `execute`, the distributed runner `schedule`), or
`client` (the client's wall from the call to the last row); `scale`
(1000 = ms)."""


def _mean_span(run, name):
    if name == "client":
        walls = [
            st.end_s - st.start_s for st in run["statements"] if not st.error
        ]
        return sum(walls) / len(walls) if walls else None
    names = [name] if isinstance(name, str) else list(name)
    totals = []
    for _, spans in run["spans"]:
        for n in names:
            found = [s["duration_ms"] for s in spans if s["name"] == n]
            if found:
                totals.append(sum(found) / 1e3)
                break
    return sum(totals) / len(totals) if totals else None


def read(run, of, minus=None, scale=1000.0):
    a = _mean_span(run, of)
    if a is None:
        return None
    if minus is not None:
        b = _mean_span(run, minus)
        if b is None:
            return None
        a -= b
    return a * scale
