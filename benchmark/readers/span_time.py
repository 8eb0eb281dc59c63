"""Mean per statement of the summed durations of the spans of one name
that carry given attributes.

args: `of`: a span name of the runner's `query_trace` tree (`feed_wait`);
`where`: attributes a span must carry with these values to count
(`{"by": "statement"}`); `scale` (1000 = ms).  As in `span_count`, a
statement with no such span counts 0 and only a run with no span tree at
all (the engine's tracing was off) reads nothing; `span_mean` instead
averages over the statements that hold the span."""

import json


def read(run, of, where=None, scale=1000.0):
    trees = [spans for _, spans in run["spans"] if spans]
    if not trees:
        return None
    total = 0.0
    for spans in trees:
        for s in spans:
            if s["name"] != of:
                continue
            attrs = json.loads(s["attributes"]) if s["attributes"] else {}
            if all(attrs.get(k) == v for k, v in (where or {}).items()):
                total += s["duration_ms"] / 1e3
    return total / len(trees) * scale
