"""Mean per statement of how many spans a statement's tree holds, or of the
sum of one numeric attribute over them.

args: `of`: a span name of the runner's `query_trace` tree (`launch`,
`host_pull`, ...); `where`: attributes a span must carry with these values
to count (`{"why": "result"}`); `attr`: sum this numeric attribute of the
matching spans instead of counting them (`bytes`); `scale`.  A flat span
holds its attributes as a JSON string (`""` when it has none).  A
statement with no such span counts 0; only a run with no span tree at all
(the engine's tracing was off) reads nothing."""

import json


def _matching(spans, of, where):
    for s in spans:
        if s["name"] != of:
            continue
        attrs = json.loads(s["attributes"]) if s["attributes"] else {}
        if all(attrs.get(k) == v for k, v in (where or {}).items()):
            yield attrs


def read(run, of, where=None, attr=None, scale=1.0):
    trees = [spans for _, spans in run["spans"] if spans]
    if not trees:
        return None
    total = 0.0
    for spans in trees:
        for attrs in _matching(spans, of, where):
            total += float(attrs.get(attr, 0)) if attr else 1.0
    return total / len(trees) * scale
