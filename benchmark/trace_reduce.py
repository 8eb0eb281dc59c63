"""From a profiler trace (`.xplane.pb`) to numbers: device busy time and idle
share, time per device op, collective time, and the idle gaps named by what
the host was doing.  Read with nothing but `jax.profiler.ProfileData`.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
op with a start and a duration in nanoseconds, and whose line `XLA Modules`
has one event per executed program; host planes (`/host:CPU`) hold one line
per thread, and `jax.profiler.TraceAnnotation` events appear there under
their own names, on the same clock.

`reduce()` keeps to one rule per number:

- busy   = the union of the op intervals of a device inside the window;
- window = the interval of the benchmark's own annotation
  (`bench_traced_window`) when it is there, else first to last device event;
- idle share = 1 - busy / window, of the busiest device;
- collective seconds = the union of the intervals of the ops whose HLO
  opcode is a collective's (`all-to-all`, `all-gather`, `all-reduce`, ...;
  jax names the op `%all_to_all.53`, the opcode says what it is), an
  asynchronous one (line `Async XLA Ops`) counted from its start to its done;
- op seconds = each op's self time (its duration less the ops it holds: a
  `while` and its body share the `XLA Ops` line), summed by
  `<program>/<op> <shape>`, so the ops' seconds add up to the busy time;
- a gap = an interval between two busy intervals inside the window, named
  by the statement annotation it falls in and the op that ran before it.

Self-check: `python3 benchmark/trace_reduce.py --self-check` reduces the
small recorded trace beside this file and compares with fixed numbers.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
#: spans of asynchronous ops (start..done): copies, slices, collectives
ASYNC_LINES = ("Async XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
COLLECTIVE = re.compile(
    r"^(ragged-)?(all-to-all|all-gather|all-reduce|reduce-scatter"
    r"|collective-permute|collective-broadcast)(-start|-done)?(\.\d+)?$"
)
#: the benchmark's own annotations (`harness/window.py` writes them)
WINDOW_ANNOTATION = "bench_traced_window"
STATEMENT_PREFIX = "stmt:"
STATEMENT = re.compile("^" + re.escape(STATEMENT_PREFIX))


def _module_name(name: str) -> str:
    """`jit_step(11992750590591994673)` -> `jit_step(119927)`: the program's
    fingerprint, shortened, tells two programs of one name apart."""
    return re.sub(r"\((\d{6})\d*\)$", r"(\1)", name)


def parse_op(hlo: str) -> tuple:
    """`%all_to_all.53 = u32[4,1,16384]{2,1,0:T(1,128)S(1)} all-to-all(...)`
    -> (`all_to_all.53`, `u32[4,1,16384]`, `all-to-all`): the op's name, the
    shape it produces without layouts, and its HLO opcode."""
    head, _, rest = hlo.partition(" = ")
    shape, opcode = "", ""
    depth = 0
    for i, ch in enumerate(rest):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            shape = re.sub(r"\{[^}]*\}", "", rest[:i]).replace(" ", "")
            opcode = rest[i + 1:].partition("(")[0]
            break
    return head.lstrip("%"), shape, opcode


def _op_name(hlo: str) -> str:
    head, shape, _ = parse_op(hlo)
    return (head + (" " + shape if shape else ""))[:96]


def is_collective(hlo: str) -> bool:
    """By the op's own opcode (or, where the trace gives only a name, its
    name) -- never by its operands, which may name a collective's result."""
    head, _, opcode = parse_op(hlo)
    return bool(COLLECTIVE.search(opcode or head))


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(
        os.path.join(directory, "**", "*.xplane.pb"), recursive=True
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _union(intervals: list) -> list:
    """Merged, sorted [start, end) intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def read(path: str) -> dict:
    """Planes -> plain lists: {plane: {line: [(name, start_ns, dur_ns)]}} for
    device planes, and every host event whose name the benchmark wrote."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    annotations: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in OP_LINES + MODULE_LINES + ASYNC_LINES:
                    lines[line.name] = [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_ANNOTATION or STATEMENT.match(
                        ev.name
                    ):
                        annotations.append(
                            (ev.name, float(ev.start_ns),
                             float(ev.duration_ns))
                        )
    return {"devices": devices, "annotations": annotations}


def reduce(path: str, top: int = 10) -> dict | None:
    """None when the trace holds no device op (a CPU rehearsal)."""
    raw = read(path)
    devices = {
        name: lines for name, lines in raw["devices"].items()
        if any(lines.get(n) for n in OP_LINES)
    }
    if not devices:
        return None
    window = [
        (s, s + d) for n, s, d in raw["annotations"]
        if n == WINDOW_ANNOTATION
    ]
    if window:
        lo, hi = window[0]
    else:
        every = [
            (s, s + d) for lines in devices.values()
            for n in OP_LINES for _, s, d in lines.get(n, [])
        ]
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    statements = sorted(
        (s, s + d, n) for n, s, d in raw["annotations"] if STATEMENT.match(n)
    )
    per_device = {}
    for name, lines in devices.items():
        modules = sorted(
            (s, s + d, _module_name(n))
            for line in MODULE_LINES for n, s, d in lines.get(line, [])
        )
        starts = [m[0] for m in modules]
        events = []
        for line in OP_LINES:
            for op, s, d in lines.get(line, []):
                c = _clip(s, s + d, lo, hi)
                if c is None:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                inside = (
                    modules[i][2] if i >= 0 and s < modules[i][1] else "?"
                )
                events.append((c[0], c[1], f"{inside}/{_op_name(op)}", op))
        # an op that holds others (a while loop and its body) is charged its
        # self time only, so the ops' seconds add up to the busy time
        events.sort(key=lambda e: (e[0], -e[1]))
        ops: dict = {}
        stack: list = []  # [end, label, self_ns]

        def close(entry):
            ops[entry[1]] = ops.get(entry[1], 0.0) + entry[2]

        # an asynchronous collective lasts from its start to its done
        collectives = []
        for a, b, label, op in events:
            while stack and stack[-1][0] <= a:
                close(stack.pop())
            if stack:
                stack[-1][2] -= min(b, stack[-1][0]) - a
            stack.append([b, label, b - a])
            if is_collective(op):
                collectives.append((a, b))
        while stack:
            close(stack.pop())
        for line in ASYNC_LINES:
            for op, s, d in lines.get(line, []):
                c = _clip(s, s + d, lo, hi)
                if c and is_collective(op):
                    collectives.append(c)
        spans = [(a, b, label) for a, b, label, _ in events]
        busy = _union([(a, b) for a, b, _ in spans])
        per_device[name] = {
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "collective_s": sum(b - a for a, b in _union(collectives)) / 1e9,
            "ops": ops, "spans": spans, "busy": busy, "events": len(spans),
            "programs": sum(1 for m in modules if _clip(m[0], m[1], lo, hi)),
        }
    busiest = max(per_device, key=lambda n: per_device[n]["busy_s"])
    dev = per_device[busiest]
    window_s = (hi - lo) / 1e9
    # idle gaps of the busiest device, named by statement and preceding op
    ends = sorted(dev["spans"], key=lambda e: e[1])
    gaps: dict = {}
    longest = []
    edges = [[lo, lo]] + dev["busy"] + [[hi, hi]]
    j = 0
    prev_op = "window start"
    for (_, a), (b, _) in zip(edges[:-1], edges[1:]):
        if b <= a:
            continue
        while j < len(ends) and ends[j][1] <= a:
            prev_op = ends[j][2]
            j += 1
        mid = (a + b) / 2
        inside = [n for s, e, n in statements if s <= mid < e]
        where = (
            STATEMENT.sub("", inside[0]).split("#")[0] if inside
            else "between statements"
        )
        label = f"{where}: host after {prev_op}"
        gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9
        longest.append((b - a) / 1e9)
    top_of = lambda d: [
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {
        "window_s": window_s,
        "busiest_device": busiest,
        "busy_s": dev["busy_s"],
        "busy_s_mean": sum(d["busy_s"] for d in per_device.values())
        / len(per_device),
        "idle_share": 1.0 - dev["busy_s"] / window_s,
        "collective_s": dev["collective_s"],
        "per_device": {
            n: {k: d[k] for k in ("busy_s", "collective_s", "events",
                                  "programs")}
            for n, d in per_device.items()
        },
        "device_ops": top_of({k: v / 1e9 for k, v in dev["ops"].items()}),
        "idle_gaps": top_of(gaps),
        "gap_count": len(longest),
        "longest_gap_s": max(longest, default=0.0),
        "statements_traced": len(statements),
    }


def describe(path: str, events: int = 5) -> dict:
    """The shape of a trace, for looking at one by hand."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "first": [
                    [e.name[:80], e.start_ns, e.duration_ns]
                    for e in evs[:events]
                ],
            }
        out[plane.name] = lines
    return out


SELF_CHECK_TRACE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "testdata", "small.xplane.pb"
)
SELF_CHECK_EXPECT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "testdata",
    "small.expected.json",
)


def self_check() -> list:
    """Reduce the recorded trace; return what differs from the fixed numbers
    (empty = the reduction still computes what it did when recorded)."""
    got = reduce(SELF_CHECK_TRACE)
    with open(SELF_CHECK_EXPECT) as f:
        want = json.load(f)
    bad = []
    for key, value in want.items():
        have = got.get(key)
        if isinstance(value, float):
            if abs(have - value) > 1e-9 * max(1.0, abs(value)):
                bad.append(f"{key}: {have!r} != {value!r}")
        elif isinstance(value, list):  # the first entries of a top-10 list
            if json.loads(json.dumps(have))[:len(value)] != value:
                bad.append(f"{key}: {have[:len(value)]!r} != {value!r}")
        elif have != value:
            bad.append(f"{key}: {have!r} != {value!r}")
    return bad


if __name__ == "__main__":
    if "--self-check" in sys.argv:
        problems = self_check()
        print("\n".join(problems) or "trace_reduce self-check: ok")
        sys.exit(1 if problems else 0)
    if len(sys.argv) == 3 and sys.argv[1] == "--describe":
        print(json.dumps(describe(sys.argv[2]), indent=1))
        sys.exit(0)
    print(json.dumps(reduce(sys.argv[1]), indent=1, default=str))
