"""The one general traffic generator.  A mix is a data file
(`benchmark/traffic/<mix>.json`); this module reads its parameters and makes
the statements from `--seed`.  The program receives only SQL text.

Keys of a mix:

- `suite`: the reference suite (`benchmark/reference/<suite>.py`) and the
  directory of the statement templates.
- `queries`: a list of `{name, template, params}`.  `template` is a path
  under `benchmark/` to SQL text with `{param}` placeholders; `params` maps
  each placeholder to how the seed draws it (`kind`: `int` lo..hi, `choice`
  of `values`, `decimal` lo..hi by `step`, `year_start` (Jan 1 of lo..hi),
  `date` (a day of lo..hi, ISO dates)).
- `loop`: `closed` (each stream sends its next statement when the last one
  has answered) or `open` (statements are due every 1/`open_rate` seconds
  whatever the system does; latency counts from the due time).
- `streams`: clients of a closed loop, each with its own seeded order.
- `open_rate`: statements per second of an open loop (a number fixed in the
  file, found once by a sweep; never searched for by the harness).
- `fresh_literals`: false = one draw per query per seed, replayed all
  window; true = every statement of the window is a new draw.
- `clear_pool`: true = the scan buffer pool is emptied before each
  statement, so every scan is cold.
- `trace_seconds`: the least length of the traced part of a `--trace 1`
  window (it always ends on a pass boundary).

Every seed gives the same set of queries per pass, in another order, so the
seed changes the literals and the order and never the amount of work.
"""

from __future__ import annotations

import datetime
import random
from dataclasses import dataclass, field
from decimal import Decimal

from benchmark.harness import spec


@dataclass
class Statement:
    query: str
    params: dict
    sql: str
    stream: int = 0
    seq: int = 0
    #: filled by the window
    due_s: float = 0.0
    start_s: float = 0.0
    end_s: float = 0.0
    rows: list | None = None
    error: str = ""
    extra: dict = field(default_factory=dict)

    @property
    def key(self) -> tuple:
        return (self.query, tuple(sorted(self.params.items())))

    @property
    def wall_s(self) -> float:
        """Client wall; in an open loop it counts from when it was due."""
        return self.end_s - (self.due_s or self.start_s)


def draw(how: dict, rng: random.Random):
    kind = how["kind"]
    if kind == "int":
        return rng.randint(int(how["lo"]), int(how["hi"]))
    if kind == "choice":
        return rng.choice(list(how["values"]))
    if kind == "decimal":
        lo, hi = Decimal(how["lo"]), Decimal(how["hi"])
        step = Decimal(how.get("step", "0.01"))
        return str(lo + step * rng.randint(0, int((hi - lo) / step)))
    if kind == "year_start":
        return f"{rng.randint(int(how['lo']), int(how['hi']))}-01-01"
    if kind == "date":
        lo = datetime.date.fromisoformat(how["lo"])
        hi = datetime.date.fromisoformat(how["hi"])
        return (lo + datetime.timedelta(
            days=rng.randint(0, (hi - lo).days))).isoformat()
    raise ValueError(f"unknown parameter kind {kind!r}")


class Mix:
    """A traffic file plus a seed: the statements, in order, per stream."""

    def __init__(self, traffic: dict, seed: int):
        self.traffic = traffic
        self.seed = int(seed)
        self.loop = traffic.get("loop", "closed")
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {self.loop!r}")
        self.streams = int(traffic.get("streams", 1))
        self.open_rate = traffic.get("open_rate")
        if self.loop == "open" and not self.open_rate:
            raise ValueError("an open loop needs open_rate")
        self.fresh_literals = bool(traffic.get("fresh_literals", False))
        self.clear_pool = bool(traffic.get("clear_pool", False))
        self.trace_seconds = float(traffic.get("trace_seconds", 10))
        self.queries = {q["name"]: q for q in traffic["queries"]}
        self.templates = {}
        for name, q in self.queries.items():
            with open(spec.path(q["template"])) as f:
                self.templates[name] = f.read().strip()
        rng = random.Random(self.seed)
        #: the seed's one draw per query (what set-up warms up)
        self.base = {
            name: self._draw(name, rng) for name in sorted(self.queries)
        }
        self._rngs = [
            random.Random(self.seed * 1_000_003 + 7919 * (s + 1))
            for s in range(max(1, self.streams))
        ]
        #: each stream's order of the pass: a seeded permutation
        self.orders = []
        for r in self._rngs:
            order = sorted(self.queries)
            r.shuffle(order)
            self.orders.append(order)
        self._seq = [0] * len(self._rngs)

    def _draw(self, name: str, rng: random.Random) -> dict:
        hows = self.queries[name].get("params", {})
        return {p: draw(hows[p], rng) for p in sorted(hows)}

    def statement(self, name: str, params: dict, stream: int = 0,
                  seq: int = 0) -> Statement:
        return Statement(
            name, params, self.templates[name].format_map(params),
            stream, seq,
        )

    def warmup(self) -> list:
        """What set-up runs once: each query with the seed's draw."""
        return [
            self.statement(name, self.base[name]) for name in sorted(self.queries)
        ]

    def next_pass(self, stream: int = 0) -> list:
        """The next pass of one stream: every query of the mix once."""
        out = []
        for name in self.orders[stream]:
            params = (
                self._draw(name, self._rngs[stream])
                if self.fresh_literals else self.base[name]
            )
            out.append(self.statement(name, params, stream, self._seq[stream]))
            self._seq[stream] += 1
        return out
