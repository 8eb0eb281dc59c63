"""Plain reference of the `tpch` statement suite: q1, q6, q3, q18.

Straight numpy over the benchmark's own copy of the data arithmetic
(`benchmark/datagen/tpch.py`); it imports nothing of the program and takes
nothing the program has made.  Every decimal is carried as an integer, so
each answer is exact and the comparison's limit is 0.

`Suite(schema).answers(statements, fault=None)` answers a list of
`(query, params)` in one pass over lineitem, chunk by chunk, so the scan
columns of SF10 are generated once whatever the number of statements and
never held whole.  `params` are the statement's substitution parameters as
the traffic generator drew them (TPC-H clause 2.4).

`fault` turns the reference into a control, by breaking one guarantee the
configurations state (see `benchmark/control.py`):

- `float32_sums`: decimal aggregates accumulated in float32, the chip's
  native width (its float64 is emulated from float32 pairs), in place of
  exact integers;
- `partial_table`: every other chunk of lineitem is left out, so a statement
  is answered from half of the table;
- `unordered`: the rows of an `ORDER BY` statement come back reversed.
"""

from __future__ import annotations

import datetime
from concurrent.futures import ThreadPoolExecutor
from decimal import Decimal

import numpy as np

from benchmark.datagen import tpch as gen

FAULTS = ("float32_sums", "partial_table", "unordered")
#: lineitem is generated and reduced in this many order ranges: small enough
#: that eight threads hold about 1 GB at SF10 (sixteen ranges held 5 GB here
#: and, called a few times over, met the chip machine's 40 GiB limit: its
#: sandbox does not hand freed arenas back)
CHUNKS = 64


def _dec(value: int, scale: int) -> Decimal:
    return Decimal(int(value)).scaleb(-scale)


def _date(d: int) -> datetime.date:
    return gen.EPOCH + datetime.timedelta(days=int(d))


def _avg_half_up(total: int, n: int, scale: int) -> Decimal:
    """avg(decimal) keeps the argument's scale, rounded half up
    (non-negative totals)."""
    return Decimal((2 * int(total) + n) // (2 * n)).scaleb(-scale)


def _sum(values: np.ndarray, f32: bool) -> int:
    if f32:
        return int(values.astype(np.float32).sum(dtype=np.float32))
    return int(values.sum())


def _group_sums(values: np.ndarray, starts: np.ndarray, f32: bool):
    """Sums of `values` over runs that start at `starts` (sorted groups)."""
    if not len(values):
        return np.zeros(0, dtype=np.int64)
    if f32:
        return np.add.reduceat(values.astype(np.float32), starts).astype(
            np.int64
        )
    return np.add.reduceat(values, starts)


def _top(rows: list, key, limit: int) -> tuple:
    """The first `limit` rows by `key`, and the rows beyond them that tie
    with the last one on the sort key (any of them is a right answer)."""
    rows = sorted(rows, key=key)
    head, rest = rows[:limit], rows[limit:]
    tail = []
    if head and rest:
        last = key(head[-1])
        tail = [r for r in rest if key(r) == last]
    return head, tail


class Q1:
    columns = ("l_returnflag", "l_linestatus", "l_quantity",
               "l_extendedprice", "l_discount", "l_tax", "l_shipdate")
    ordered, key, limit = True, (0, 1), None

    def __init__(self, suite, params, f32):
        self.f32 = f32
        self.cutoff = gen.days(datetime.date(1998, 12, 1)) - int(
            params["delta"]
        )

    def partial(self, c, first_order):
        live = c["l_shipdate"] <= self.cutoff
        key = c["l_returnflag"] * 2 + c["l_linestatus"]
        out = {}
        for k in np.unique(key[live]):
            sel = live & (key == k)
            q, p = c["l_quantity"][sel], c["l_extendedprice"][sel]
            d, t = c["l_discount"][sel], c["l_tax"][sel]
            disc_price = p * (100 - d)
            out[int(k)] = [
                _sum(q, self.f32), _sum(p, self.f32),
                _sum(disc_price, self.f32),
                _sum(disc_price * (100 + t), self.f32),
                _sum(d, self.f32), int(sel.sum()),
            ]
        return out

    def finish(self, partials):
        tot: dict = {}
        for part in partials:
            for k, v in part.items():
                acc = tot.setdefault(k, [0] * 6)
                for i, x in enumerate(v):
                    acc[i] += x
        rows = []
        for k in sorted(tot):
            q, p, dp, ch, d, n = tot[k]
            rows.append((
                gen.RETURNFLAGS[k // 2], gen.LINESTATUS[k % 2],
                _dec(q, 2), _dec(p, 2), _dec(dp, 4), _dec(ch, 6),
                _avg_half_up(q, n, 2), _avg_half_up(p, n, 2),
                _avg_half_up(d, n, 2), n,
            ))
        return rows, []


class Q6:
    columns = ("l_extendedprice", "l_discount", "l_quantity", "l_shipdate")
    ordered, key, limit = False, (), None

    def __init__(self, suite, params, f32):
        self.f32 = f32
        start = datetime.date.fromisoformat(params["date"])
        self.lo = gen.days(start)
        self.hi = gen.days(start.replace(year=start.year + 1))
        disc = int(Decimal(str(params["discount"])) * 100)
        self.dlo, self.dhi = disc - 1, disc + 1
        self.qty = int(params["quantity"]) * 100

    def partial(self, c, first_order):
        m = (
            (c["l_shipdate"] >= self.lo) & (c["l_shipdate"] < self.hi)
            & (c["l_discount"] >= self.dlo) & (c["l_discount"] <= self.dhi)
            & (c["l_quantity"] < self.qty)
        )
        return (
            _sum(c["l_extendedprice"][m] * c["l_discount"][m], self.f32),
            int(m.sum()),
        )

    def finish(self, partials):
        n = sum(p[1] for p in partials)
        if not n:
            return [(None,)], []
        return [(_dec(sum(p[0] for p in partials), 4),)], []


class Q3:
    columns = ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate")
    ordered, key, limit = True, (1, 2), 10

    def __init__(self, suite, params, f32):
        self.f32 = f32
        t = suite.data
        self.date = gen.days(datetime.date.fromisoformat(params["date"]))
        seg = gen.SEGMENTS.index(params["segment"])
        oidx = np.arange(t.O, dtype=np.int64)
        self.odate = t.order_dates(oidx)
        in_seg = t.customer_segments() == seg
        self.order_ok = (self.odate < self.date) & in_seg[
            t.order_custkeys(oidx) - 1
        ]

    @staticmethod
    def sort_key(r):
        return (-r[1], r[2])

    def partial(self, c, first_order):
        oidx = c["l_orderkey"] - 1
        m = (c["l_shipdate"] > self.date) & self.order_ok[oidx]
        oidx = oidx[m]
        if not len(oidx):
            return []
        revenue = c["l_extendedprice"][m] * (100 - c["l_discount"][m])
        starts = np.flatnonzero(np.r_[True, oidx[1:] != oidx[:-1]])
        sums = _group_sums(revenue, starts, self.f32)
        rows = [
            (int(o) + 1, int(s), int(self.odate[o]), 0)
            for o, s in zip(oidx[starts], sums)
        ]
        head, tail = _top(rows, self.sort_key, self.limit)
        return head + tail

    def finish(self, partials):
        rows = [r for part in partials for r in part]
        head, tail = _top(rows, self.sort_key, self.limit)
        fmt = lambda r: (r[0], _dec(r[1], 4), _date(r[2]), r[3])
        return [fmt(r) for r in head], [fmt(r) for r in tail]


class Q18:
    columns = ("l_orderkey", "l_quantity")
    ordered, key, limit = True, (4, 3), 100

    def __init__(self, suite, params, f32):
        self.f32 = f32
        self.data = suite.data
        self.qty = int(params["quantity"]) * 100

    def partial(self, c, first_order):
        oidx = c["l_orderkey"] - 1
        starts = np.flatnonzero(np.r_[True, oidx[1:] != oidx[:-1]])
        sums = _group_sums(c["l_quantity"], starts, self.f32)
        big = sums > self.qty
        return [(int(o), int(s)) for o, s in zip(oidx[starts][big], sums[big])]

    def finish(self, partials):
        found = [r for part in partials for r in part]
        if not found:
            return [], []
        oidx = np.array([o for o, _ in found], dtype=np.int64)
        t = self.data
        cust = t.order_custkeys(oidx)
        date = t.order_dates(oidx)
        total = t.order_totalprice(oidx)
        rows = [
            (t.customer_name(int(c)), int(c), int(o) + 1, int(d), int(tp), s)
            for (o, s), c, d, tp in zip(found, cust, date, total)
        ]
        head, tail = _top(rows, lambda r: (-r[4], r[3]), self.limit)
        fmt = lambda r: (r[0], r[1], r[2], _date(r[3]), _dec(r[4], 2),
                         _dec(r[5], 2))
        return [fmt(r) for r in head], [fmt(r) for r in tail]


QUERIES = {"q1": Q1, "q6": Q6, "q3": Q3, "q18": Q18}


class Suite:
    def __init__(self, schema: str, threads: int = 8):
        self.data = gen.Tpch(schema)
        self.threads = threads

    def answers(self, statements, fault=None) -> list:
        """One answer per `(query, params)`: a dict with `rows`, `ordered`,
        `key` (the ORDER BY columns), `limit`, and `tail` (rows that tie
        with the last row on the sort key and could stand in its place)."""
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        f32 = fault == "float32_sums"
        work = [QUERIES[q](self, params, f32) for q, params in statements]
        columns = sorted({c for w in work for c in w.columns})
        t = self.data
        chunks = max(1, min(CHUNKS, t.O // 1024 or 1))
        per = -(-t.O // chunks)
        ranges = [(a, min(per, t.O - a)) for a in range(0, t.O, per)]
        if fault == "partial_table":
            ranges = ranges[::2]

        def one(r):
            c = t.lineitem(columns, order_start=r[0], order_count=r[1])
            return [w.partial(c, r[0]) for w in work]

        with ThreadPoolExecutor(max_workers=self.threads) as pool:
            parts = list(pool.map(one, ranges))
        out = []
        for i, w in enumerate(work):
            rows, tail = w.finish([p[i] for p in parts])
            if fault == "unordered" and w.ordered:
                rows = rows[::-1]
            out.append({
                "rows": rows, "tail": tail, "ordered": w.ordered,
                "key": list(w.key), "limit": w.limit,
            })
        return out
