"""Plain reference of the `tpcds` statement suite: q3, q7, q27, q89 (the
store-channel star joins of TPC-DS v3.2.0, App. B).

Straight numpy over the benchmark's own copy of the data arithmetic
(`benchmark/datagen/tpcds.py`); it imports nothing of the program and takes
nothing the program has made.  A join is an index look-up into a dimension
whose surrogate key is dense, a fact row whose key is NULL joins nothing, a
group is a run of a sort, a decimal is an integer of cents, `avg(decimal)`
is the integer quotient rounded half up, `avg(integer)` is Python's
`int / int` (the double nearest to the exact quotient), the window of q89
is a grouped mean joined back.  Every answer is exact and the comparison's
limit is 0.

`Suite(schema).answers(statements, fault=None)` answers a list of
`(query, params)`; store_sales (2.88 M rows at SF1) is made once for all of
them.  `params` are the statement's substitution parameters as the traffic
generator drew them.

`fault` turns the reference into a control, by breaking one guarantee the
configuration states (see `benchmark/control.py`):

- `float32_sums`: money carried as float32 in its logical unit (dollars),
  the chip's native width, sums and averages formed in float32 and brought
  back to the column's scale at the end, and `avg(integer)` divided in
  float32, in place of exact integers.  (Groups here hold one to three
  rows, so accumulation alone would lose nothing: what float32 loses is
  the cent itself -- 48.055 is no float32 -- and the last bits of a
  quotient);
- `partial_table`: every other range of store_sales is left out;
- `unordered`: the rows of an `ORDER BY` statement come back reversed;
- `null_keys_joined`: a NULL foreign key is read as the value under its
  mask, so the row joins a dimension row and lands in a group.
"""

from __future__ import annotations

from decimal import Decimal

import numpy as np

from benchmark.datagen import tpcds as gen

FAULTS = ("float32_sums", "partial_table", "unordered", "null_keys_joined")
#: `partial_table` leaves out every other of this many row ranges
RANGES = 16


def _dec(value: int, scale: int = 2) -> Decimal:
    return Decimal(int(value)).scaleb(-scale)


def _half_up(num: int, den: int) -> int:
    """num / den rounded half up (both non-negative, den > 0)."""
    return (2 * int(num) + int(den)) // (2 * int(den))


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


def _groups(keys: list, live: np.ndarray):
    """Group the live rows by the tuple of integer `keys`: returns the
    order that sorts them, the start of each run, and each key's value per
    group."""
    rows = np.flatnonzero(live)
    cols = [k[rows] for k in keys]
    order = np.lexsort(cols[::-1]) if cols else np.arange(len(rows))
    cols = [c[order] for c in cols]
    if not len(rows):
        return rows, np.zeros(0, np.int64), [c[:0] for c in cols]
    new = np.zeros(len(rows), bool)
    new[0] = True
    for c in cols:
        new[1:] |= c[1:] != c[:-1]
    starts = np.flatnonzero(new)
    return rows[order], starts, [c[starts] for c in cols]


def _sums(values: np.ndarray, starts: np.ndarray, f32: bool) -> np.ndarray:
    """Sum of `values` over each run: exact integers, or under
    `float32_sums` float32 in the logical unit (hundredths as dollars)."""
    if f32:
        values = values.astype(np.float32) / np.float32(100)
    if not len(starts):
        return values[:0]
    return np.add.reduceat(values, starts)


def _cents(x) -> int:
    """A sum as an integer of cents (float32 dollars: rounded half up)."""
    if isinstance(x, np.float32):
        return int(np.floor(x * np.float32(100) + np.float32(0.5)))
    return int(x)


def _avg_cents(total, n: int) -> int:
    """avg(decimal) in cents: the exact quotient rounded half up, or the
    float32 quotient brought back to cents."""
    if isinstance(total, np.float32):
        return _cents(total / np.float32(n))
    return _half_up(int(total), n)


def _counts(starts: np.ndarray, n: int) -> list:
    return [int(x) for x in np.diff(np.r_[starts, n])]


class _Star:
    """What the four statements share: a fact foreign key as a row index
    into its dimension, and whether the row joins (a NULL key does not;
    under `null_keys_joined` every row does)."""

    def __init__(self, suite, fact, fault):
        self.suite = suite
        self.fact = fact
        self.f32 = fault == "float32_sums"
        self.nulls_join = fault == "null_keys_joined"

    def fk(self, col: str, base: int = 1) -> tuple:
        """(row index into the dimension, joins) for a fact foreign key."""
        valid = self.fact[col + ".valid"]
        if self.nulls_join:
            valid = np.ones(len(valid), bool)
        return self.fact[col] - base, valid


class Q3(_Star):
    columns = ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price")
    ordered, key, limit = True, (0, 3, 1), 100

    def answer(self, params):
        s = self.suite
        d, d_ok = self.fk("ss_sold_date_sk", gen.JULIAN_1900)
        i, i_ok = self.fk("ss_item_sk")
        live = (
            d_ok & i_ok
            & (s.date["d_moy"][d] == int(params["month"]))
            & (s.item["i_manufact_id"][i] == int(params["manufact"]))
        )
        rows, starts, (year, brand_id) = _groups(
            [s.date["d_year"][d], s.item["i_brand_id"][i]], live
        )
        sums = _sums(self.fact["ss_ext_sales_price"][rows], starts, self.f32)
        out = [
            (int(y), int(b), gen.Tpcds.brand(int(b) % gen.BRANDS),
             _dec(_cents(t)))
            for y, b, t in zip(year, brand_id, sums)
        ]
        return _top(out, lambda r: (r[0], -r[3], r[1]), self.limit)


class _Averages(_Star):
    """q7 and q27: avg(ss_quantity) and three avg(decimal(7,2))."""

    measures = ("ss_quantity", "ss_list_price", "ss_coupon_amt",
                "ss_sales_price")

    def demographics(self, params) -> tuple:
        s = self.suite
        c, c_ok = self.fk("ss_cdemo_sk")
        cd = s.demographics
        return c_ok & (
            (cd["cd_gender"][c] == gen.GENDER.index(params["gender"]))
            & (cd["cd_marital_status"][c]
               == gen.MARITAL.index(params["marital"]))
            & (cd["cd_education_status"][c]
               == gen.EDUCATION.index(params["education"]))
        )

    def averages(self, rows, starts) -> list:
        """Per group: (avg quantity as a double, three decimal averages)."""
        n = _counts(starts, len(rows))
        q = np.add.reduceat(self.fact["ss_quantity"][rows], starts)
        lp, ca, sp = (
            _sums(self.fact[m][rows], starts, self.f32)
            for m in self.measures[1:]
        )
        if self.f32:
            quantity = [
                float(np.float32(q[g]) / np.float32(n[g]))
                for g in range(len(n))
            ]
        else:  # int / int: the double nearest the exact quotient
            quantity = [int(q[g]) / n[g] for g in range(len(n))]
        return [
            (quantity[g], _dec(_avg_cents(lp[g], n[g])),
             _dec(_avg_cents(ca[g], n[g])), _dec(_avg_cents(sp[g], n[g])))
            for g in range(len(n))
        ]


class Q7(_Averages):
    columns = ("ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_promo_sk",
               ) + _Averages.measures
    ordered, key, limit = True, (0,), 100

    def answer(self, params):
        s = self.suite
        d, d_ok = self.fk("ss_sold_date_sk", gen.JULIAN_1900)
        i, i_ok = self.fk("ss_item_sk")
        p, p_ok = self.fk("ss_promo_sk")
        promo = s.promotion
        live = (
            self.demographics(params) & d_ok & i_ok & p_ok
            & (s.date["d_year"][d] == int(params["year"]))
            & ~(promo["p_channel_email"][p] & promo["p_channel_event"][p])
        )
        rows, starts, (item,) = _groups([i], live)
        out = [
            (gen.Tpcds.item_id(it),) + avgs
            for it, avgs in zip(item, self.averages(rows, starts))
        ]
        return _top(out, lambda r: r[0], self.limit)


class Q27(_Averages):
    columns = ("ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk", "ss_store_sk",
               ) + _Averages.measures
    ordered, key, limit = True, (0, 1), 100

    def answer(self, params):
        s = self.suite
        d, d_ok = self.fk("ss_sold_date_sk", gen.JULIAN_1900)
        i, i_ok = self.fk("ss_item_sk")
        st, st_ok = self.fk("ss_store_sk")
        state = s.store["s_state"][st]
        live = (
            self.demographics(params) & d_ok & i_ok & st_ok
            & (s.date["d_year"][d] == int(params["year"]))
            & (state == gen.STORE_STATES.index(params["state"]))
        )
        out = []
        # ROLLUP(i_item_id, s_state): every level, its absent keys NULL
        rows, starts, (item, stt) = _groups([i, state], live)
        for it, sc, avgs in zip(item, stt, self.averages(rows, starts)):
            out.append(
                (gen.Tpcds.item_id(it), gen.STORE_STATES[sc], 0) + avgs
            )
        rows, starts, (item,) = _groups([i], live)
        for it, avgs in zip(item, self.averages(rows, starts)):
            out.append((gen.Tpcds.item_id(it), None, 1) + avgs)
        rows, starts, _ = _groups([], live)
        if len(rows):
            out.append((None, None, 1) + self.averages(rows, starts[:1])[0])
        else:  # the grand total of no rows is still a row
            out.append((None, None, 1, None, None, None, None))
        # ascending, NULL last
        nl = lambda v: (v is None, v or "")
        return _top(out, lambda r: (nl(r[0]), nl(r[1])), self.limit)


class Q89(_Star):
    columns = ("ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
               "ss_sales_price")
    ordered, limit = True, 100

    def answer(self, params):
        s = self.suite
        d, d_ok = self.fk("ss_sold_date_sk", gen.JULIAN_1900)
        i, i_ok = self.fk("ss_item_sk")
        st, st_ok = self.fk("ss_store_sk")
        cat, cls = s.item["i_category"][i], s.item["i_class"][i]

        def among(codes, names, vocabulary):
            return np.isin(codes, [vocabulary.index(params[n]) for n in names])

        picked = (
            among(cat, ("cat_a", "cat_b", "cat_c"), gen.CATEGORIES)
            & among(cls, ("class_a", "class_b", "class_c"), gen.CLASSES)
        ) | (
            among(cat, ("cat_d", "cat_e", "cat_f"), gen.CATEGORIES)
            & among(cls, ("class_d", "class_e", "class_f"), gen.CLASSES)
        )
        live = (
            d_ok & i_ok & st_ok & picked
            & (s.date["d_year"][d] == int(params["year"]))
        )
        # GROUP BY i_category, i_brand, s_store_name, s_company_name (the
        # window's partition), then i_class, d_moy
        rows, starts, (gc, gb, gs, gco, gcl, gm) = _groups(
            [cat, s.item["i_brand"][i], s.store["s_store_name"][st],
             s.store["s_company_name"][st], cls, s.date["d_moy"][d]], live,
        )
        sums = [
            _cents(t)
            for t in _sums(self.fact["ss_sales_price"][rows], starts, self.f32)
        ]
        out = []
        part = list(zip(gc, gb, gs, gco))
        g = 0
        while g < len(sums):
            h = g
            while h < len(sums) and part[h] == part[g]:
                h += 1
            # avg(sum(...)) OVER (PARTITION BY ...): decimal, half up
            avg = _half_up(sum(sums[g:h]), h - g)
            for k in range(g, h):
                # abs(sum - avg) / avg at scale 2, half up, > 0.1
                if avg and _half_up(abs(sums[k] - avg) * 100, avg) > 10:
                    out.append((
                        gen.CATEGORIES[gc[k]], gen.CLASSES[gcl[k]],
                        gen.Tpcds.brand(gb[k]), gen.STORE_NAMES[gs[k]],
                        gen.COMPANY_NAMES[gco[k]], int(gm[k]),
                        _dec(sums[k]), _dec(avg),
                    ))
            g = h
        sort_key = lambda r: (r[6] - r[7], r[3])
        head, tail = _top(out, sort_key, self.limit)
        # the sort key is an expression: where no two rows tie on it with
        # other sums, the order is held on (store, sum, average); where
        # some do, on the store name alone
        seen: dict = {}
        for r in head + tail:
            seen.setdefault(sort_key(r), set()).add((r[6], r[7]))
        strict = all(len(v) == 1 for v in seen.values())
        self.key = (3, 6, 7) if strict else (3,)
        return head, tail


QUERIES = {"q3": Q3, "q7": Q7, "q27": Q27, "q89": Q89}


class Suite:
    def __init__(self, schema: str):
        self.data = t = gen.Tpcds(schema)
        self.date = t.date_dim()
        self.item = t.item()
        self.demographics = t.customer_demographics()
        self.promotion = t.promotion()
        self.store = t.store()

    def answers(self, statements, fault=None) -> list:
        """One answer per `(query, params)`: a dict with `rows`, `ordered`,
        `key` (the ORDER BY columns), `limit`, and `tail` (rows that tie
        with the last row on the sort key and could stand in its place)."""
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        columns = sorted({
            c for q, _ in statements for c in QUERIES[q].columns
        })
        n = self.data.rows["store_sales"]
        if fault == "partial_table":
            per = -(-n // RANGES)
            parts = [
                self.data.store_sales(columns, a, min(per, n - a))
                for a in range(0, n, 2 * per)
            ]
            fact = {c: np.concatenate([p[c] for p in parts]) for c in parts[0]}
        else:
            fact = self.data.store_sales(columns)
        out = []
        for q, params in statements:
            w = QUERIES[q](self, fact, fault)
            rows, tail = w.answer(params)
            if fault == "unordered" and w.ordered:
                rows = rows[::-1]
            out.append({
                "rows": rows, "tail": tail, "ordered": w.ordered,
                "key": list(w.key), "limit": w.limit,
            })
        return out
