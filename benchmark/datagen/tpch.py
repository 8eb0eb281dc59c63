"""The benchmark's own copy of the TPC-H data arithmetic.

The `tpch` connector generates every value as a pure function of (table,
column, row index, scale) through a splitmix64 hash
(`trino_tpu/connectors/tpch/generator.py`).  The plain reference must not
take its tables from the program, so the arithmetic of the columns the
benchmark's statements read is copied here, in plain numpy, and imports
nothing of the program.  A later change to the program's generator that
alters the data shows as `correct: false`; one that only makes it faster
does not touch this file.

Only what the reference suites need is here: lineitem (orderkey, quantity,
extendedprice, discount, tax, returnflag, linestatus, shipdate), orders
(orderkey, custkey, orderdate, shippriority, totalprice) and customer
(custkey, name, mktsegment).  Money and quantities are integers (cents,
hundredths), dates are days since 1970-01-01.
"""

from __future__ import annotations

import datetime

import numpy as np

SCHEMAS = {"tiny": 0.01, "sf1": 1.0, "sf10": 10.0, "sf100": 100.0}
BASE_ROWS = {"supplier": 10_000, "part": 200_000, "customer": 150_000,
             "orders": 1_500_000}

SEGMENTS = ("AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY")
RETURNFLAGS = ("A", "N", "R")  # codes of l_returnflag
LINESTATUS = ("F", "O")  # codes of l_linestatus

EPOCH = datetime.date(1970, 1, 1)
START_DATE = (datetime.date(1992, 1, 1) - EPOCH).days
END_DATE = (datetime.date(1998, 12, 31) - EPOCH).days
CURRENT_DATE = (datetime.date(1995, 6, 17) - EPOCH).days
ORDER_DATE_SPAN = END_DATE - START_DATE - 151

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_U = np.uint64


def days(date: datetime.date) -> int:
    return (date - EPOCH).days


def _mix(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = x + _GOLDEN
        z = (z ^ (z >> _U(30))) * _U(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
        return z ^ (z >> _U(31))


def _stream(name: str) -> np.uint64:
    h = _U(1469598103934665603)
    with np.errstate(over="ignore"):
        for ch in name.encode():
            h = (h ^ _U(ch)) * _U(1099511628211)
    return h


def rand64(stream: str, idx: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        return _mix(
            np.asarray(idx, np.uint64) * _U(0x2545F4914F6CDD1D)
            + _stream(stream)
        )


def _mod(stream: str, idx: np.ndarray, n: int) -> np.ndarray:
    return (rand64(stream, idx) % _U(n)).astype(np.int64)


def scaled_rows(table: str, sf: float) -> int:
    return max(1, int(BASE_ROWS[table] * sf))


class Tpch:
    """Columns of one schema, as host numpy arrays."""

    def __init__(self, schema: str):
        self.schema = schema
        self.sf = SCHEMAS[schema]
        self.S = scaled_rows("supplier", self.sf)
        self.P = scaled_rows("part", self.sf)
        self.C = scaled_rows("customer", self.sf)
        self.O = scaled_rows("orders", self.sf)

    # -- orders --------------------------------------------------------------

    def order_dates(self, oidx: np.ndarray) -> np.ndarray:
        return START_DATE + _mod("o_date", oidx, ORDER_DATE_SPAN)

    def order_custkeys(self, oidx: np.ndarray) -> np.ndarray:
        r = _mod("o_cust", oidx, self.C - self.C // 3)
        return (r // 2) * 3 + 1 + (r % 2)

    def line_counts(self, oidx: np.ndarray) -> np.ndarray:
        return 1 + _mod("l_count", oidx, 7)

    def order_totalprice(self, oidx: np.ndarray) -> np.ndarray:
        """o_totalprice in cents: per line extendedprice*(1+tax)*(1-disc),
        floored to the cent, summed over the order's lines."""
        li = self.lineitem(
            ("l_extendedprice", "l_discount", "l_tax"), oidx=oidx
        )
        line_total = (
            li["l_extendedprice"] * (100 + li["l_tax"])
            * (100 - li["l_discount"])
        ) // 10000
        seg = np.repeat(np.arange(len(oidx)), self.line_counts(oidx))
        out = np.zeros(len(oidx), dtype=np.int64)
        np.add.at(out, seg, line_total)
        return out

    # -- customer ------------------------------------------------------------

    def customer_segments(self) -> np.ndarray:
        """Index into SEGMENTS for custkey 1..C (position custkey-1)."""
        return _mod("c_mktseg", np.arange(self.C, dtype=np.int64), 5)

    @staticmethod
    def customer_name(custkey: int) -> str:
        return "Customer#%09d" % custkey

    # -- lineitem ------------------------------------------------------------

    def lineitem(self, columns, oidx=None, order_start=0, order_count=None):
        """The named lineitem columns for a range (or an array) of order
        indices, every line of those orders, in table order."""
        if oidx is None:
            if order_count is None:
                order_count = self.O - order_start
            oidx = np.arange(order_start, order_start + order_count,
                             dtype=np.int64)
        lc = self.line_counts(oidx)
        total = int(lc.sum())
        order_rep = np.repeat(oidx, lc)
        ln = np.arange(total, dtype=np.int64) - np.repeat(
            np.cumsum(lc) - lc, lc
        ) + 1
        lid = order_rep * 8 + ln
        want = set(columns)
        out = {}
        if "l_orderkey" in want:
            out["l_orderkey"] = order_rep + 1
        qty = None
        if want & {"l_quantity", "l_extendedprice"}:
            qty = 1 + _mod("l_qty", lid, 50)
        if "l_quantity" in want:
            out["l_quantity"] = qty * 100
        if "l_extendedprice" in want:
            p = 1 + _mod("l_part", lid, self.P)
            retail = 90000 + ((p // 10) % 20001) + 100 * (p % 1000)
            out["l_extendedprice"] = qty * retail
        if "l_discount" in want:
            out["l_discount"] = _mod("l_disc", lid, 11)
        if "l_tax" in want:
            out["l_tax"] = _mod("l_tax", lid, 9)
        if want & {"l_shipdate", "l_returnflag", "l_linestatus"}:
            odate = np.repeat(self.order_dates(oidx), lc)
            ship = odate + 1 + _mod("l_ship", lid, 121)
            if "l_shipdate" in want:
                out["l_shipdate"] = ship
            if "l_linestatus" in want:
                out["l_linestatus"] = (ship > CURRENT_DATE).astype(np.int64)
            if "l_returnflag" in want:
                receipt = ship + 1 + _mod("l_rcpt", lid, 30)
                coin = _mod("l_rflag", lid, 2).astype(bool)
                # A=0, N=1, R=2 (codes of RETURNFLAGS)
                out["l_returnflag"] = np.where(
                    receipt <= CURRENT_DATE, np.where(coin, 2, 0), 1
                ).astype(np.int64)
        return out
