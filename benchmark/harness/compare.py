"""The comparison that decides `correct`: the rows a statement of the timed
window returned to the client, against the plain reference's answer.

Everything the suites return is exact (integers, decimals carried as
integers, dates, strings), so a cell either equals the reference's or it
does not, and the limit on every count below is 0."""

from __future__ import annotations

import datetime
from collections import Counter
from decimal import Decimal


def same_value(a, e) -> bool:
    """`a` from the client, `e` from the reference.  No tolerance, and no
    float may stand where the reference has a decimal or an integer."""
    if e is None or a is None:
        return a is None and e is None
    if isinstance(e, Decimal):
        return isinstance(a, Decimal) and a == e
    if isinstance(e, bool):
        return isinstance(a, bool) and a == e
    if isinstance(e, int):
        return isinstance(a, int) and not isinstance(a, bool) and a == e
    if isinstance(e, datetime.date):
        return isinstance(a, datetime.date) and a == e
    return type(a) is type(e) and a == e


def _norm(row) -> tuple:
    """A hashable, type-strict form of a row (Decimal('1.0') and 1 differ)."""
    return tuple((type(v).__name__, str(v)) for v in row)


def wrong(actual, answer: dict) -> str:
    """'' when `actual` is a right answer, else what differs (one line)."""
    actual = [tuple(r) for r in actual]
    want = answer["rows"]
    if len(actual) != len(want):
        return f"{len(actual)} rows, reference has {len(want)}"
    if not answer["ordered"]:
        if Counter(map(_norm, actual)) != Counter(map(_norm, want)):
            return f"rows differ: got {actual[:2]}, reference {want[:2]}"
        return ""
    key = answer["key"]
    for i, (ra, re) in enumerate(zip(actual, want)):
        if len(ra) != len(re):
            return f"row {i}: {len(ra)} columns, reference has {len(re)}"
        if not all(same_value(ra[k], re[k]) for k in key):
            return f"row {i} out of order or wrong: got {ra}, reference {re}"
    # rows that tie with the last one on the sort key may stand in for it
    pool = Counter(map(_norm, want + answer.get("tail", [])))
    for i, ra in enumerate(actual):
        n = _norm(ra)
        if pool[n] <= 0:
            return f"row {i} is not in the reference: got {ra}, reference {want[i]}"
        pool[n] -= 1
    return ""
