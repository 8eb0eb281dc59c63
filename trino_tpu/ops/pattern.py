"""Row pattern recognition operator (MATCH_RECOGNIZE).

Reference roles: sql/planner/rowpattern/ (IrRowPattern + Parser),
operator/window/matcher/Matcher.java (the NFA program interpreter) and
PatternRecognitionPartition.

TPU-first split of the work: everything per-row and data-parallel — the
DEFINE predicates, including PREV/NEXT navigation (partition-masked shifts)
— is evaluated ON DEVICE over the whole sorted input in one vectorized pass
per variable.  Only the inherently sequential part (walking the
leftmost-greedy regex over each partition's classification bits) runs on
host, over packed boolean vectors, exactly the part the reference also runs
one-row-at-a-time on the CPU.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import COMPACT, concat_batches, host_pull
from trino_tpu.columnar.dictionary import StringDictionary
from trino_tpu.expr import ExprCompiler
from trino_tpu.expr.compiler import Val, _and_valid
from trino_tpu.expr.functions import register
from trino_tpu.expr.ir import Call, Expr, InputRef, Literal, visit
from trino_tpu.ops.common import SortKey, multi_key_sort_perm, next_pow2


# -- pattern AST + parser ----------------------------------------------------
# grammar (SqlBase.g4 rowPattern, the concatenation/alternation/quantifier
# subset): alt := seq ('|' seq)* ; seq := factor+ ; factor := primary quant? ;
# primary := VAR | '(' alt ')' ; quant := '*' | '+' | '?' | '{' n [',' [m]] '}'


@dataclass
class PVar:
    name: str


@dataclass
class PSeq:
    parts: list


@dataclass
class PAlt:
    options: list


@dataclass
class PQuant:
    child: object
    lo: int
    hi: Optional[int]  # None = unbounded
    greedy: bool = True


def parse_pattern(text: str):
    tokens: list = []
    i = 0
    while i < len(text):
        c = text[i]
        if c.isspace():
            i += 1
        elif c in "()|*+?{}," or c.isdigit():
            tokens.append(c)
            i += 1
        elif c.isalpha() or c == "_":
            j = i
            while j < len(text) and (text[j].isalnum() or text[j] == "_"):
                j += 1
            tokens.append(text[i:j].lower())
            i = j
        else:
            raise ValueError(f"unsupported pattern token {c!r} in {text!r}")
    pos = [0]

    def peek():
        return tokens[pos[0]] if pos[0] < len(tokens) else None

    def take():
        t = peek()
        pos[0] += 1
        return t

    def alt():
        opts = [seq()]
        while peek() == "|":
            take()
            opts.append(seq())
        return opts[0] if len(opts) == 1 else PAlt(opts)

    def seq():
        parts = []
        while peek() is not None and peek() not in ")|":
            parts.append(factor())
        if not parts:
            raise ValueError(f"empty pattern branch in {text!r}")
        return parts[0] if len(parts) == 1 else PSeq(parts)

    def number():
        ds = ""
        while peek() is not None and peek().isdigit():
            ds += take()
        if not ds:
            raise ValueError(f"expected number in quantifier of {text!r}")
        return int(ds)

    def factor():
        t = take()
        if t == "(":
            node = alt()
            if take() != ")":
                raise ValueError(f"unbalanced parens in {text!r}")
        elif t is not None and (t[0].isalpha() or t[0] == "_"):
            node = PVar(t)
        else:
            raise ValueError(f"unexpected {t!r} in pattern {text!r}")
        q = peek()
        if q == "*":
            take()
            return PQuant(node, 0, None)
        if q == "+":
            take()
            return PQuant(node, 1, None)
        if q == "?":
            take()
            return PQuant(node, 0, 1)
        if q == "{":
            take()
            lo = number()
            hi: Optional[int] = lo
            if peek() == ",":
                take()
                hi = number() if peek() is not None and peek().isdigit() else None
            if take() != "}":
                raise ValueError(f"unbalanced {{}} in {text!r}")
            return PQuant(node, lo, hi)
        return node

    out = alt()
    if pos[0] != len(tokens):
        raise ValueError(f"trailing pattern input in {text!r}")
    return out


def pattern_variables(node, acc=None) -> list:
    if acc is None:
        acc = []
    if isinstance(node, PVar):
        if node.name not in acc:
            acc.append(node.name)
    elif isinstance(node, PSeq):
        for p in node.parts:
            pattern_variables(p, acc)
    elif isinstance(node, PAlt):
        for p in node.options:
            pattern_variables(p, acc)
    elif isinstance(node, PQuant):
        pattern_variables(node.child, acc)
    return acc


# -- matcher -----------------------------------------------------------------


def _match_from(node, i: int, end: int, ok, var_ix: dict, labels: list):
    """Generator of end positions for matching `node` starting at row i,
    in regex preference order (greedy quantifiers try longest first).
    `labels` accumulates the classifier per consumed row; generators restore
    it on backtrack."""
    if isinstance(node, PVar):
        v = var_ix[node.name]
        if i < end and ok[v, i]:
            labels.append(node.name)
            yield i + 1
            labels.pop()
        return
    if isinstance(node, PSeq):
        yield from _match_seq(node.parts, 0, i, end, ok, var_ix, labels)
        return
    if isinstance(node, PAlt):
        for opt in node.options:
            yield from _match_from(opt, i, end, ok, var_ix, labels)
        return
    if isinstance(node, PQuant):
        yield from _match_quant(node, i, end, ok, var_ix, labels, 0)
        return
    raise TypeError(node)


def _match_seq(parts, k, i, end, ok, var_ix, labels):
    if k == len(parts):
        yield i
        return
    for j in _match_from(parts[k], i, end, ok, var_ix, labels):
        mark = len(labels)
        yield from _match_seq(parts, k + 1, j, end, ok, var_ix, labels)
        del labels[mark:]


def _match_quant(node, i, end, ok, var_ix, labels, count):
    """Greedy: consume as many repetitions as possible first; `count` is
    repetitions consumed so far."""
    if node.hi is None or count < node.hi:
        for j in _match_from(node.child, i, end, ok, var_ix, labels):
            if j == i:
                break  # zero-width repetition guard
            mark = len(labels)
            yield from _match_quant(node, j, end, ok, var_ix, labels, count + 1)
            del labels[mark:]
    if count >= node.lo:
        yield i


# -- navigation functions (device) -------------------------------------------


@register("$nav_prev")
def _nav_prev(ctx, call, v, n, pid):
    k = int(np.asarray(n.data))
    cap = ctx.capacity
    data = jnp.broadcast_to(jnp.asarray(v.data), (cap,) + jnp.shape(v.data)[1:])
    idx = jnp.arange(cap, dtype=jnp.int64) - k
    src = jnp.clip(idx, 0, cap - 1)
    out = jnp.take(data, src, axis=0)
    same = jnp.logical_and(
        idx >= 0,
        jnp.take(jnp.asarray(pid.data), src) == jnp.asarray(pid.data),
    )
    valid = _and_valid(
        None if v.valid is None else jnp.take(jnp.asarray(v.valid), src), same
    )
    return Val(out, valid, call.type, v.dictionary)


@register("$nav_next")
def _nav_next(ctx, call, v, n, pid):
    k = int(np.asarray(n.data))
    cap = ctx.capacity
    data = jnp.broadcast_to(jnp.asarray(v.data), (cap,) + jnp.shape(v.data)[1:])
    idx = jnp.arange(cap, dtype=jnp.int64) + k
    src = jnp.clip(idx, 0, cap - 1)
    out = jnp.take(data, src, axis=0)
    same = jnp.logical_and(
        idx < cap,
        jnp.take(jnp.asarray(pid.data), src) == jnp.asarray(pid.data),
    )
    valid = _and_valid(
        None if v.valid is None else jnp.take(jnp.asarray(v.valid), src), same
    )
    return Val(out, valid, call.type, v.dictionary)


# -- operator ----------------------------------------------------------------


class PatternRecognitionOperator:
    """Materialize -> device sort -> device DEFINE bools -> host NFA ->
    host-built output batch."""

    def __init__(
        self,
        node,  # P.PatternRecognitionNode
        source_symbols: list,
    ):
        self.node = node
        self.source_symbols = list(source_symbols)
        self.pattern = parse_pattern(node.pattern)
        # variables without a DEFINE entry match any row (the reference's
        # implicit TRUE definition) — `ok` starts all-true in process()
        self.vars = pattern_variables(self.pattern)

    def _channel(self, name: str) -> int:
        for i, s in enumerate(self.source_symbols):
            if s.name == name:
                return i
        raise KeyError(name)

    def process(self, stream):
        batches = list(stream)
        if not batches:
            return
        big = concat_batches(batches) if len(batches) > 1 else batches[0]
        n = big.num_rows_host()
        if n == 0:
            return
        cap = next_pow2(n, floor=1)
        big = COMPACT(big, out_capacity=cap)
        node = self.node
        keys = [SortKey(self._channel(s.name)) for s in node.partition_by] + [
            SortKey(self._channel(s.name), ascending=asc, nulls_first=nf)
            for s, asc, nf in node.order_by
        ]
        if keys:
            perm = multi_key_sort_perm(big, keys)
            live = jnp.take(big.mask(), perm, mode="clip")
            big = big.gather(perm, valid=live)
        host = host_pull(big, "host_operator")
        live_h = np.asarray(host.mask())[:n]
        # partition ids from sorted partition-key runs: a new partition
        # starts wherever ANY key's (value, validity) changes — collision
        # free, null-safe (the sorted-run analog of group_ids_from_sorted)
        change = np.zeros(n, dtype=bool)
        for s in node.partition_by:
            c = host.columns[self._channel(s.name)]
            d = np.asarray(c.data)[:n]
            change[1:] |= d[1:] != d[:-1]
            if c.valid is not None:
                v = np.asarray(c.valid)[:n]
                change[1:] |= v[1:] != v[:-1]
        pid = np.cumsum(change)
        # DEFINE bools on device: rewrite prev/next -> $nav calls with the
        # pid channel appended.  Padded dead slots get pid -1 so navigation
        # never treats them as in-partition (compact_device's dead slots
        # read row 0: `slot_sources` gives them source row 0).
        pid_col = Column(
            jnp.asarray(
                np.pad(pid, (0, cap - n), constant_values=-1)
            ),
            T.BIGINT,
        )
        dev = Batch(list(big.columns) + [pid_col], big.row_mask)
        pid_ch = len(big.columns)

        def rewrite_nav(e: Expr) -> Expr:
            def fn(x: Expr) -> Expr:
                if isinstance(x, Call) and x.name in ("prev", "next"):
                    arg = x.args[0]
                    k = (
                        x.args[1]
                        if len(x.args) > 1
                        else Literal(1, T.BIGINT)
                    )
                    return Call(
                        "$nav_prev" if x.name == "prev" else "$nav_next",
                        [arg, k, InputRef(pid_ch, T.BIGINT)],
                        x.type,
                    )
                return x

            return visit(e, fn)

        ok = np.ones((len(self.vars), n), dtype=bool)
        defines = dict(self.node.defines)
        compiler = ExprCompiler(dev)
        for vi, v in enumerate(self.vars):
            cond = defines.get(v)
            if cond is None:
                continue
            mask = compiler.filter_mask(rewrite_nav(cond))
            ok[vi] = host_pull(mask, "host_operator")[:n]
        ok &= live_h[None, :]
        var_ix = {v: i for i, v in enumerate(self.vars)}
        # host NFA walk per partition
        yield from self._emit(host, n, pid, ok, var_ix)

    # -- matching + output ----------------------------------------------------

    def _emit(self, host: Batch, n: int, pid, ok, var_ix):
        node = self.node
        starts = np.flatnonzero(
            np.concatenate(([True], pid[1:] != pid[:-1]))
        ) if n else np.array([], dtype=np.int64)
        bounds = list(starts) + [n]
        matches = []  # (start, end, labels list, match_number)
        for b in range(len(bounds) - 1):
            lo, hi = bounds[b], bounds[b + 1]
            i = lo
            mno = 0  # MATCH_NUMBER() restarts per partition (SQL-2016)
            while i < hi:
                labels: list = []
                got = None
                for end in _match_from(
                    self.pattern, i, hi, ok, var_ix, labels
                ):
                    got = (end, list(labels))
                    break
                if got is not None and got[0] > i:
                    mno += 1
                    matches.append((i, got[0], got[1], mno))
                    i = got[0] if node.after_match == "past_last" else i + 1
                else:
                    i += 1
        yield self._build_output(host, matches)

    def _measure_values(self, host, s0, e0, labels, mno):
        out = []
        for _sym, m in self.node.measures:
            if m.kind == "match_number":
                out.append(mno)
                continue
            if m.kind == "classifier":
                out.append(labels[-1] if labels else None)
                continue
            if m.kind == "agg" and m.source is None:  # count(*)
                out.append(e0 - s0)
                continue
            rows = range(s0, e0)
            if m.var is not None:
                rows = [
                    r for r, lab in zip(range(s0, e0), labels) if lab == m.var
                ]
            ch = self._channel(m.source.name)
            col = host.columns[ch]
            data = np.asarray(col.data)
            valid = None if col.valid is None else np.asarray(col.valid)

            def decode(r):
                if valid is not None and not valid[r]:
                    return None
                v = data[r]
                if col.dictionary is not None:
                    return col.dictionary.values[int(v)]
                return v

            vals = [decode(r) for r in rows]
            if m.kind in ("first", "last"):
                ix = m.offset if m.kind == "first" else len(vals) - 1 - m.offset
                out.append(vals[ix] if 0 <= ix < len(vals) else None)
                continue
            live_vals = [v for v in vals if v is not None]
            if m.agg == "count":
                out.append(len(live_vals))
            elif not live_vals:
                out.append(None)
            elif m.agg == "sum":
                out.append(sum(live_vals))
            elif m.agg == "min":
                out.append(min(live_vals))
            elif m.agg == "max":
                out.append(max(live_vals))
            elif m.agg == "avg":
                out.append(float(sum(live_vals)) / len(live_vals))
            else:
                raise NotImplementedError(f"measure agg {m.agg}")
        return out

    def _build_output(self, host: Batch, matches) -> Batch:
        node = self.node
        one = node.rows_per_match == "one"
        rows_out: list = []  # parallel lists per output column
        out_syms = node.outputs
        per_col: list = [[] for _ in out_syms]
        for (s0, e0, labels, mno) in matches:
            measures = self._measure_values(host, s0, e0, labels, mno)
            if one:
                head = [
                    self._host_value(host, self._channel(s.name), s0)
                    for s in node.partition_by
                ]
                for ci, v in enumerate(head + measures):
                    per_col[ci].append(v)
            else:
                for off, r in enumerate(range(s0, e0)):
                    row_measures = list(measures)
                    # per-row classifier under ALL ROWS PER MATCH
                    for mi, (_s, m) in enumerate(node.measures):
                        if m.kind == "classifier":
                            row_measures[mi] = labels[off]
                    head = [
                        self._host_value(host, ci, r)
                        for ci in range(len(self.source_symbols))
                    ]
                    for ci, v in enumerate(head + row_measures):
                        per_col[ci].append(v)
        cols = []
        for sym, values in zip(out_syms, per_col):
            cols.append(_column_from_python(sym.type, values))
        cap = len(per_col[0]) if per_col else 0
        return Batch(cols, None if cap else np.zeros(0, dtype=bool))

    def _host_value(self, host: Batch, ch: int, row: int):
        col = host.columns[ch]
        if col.valid is not None and not np.asarray(col.valid)[row]:
            return None
        v = np.asarray(col.data)[row]
        if col.dictionary is not None:
            return col.dictionary.values[int(v)]
        return v


def _column_from_python(t: T.Type, values: list) -> Column:
    if T.is_string_kind(t):
        return Column.from_strings(values, t)
    arr = np.zeros(len(values), dtype=t.np_dtype)
    valid = np.ones(len(values), dtype=bool)
    for i, v in enumerate(values):
        if v is None:
            valid[i] = False
        else:
            arr[i] = v
    return Column(
        arr, t, None if valid.all() else valid, None
    )
