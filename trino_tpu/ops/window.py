"""Window function operator (reference: operator/WindowOperator.java +
operator/window/* — rank family, value family, aggregate-over-frame).

TPU substitution: one materialized sort by (partition keys, order keys), then
every window function is a closed-form computation over partition/peer
boundary flags — prefix sums (`cumsum`), segment reductions, and shifted
gathers — a single static-shape XLA program instead of the reference's
per-partition imperative loops (WindowPartition.processNextRow).

Supported frames: the SQL default RANGE BETWEEN UNBOUNDED PRECEDING AND
CURRENT ROW (running, peer-inclusive), ROWS frames with unbounded or literal
row offsets (reference: operator/window/FrameInfo.java), and the
whole-partition frame (no ORDER BY, or UNBOUNDED..UNBOUNDED).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import concat_batches
from trino_tpu.ops.aggregation import _pad_device
from trino_tpu.ops.common import (
    SortKey,
    _max_sentinel,
    _min_sentinel,
    multi_key_sort_perm,
    next_pow2,
)
from trino_tpu.telemetry.programs import jit_program


@dataclass(frozen=True)
class WindowSpec:
    """One window function: rank family (no arg) or aggregate/value family
    (arg = input channel).  frame: 'range' (default running, peer-aware),
    'rows' (running, row-exact), 'full' (whole partition)."""

    name: str  # row_number | rank | dense_rank | ntile | percent_rank |
    #            cume_dist | lag | lead | first_value | last_value |
    #            sum | count | avg | min | max
    arg: Optional[int]
    out_type: T.Type
    offset: int = 1  # lag/lead offset (literal)
    default_channel: Optional[int] = None  # lag/lead default value column
    n_buckets: int = 1  # ntile
    frame: str = "range"
    # ROWS-frame bounds relative to the current row (None = unbounded on that
    # side); the default running frame is (None, 0).
    start_off: Optional[int] = None
    end_off: Optional[int] = 0
    # IGNORE NULLS for lag/lead/first_value/last_value (reference:
    # operator/window/LagFunction.java ignoreNulls handling)
    ignore_nulls: bool = False
    #: proof-licensed |frame sum| bound for decimal sum/avg (planner range
    #: certificate, plan.WindowFunction.sum_bound): a long-decimal input
    #: whose every frame sum provably fits int64 runs the single-plane
    #: prefix-sum kernel instead of limb-plane arithmetic
    sum_bound: Optional[int] = None


_WINDOW_STEP_CACHE: dict = {}


class WindowOperator:
    def __init__(
        self,
        partition_channels: Sequence[int],
        order_keys: Sequence[SortKey],
        specs: Sequence[WindowSpec],
    ):
        self.partition_channels = list(partition_channels)
        self.order_keys = list(order_keys)
        self.specs = list(specs)
        self._acc: list[Batch] = []
        # shared jitted step across per-query instances (wave execution
        # constructs one operator per wave; identical configs must not
        # re-trace — the _STEP_CACHE convention of ops/sort.py)
        key = (
            "window",
            tuple(self.partition_channels),
            tuple(self.order_keys),
            tuple(
                (
                    sp.name, sp.arg, sp.out_type.name, sp.offset,
                    sp.default_channel, sp.n_buckets, sp.frame,
                    sp.start_off, sp.end_off, sp.ignore_nulls,
                    sp.sum_bound,
                )
                for sp in self.specs
            ),
        )
        cached = _WINDOW_STEP_CACHE.get(key)
        if cached is None:
            cached = jit_program(self._window_step, "window")
            _WINDOW_STEP_CACHE[key] = cached
        self._step = cached

    # -- the jitted kernel ----------------------------------------------------

    def _window_step(self, batch: Batch) -> Batch:
        cap = batch.capacity
        keys = [SortKey(ch) for ch in self.partition_channels] + self.order_keys
        # always sort: even with no keys, multi_key_sort_perm moves dead
        # (filtered-out) rows last, so positional logic below only sees live
        # rows in the prefix — `row_number() over ()` must not count dead rows
        perm = multi_key_sort_perm(batch, keys)
        live = jnp.take(batch.mask(), perm, mode="clip")
        pos = jnp.arange(cap, dtype=jnp.int64)

        # partition boundaries (null-safe equality over partition keys)
        new_part = jnp.zeros(cap, dtype=bool)
        first_live = jnp.logical_and(live, jnp.cumsum(live) == 1)
        for ch in self.partition_channels:
            col = batch.columns[ch]
            d = jnp.take(col.data, perm, axis=0, mode="clip")
            neq = d != jnp.roll(d, 1, axis=0)
            if neq.ndim > 1:  # long-decimal limb planes
                neq = jnp.any(neq, axis=-1)
            if col.valid is not None:
                v = jnp.take(col.valid, perm, mode="clip")
                pv = jnp.roll(v, 1)
                neq = jnp.logical_or(
                    jnp.logical_and(neq, jnp.logical_and(v, pv)), v != pv
                )
            new_part = jnp.logical_or(new_part, neq)
        new_part = jnp.logical_or(jnp.logical_and(live, new_part), first_live)
        pid = jnp.cumsum(new_part) - 1  # partition id per sorted row
        pid = jnp.where(live, pid, cap)
        nseg = cap + 1
        part_start = jax.ops.segment_min(jnp.where(live, pos, cap), pid, nseg)
        part_size = jax.ops.segment_sum(live.astype(jnp.int64), pid, nseg)
        idx_in_part = pos - part_start[jnp.clip(pid, 0, cap)]

        # peer boundaries (order-key ties within a partition)
        new_peer = new_part
        for k in self.order_keys:
            col = batch.columns[k.channel]
            d = jnp.take(col.data, perm, axis=0, mode="clip")
            neq = d != jnp.roll(d, 1, axis=0)
            if neq.ndim > 1:  # long-decimal limb planes
                neq = jnp.any(neq, axis=-1)
            if col.valid is not None:
                v = jnp.take(col.valid, perm, mode="clip")
                pv = jnp.roll(v, 1)
                neq = jnp.logical_or(
                    jnp.logical_and(neq, jnp.logical_and(v, pv)), v != pv
                )
            new_peer = jnp.logical_or(new_peer, jnp.logical_and(live, neq))
        peer_gid = jnp.cumsum(new_peer) - 1
        peer_gid = jnp.where(live, peer_gid, cap)
        # last row index of each peer group (for RANGE running frames)
        peer_last = jax.ops.segment_max(jnp.where(live, pos, -1), peer_gid, nseg)

        out_cols = []
        for spec in self.specs:
            vals = self._compute(
                spec, batch, perm, live, pid, nseg, part_start, part_size,
                idx_in_part, new_peer, peer_gid, peer_last, pos, cap,
            )
            out_cols.append(vals)
        # scatter back to original row order
        inv = jnp.zeros(cap, dtype=jnp.int64).at[perm].set(pos)
        final_cols = list(batch.columns)
        for c in out_cols:
            data = jnp.take(c.data, inv, axis=0, mode="clip")
            valid = None if c.valid is None else jnp.take(c.valid, inv, mode="clip")
            final_cols.append(Column(data, c.type, valid, c.dictionary))
        return Batch(final_cols, batch.row_mask)

    @staticmethod
    def _valid_ranks(v, live, part_first, pos, cap):
        """(pref, pos_of) for IGNORE NULLS: pref[i] = count of non-null live
        rows at or before sorted row i WITHIN its partition; pos_of is a
        [cap+1] table mapping slot part_first + rank (0-based, per
        partition) -> the sorted-row index of that partition's rank-th
        non-null row (cap = no such row).  Slots of different partitions
        are disjoint because ranks never exceed the partition size."""
        vi = jnp.logical_and(live, v)
        c = jnp.cumsum(vi.astype(jnp.int64))
        base = jnp.where(
            part_first > 0,
            jnp.take(c, jnp.maximum(part_first - 1, 0), mode="clip"),
            0,
        )
        pref = c - base
        slot = jnp.where(vi, part_first + pref - 1, cap)
        pos_of = jnp.full(cap + 1, cap, jnp.int64).at[slot].set(
            pos, mode="drop"
        )
        return pref, pos_of

    def _compute(
        self, spec, batch, perm, live, pid, nseg, part_start, part_size,
        idx_in_part, new_peer, peer_gid, peer_last, pos, cap,
    ) -> Column:
        name = spec.name
        safe_pid = jnp.clip(pid, 0, cap)
        n_in_part = part_size[safe_pid]

        # frame bounds as sorted-row indices [lo, hi] per row (FrameInfo.java)
        part_first = part_start[safe_pid]
        part_last = part_first + n_in_part - 1
        whole = spec.frame == "full" or not self.order_keys
        if whole:
            lo, hi = part_first, part_last
        elif spec.frame == "rows":
            lo = (
                part_first
                if spec.start_off is None
                else jnp.maximum(part_first, pos + spec.start_off)
            )
            hi = (
                part_last
                if spec.end_off is None
                else jnp.minimum(part_last, pos + spec.end_off)
            )
        else:  # default RANGE running frame: start of partition .. last peer
            lo = part_first
            hi = peer_last[jnp.clip(peer_gid, 0, cap)]
        frame_n = jnp.maximum(hi - lo + 1, 0)

        if name == "row_number":
            return Column(idx_in_part + 1, T.BIGINT, None)
        if name in ("rank", "dense_rank", "percent_rank", "cume_dist", "ntile"):
            # rank = index of first peer row in partition + 1
            first_peer = jax.ops.segment_min(jnp.where(live, pos, cap), peer_gid, nseg)
            rank = first_peer[jnp.clip(peer_gid, 0, cap)] - part_start[safe_pid] + 1
            if name == "rank":
                return Column(rank, T.BIGINT, None)
            if name == "dense_rank":
                dense = jnp.cumsum(new_peer) - jnp.take(
                    jnp.cumsum(new_peer), part_start[safe_pid], mode="clip"
                ) + 1
                return Column(dense, T.BIGINT, None)
            if name == "percent_rank":
                den = jnp.maximum(n_in_part - 1, 1)
                return Column((rank - 1) / den, T.DOUBLE, None)
            if name == "cume_dist":
                last = peer_last[jnp.clip(peer_gid, 0, cap)]
                covered = last - part_start[safe_pid] + 1
                return Column(covered / jnp.maximum(n_in_part, 1), T.DOUBLE, None)
            if name == "ntile":
                n = spec.n_buckets
                sz = n_in_part
                base, rem = sz // n, sz % n
                big = (base + 1) * rem  # rows covered by the larger buckets
                in_big = idx_in_part < big
                bucket = jnp.where(
                    in_big,
                    idx_in_part // jnp.maximum(base + 1, 1),
                    rem + (idx_in_part - big) // jnp.maximum(base, 1),
                )
                return Column(bucket + 1, T.BIGINT, None)
        if name in ("lag", "lead"):
            col = batch.columns[spec.arg]
            d = jnp.take(col.data, perm, axis=0, mode="clip")
            v = jnp.take(col.valid, perm, mode="clip") if col.valid is not None else jnp.ones(cap, bool)
            if spec.ignore_nulls:
                # k-th non-null neighbour via per-partition valid-rank
                # indexing: rank positions scatter to a dense pos_of table
                # laid out at partition offsets, so one gather finds the row
                pref, pos_of = self._valid_ranks(
                    v, live, part_first, pos, cap
                )
                if name == "lag":
                    tgt = pref - v.astype(jnp.int64) - spec.offset
                    found = tgt >= 0
                else:
                    total = jnp.take(
                        pref, jnp.clip(part_last, 0, cap - 1), mode="clip"
                    )
                    tgt = pref + spec.offset - 1
                    found = pref + spec.offset <= total
                slot = jnp.where(found, part_first + tgt, cap)
                src_row = jnp.take(pos_of, jnp.clip(slot, 0, cap), mode="clip")
                data = jnp.take(d, jnp.clip(src_row, 0, cap - 1), axis=0, mode="clip")
                valid = jnp.logical_and(found, src_row < cap)
                if spec.default_channel is not None:
                    dc = batch.columns[spec.default_channel]
                    dd = jnp.take(dc.data, perm, axis=0, mode="clip")
                    dv = (
                        jnp.take(dc.valid, perm, mode="clip")
                        if dc.valid is not None
                        else jnp.ones(cap, bool)
                    )
                    data = jnp.where(valid, data, dd)
                    valid = jnp.where(valid, valid, dv)
                return Column(
                    data.astype(spec.out_type.np_dtype), spec.out_type,
                    valid, col.dictionary,
                )
            off = spec.offset if name == "lag" else -spec.offset
            src = pos - off
            in_part = jnp.logical_and(
                src >= part_start[safe_pid], src < part_start[safe_pid] + n_in_part
            )
            src_c = jnp.clip(src, 0, cap - 1)
            data = jnp.take(d, src_c, axis=0, mode="clip")
            valid = jnp.logical_and(in_part, jnp.take(v, src_c, mode="clip"))
            if spec.default_channel is not None:
                dc = batch.columns[spec.default_channel]
                dd = jnp.take(dc.data, perm, axis=0, mode="clip")
                dv = (
                    jnp.take(dc.valid, perm, mode="clip")
                    if dc.valid is not None
                    else jnp.ones(cap, bool)
                )
                data = jnp.where(in_part, data, dd)
                valid = jnp.where(in_part, valid, dv)
            return Column(data.astype(spec.out_type.np_dtype), spec.out_type, valid, col.dictionary)
        if name in ("first_value", "last_value", "nth_value"):
            col = batch.columns[spec.arg]
            d = jnp.take(col.data, perm, axis=0, mode="clip")
            v = jnp.take(col.valid, perm, mode="clip") if col.valid is not None else jnp.ones(cap, bool)
            if spec.ignore_nulls:
                # first/last/nth non-null row of the frame [lo, hi] via the
                # same valid-rank table: frame valid count = pref[hi]-pref[lo-1]
                pref, pos_of = self._valid_ranks(
                    v, live, part_first, pos, cap
                )
                before = jnp.where(
                    lo > part_first,
                    jnp.take(pref, jnp.clip(lo - 1, 0, cap - 1), mode="clip"),
                    0,
                )
                upto = jnp.where(
                    frame_n > 0,
                    jnp.take(pref, jnp.clip(hi, 0, cap - 1), mode="clip"),
                    before,
                )
                if name == "first_value":
                    found = upto > before
                    rank0 = before
                elif name == "last_value":
                    found = upto > before
                    rank0 = upto - 1
                else:  # nth_value(x, n): n-th non-null row of the frame
                    found = upto - before >= spec.offset
                    rank0 = before + spec.offset - 1
                slot = jnp.where(found, part_first + rank0, cap)
                src_row = jnp.take(pos_of, jnp.clip(slot, 0, cap), mode="clip")
                return Column(
                    jnp.take(d, jnp.clip(src_row, 0, cap - 1), axis=0, mode="clip")
                    .astype(spec.out_type.np_dtype),
                    spec.out_type,
                    jnp.logical_and(found, src_row < cap),
                    col.dictionary,
                )
            if name == "nth_value":
                src_raw = lo + spec.offset - 1
                in_frame = src_raw <= hi
                src = jnp.clip(src_raw, 0, cap - 1)
                return Column(
                    jnp.take(d, src, axis=0, mode="clip").astype(
                        spec.out_type.np_dtype
                    ),
                    spec.out_type,
                    jnp.logical_and(
                        jnp.logical_and(
                            jnp.take(v, src, mode="clip"), in_frame
                        ),
                        frame_n > 0,
                    ),
                    col.dictionary,
                )
            src = jnp.clip(lo if name == "first_value" else hi, 0, cap - 1)
            return Column(
                jnp.take(d, src, axis=0, mode="clip").astype(spec.out_type.np_dtype),
                spec.out_type,
                jnp.logical_and(jnp.take(v, src, mode="clip"), frame_n > 0),
                col.dictionary,
            )
        # aggregates over the frame
        if name == "count" and spec.arg is None:  # count(*) over (...)
            return Column(frame_n, T.BIGINT, None)
        col = batch.columns[spec.arg]
        d = jnp.take(col.data, perm, axis=0, mode="clip")
        v = live
        if col.valid is not None:
            v = jnp.logical_and(v, jnp.take(col.valid, perm, mode="clip"))
        if name in ("sum", "avg", "count"):
            if d.ndim > 1:
                if name != "count":
                    # long-decimal (two-limb) input: exact frame sums over
                    # limb planes — or, when the planner attached a range
                    # certificate proving every frame sum fits int64, the
                    # single-plane licensed kernel
                    return self._long_decimal_sum_avg(
                        spec, name, d, v, whole, pid, nseg, safe_pid,
                        lo, hi, frame_n, cap,
                    )
                # count reads only the validity mask: a 1-D surrogate keeps
                # the shared sum/count reduction below shape-correct
                d = jnp.zeros(d.shape[0], dtype=jnp.int64)
            dd = jnp.where(v, d, 0).astype(
                jnp.float64 if jnp.issubdtype(d.dtype, jnp.floating) else jnp.int64
            )
            cnt_inc = v.astype(jnp.int64)
            if whole:
                ssum = jax.ops.segment_sum(dd, pid, nseg)[safe_pid]
                scnt = jax.ops.segment_sum(cnt_inc, pid, nseg)[safe_pid]
            else:
                run = jnp.cumsum(dd)
                runc = jnp.cumsum(cnt_inc)
                run_at = lambda r, i: jnp.take(r, jnp.clip(i, 0, cap - 1), mode="clip")
                before = jnp.where(lo > 0, run_at(run, lo - 1), 0)
                beforec = jnp.where(lo > 0, run_at(runc, lo - 1), 0)
                ssum = jnp.where(frame_n > 0, run_at(run, hi) - before, 0)
                scnt = jnp.where(frame_n > 0, run_at(runc, hi) - beforec, 0)
            if name == "count":
                return Column(scnt, T.BIGINT, None)
            if name == "sum":
                return Column(
                    ssum.astype(spec.out_type.np_dtype), spec.out_type, scnt > 0, col.dictionary
                )
            if isinstance(spec.out_type, T.DecimalType):
                # exact integer half-away-from-zero, matching the grouped
                # aggregate's _finalize (jnp.round is half-to-even)
                den = jnp.maximum(scnt, 1)
                sign = jnp.sign(ssum)
                q = jnp.abs(ssum) // den
                r = jnp.abs(ssum) - q * den
                avg = sign * (q + jnp.where(2 * r >= den, 1, 0))
            else:
                avg = ssum.astype(jnp.float64) / jnp.maximum(scnt, 1)
            return Column(avg.astype(spec.out_type.np_dtype), spec.out_type, scnt > 0)
        if name in ("min", "max"):
            if d.ndim > 1:
                raise NotImplementedError(
                    "window min/max over a long-decimal input column "
                    "(cast to decimal(18,s) or double first)"
                )
            return self._minmax(
                spec, name, d, v, whole, pid, nseg, safe_pid, lo, hi,
                frame_n, cap, col,
            )
        raise NotImplementedError(f"window function {name}")

    def _long_decimal_sum_avg(
        self, spec, name, d, v, whole, pid, nseg, safe_pid, lo, hi,
        frame_n, cap,
    ) -> Column:
        """sum/avg over a long-decimal (limb-plane) input column.

        Validity contract: invalid rows are zeroed before every reduction
        (additive identity) and the output plane is scnt > 0 — NULLs can
        never resurface as values (the dropped-validity hazard the
        numeric verifier polices).

        Licensed path: the planner's range certificate (WindowSpec
        .sum_bound, from verify.numeric.license_decimal_sums) proves every
        value AND every frame sum lies inside int64, so the low limb IS
        the value (high limb pure sign extension) and one i64 prefix /
        segment sum is exact — no limb traffic, no runtime check.

        Limb path: exact i128 frame sums.  Whole-partition frames reduce
        via segment_sum128; running frames build prefix sums over the four
        32-bit chunk planes (each prefix stays under cap * 2**32 < 2**63,
        the recombine4 contract) and difference them per frame with a full
        128-bit borrow."""
        from trino_tpu.ops.aggregation import _note_fastpath
        from trino_tpu.types import int128 as i128

        h = jnp.asarray(d[:, 0], jnp.int64)
        l = jnp.asarray(d[:, 1], jnp.int64)
        h = jnp.where(v, h, 0)
        l = jnp.where(v, l, 0)
        cnt_inc = v.astype(jnp.int64)

        def run_at(r, i):
            return jnp.take(r, jnp.clip(i, 0, cap - 1), mode="clip")

        if whole:
            scnt = jax.ops.segment_sum(cnt_inc, pid, nseg)[safe_pid]
        else:
            runc = jnp.cumsum(cnt_inc)
            beforec = jnp.where(lo > 0, run_at(runc, lo - 1), 0)
            scnt = jnp.where(frame_n > 0, run_at(runc, hi) - beforec, 0)

        licensed = (
            spec.sum_bound is not None and spec.sum_bound < (1 << 63) - 1
        )
        if licensed:
            _note_fastpath("proven")
            # |value| <= sum_bound < 2**63: the low limb is the value
            if whole:
                ssum = jax.ops.segment_sum(l, pid, nseg)[safe_pid]
            else:
                run = jnp.cumsum(l)
                before = jnp.where(lo > 0, run_at(run, lo - 1), 0)
                ssum = jnp.where(frame_n > 0, run_at(run, hi) - before, 0)
            sh, sl = i128.widen64(ssum)
        else:
            _note_fastpath("limb")
            if whole:
                sh, sl = i128.segment_sum128(h, l, pid, nseg)
                sh = sh[safe_pid]
                sl = sl[safe_pid]
            else:
                mask32 = jnp.int64(0xFFFFFFFF)
                planes = (l & mask32, (l >> 32) & mask32, h & mask32, h >> 32)
                runs = [jnp.cumsum(p) for p in planes]

                def frame_at(i, present):
                    vals = [
                        jnp.where(present, run_at(r, i), 0) for r in runs
                    ]
                    return i128.recombine4(*vals)

                eh, el = frame_at(hi, frame_n > 0)
                bh, bl = frame_at(lo - 1, jnp.logical_and(frame_n > 0, lo > 0))
                sh, sl = i128.sub128(eh, el, bh, bl)

        if name == "sum":
            if spec.out_type.is_long:
                data = jnp.stack([sh, sl], axis=-1)
            else:
                # a short declared result asserts the values fit: the low
                # limb carries them exactly (same contract as _finalize)
                data = sl
            return Column(data, spec.out_type, scnt > 0)
        # avg: exact integer division, round half away from zero —
        # mirroring _finalize's DecimalAverageAggregation path bit for bit
        den = jnp.maximum(scnt, 1)
        qh, ql, r = i128.divmod128_by_vec(sh, sl, den)
        half = jnp.where(2 * jnp.abs(r) >= den, 1, 0)
        neg = sh < 0
        bump = jnp.where(neg, -half, half)
        qh2, ql2 = i128.add128(qh, ql, bump >> 63, bump)
        if spec.out_type.is_long:
            data = jnp.stack([qh2, ql2], axis=-1)
        else:
            data = ql2
        return Column(data, spec.out_type, scnt > 0)

    def _minmax(
        self, spec, name, d, v, whole, pid, nseg, safe_pid, lo, hi,
        frame_n, cap, col,
    ) -> Column:
        sent = _max_sentinel(d.dtype) if name == "min" else _min_sentinel(d.dtype)
        dd = jnp.where(v, d, sent)
        if whole:
            red = (
                jax.ops.segment_min(dd, pid, nseg)
                if name == "min"
                else jax.ops.segment_max(dd, pid, nseg)
            )[safe_pid]
            cnt = jax.ops.segment_sum(v.astype(jnp.int64), pid, nseg)[safe_pid]
            return Column(red, spec.out_type, cnt > 0, col.dictionary)
        op = jnp.minimum if name == "min" else jnp.maximum
        hi_c = jnp.clip(hi, 0, cap - 1)
        if spec.start_off is not None:
            # bounded sliding min/max: sparse-table range query
            # (O(n log n) build of power-of-two block minima, O(1)
            # two-block query per row — fully vectorized; the TPU-native
            # substitute for the reference's per-row frame re-scan)
            levels = [dd]
            width = 1
            while width < cap:
                prev = levels[-1]
                shifted = jnp.concatenate(
                    [prev[width:], jnp.full(width, sent, dd.dtype)]
                )
                levels.append(op(prev, shifted))
                width *= 2
            table = jnp.stack(levels)  # [L, cap]; level j covers 2^j rows
            length = jnp.maximum(hi - lo + 1, 1)
            j = (
                jnp.floor(jnp.log2(length.astype(jnp.float64)))
            ).astype(jnp.int64)
            j = jnp.clip(j, 0, len(levels) - 1)
            lo_c = jnp.clip(lo, 0, cap - 1)
            start2 = jnp.clip(hi - (jnp.int64(1) << j) + 1, 0, cap - 1)
            flat = table.reshape(-1)
            a_val = jnp.take(flat, j * cap + lo_c, mode="clip")
            b_val = jnp.take(flat, j * cap + start2, mode="clip")
            red = op(a_val, b_val)
        else:
            # running min/max: prefix scan reset at partition starts —
            # cummax over (partition-tagged) values via associative_scan
            def scan_fn(a, b):
                a_pid, a_val = a
                b_pid, b_val = b
                merged = jnp.where(a_pid == b_pid, op(a_val, b_val), b_val)
                return (b_pid, merged)

            _, red = jax.lax.associative_scan(scan_fn, (pid, dd))
            red = jnp.take(red, hi_c, mode="clip")
        runc = jnp.cumsum(v.astype(jnp.int64))
        before = jnp.where(
            lo > 0, jnp.take(runc, jnp.clip(lo - 1, 0, cap - 1), mode="clip"), 0
        )
        cnt = jnp.where(
            frame_n > 0, jnp.take(runc, hi_c, mode="clip") - before, 0
        )
        return Column(red, spec.out_type, cnt > 0, col.dictionary)

    # -- host-side ------------------------------------------------------------

    def _unify_default_dicts(self, batch: Batch) -> Batch:
        """lag/lead defaults must share the argument's dictionary: the kernel
        merges raw codes with jnp.where, so mixed dictionaries would decode
        wrongly (host-side recode, the DictionaryBlock-compaction analog)."""
        from trino_tpu.columnar.dictionary import union_many

        cols = list(batch.columns)
        for spec in self.specs:
            if spec.name not in ("lag", "lead") or spec.default_channel is None:
                continue
            a, d = cols[spec.arg], cols[spec.default_channel]
            if a.dictionary is None and d.dictionary is None:
                continue
            if a.dictionary is d.dictionary or a.dictionary == d.dictionary:
                continue
            if a.dictionary is None or d.dictionary is None:
                raise NotImplementedError(
                    "lag/lead default mixes dictionary and non-dictionary strings"
                )
            merged, (ta, td) = union_many([a.dictionary, d.dictionary])
            for ch, col, table in ((spec.arg, a, ta), (spec.default_channel, d, td)):
                if table is None:
                    cols[ch] = Column(col.data, col.type, col.valid, merged)
                else:
                    cols[ch] = Column(
                        jnp.take(
                            jnp.asarray(table), jnp.asarray(col.data, jnp.int64),
                            mode="clip",
                        ),
                        col.type, col.valid, merged,
                    )
        return batch.with_columns(cols)

    def process(self, stream):
        for b in stream:
            self._acc.append(b)
        if not self._acc:
            return
        big = self._acc[0] if len(self._acc) == 1 else concat_batches(self._acc)
        big = self._unify_default_dicts(big)
        big = _pad_device(big, next_pow2(big.capacity, floor=1))
        yield self._step(big)
