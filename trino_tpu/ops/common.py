"""Shared device kernels: multi-key stable sort, segmented grouping.

Reference roles: OrderingCompiler (sql/gen/OrderingCompiler.java) for sort
orders, MultiChannelGroupByHash.getGroupIds (operator/MultiChannelGroupByHash
.java:216) for group-id assignment.  The TPU substitution is sort-based:
iterated stable argsorts (lexicographic) + key-change flags + cumsum group ids
+ segmented reductions — all static-shape, all fusable by XLA.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from trino_tpu.columnar import Batch, Column
from trino_tpu.telemetry.programs import note_path
from trino_tpu.types import DecimalType


@dataclass(frozen=True)
class SortKey:
    channel: int
    ascending: bool = True
    nulls_first: bool = False


def _key_with_null_order(col: Column, ascending: bool, nulls_first: bool):
    """(rank or None, value key) for one sort key.

    The value key realizes direction without arithmetic negation of ints
    (bitwise complement is INT64_MIN-safe) and without float bitcasts (which
    the TPU x64-rewrite cannot lower): NaN and NULL placement ride a small
    int8 rank sorted in a second stable pass.  NaN orders as largest
    (reference DoubleOperators semantics); NULL placement follows nulls_first.
    """
    data = col.data
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    rank = None
    if jnp.issubdtype(data.dtype, jnp.floating):
        nan = jnp.isnan(data)
        value_key = jnp.where(nan, jnp.asarray(0, data.dtype), data)
        if not ascending:
            value_key = -value_key  # finite negation is exact for floats
        rank = jnp.where(nan, 1 if ascending else -1, 0).astype(jnp.int8)
    else:
        value_key = data if ascending else ~data
    if col.valid is not None:
        base = rank if rank is not None else jnp.zeros_like(data, dtype=jnp.int8)
        rank = jnp.where(
            col.valid, base, jnp.asarray(-2 if nulls_first else 2, jnp.int8)
        )
    return rank, value_key


def multi_key_sort_perm(batch: Batch, keys, capacity=None):
    """Stable permutation sorting live rows by `keys` (lexicographic);
    dead rows sort last.  keys: sequence of SortKey."""
    n = batch.capacity
    perm = jnp.arange(n, dtype=jnp.int64)
    # iterate stable sorts from least-significant key to most-significant
    for k in reversed(list(keys)):
        col = batch.columns[k.channel].gather(perm)
        if col.data.ndim == 2 and isinstance(col.type, DecimalType):
            # long decimal: two stable passes — low limb (unsigned order via
            # sign-flip), then high limb; null rank rides the high pass
            from trino_tpu.types.int128 import _SIGN

            lo = col.data[:, 1] ^ _SIGN
            if not k.ascending:
                lo = ~lo
            perm = perm[jnp.argsort(lo, stable=True)]
            hi = jnp.take(
                batch.columns[k.channel].data[:, 0], perm, mode="clip"
            )
            if not k.ascending:
                hi = ~hi
            perm = perm[jnp.argsort(hi, stable=True)]
            if col.valid is not None:
                v = jnp.take(batch.columns[k.channel].valid, perm, mode="clip")
                rank = jnp.where(
                    v,
                    jnp.zeros(n, jnp.int8),
                    jnp.asarray(-2 if k.nulls_first else 2, jnp.int8),
                )
                perm = perm[jnp.argsort(rank, stable=True)]
            continue
        rank, key = _key_with_null_order(col, k.ascending, k.nulls_first)
        order = jnp.argsort(key, stable=True)
        perm = perm[order]
        if rank is not None:
            perm = perm[jnp.argsort(rank[order], stable=True)]
    # dead rows last (most significant)
    dead = jnp.logical_not(jnp.take(batch.mask(), perm, mode="clip"))
    perm = perm[jnp.argsort(dead, stable=True)]
    return perm


def group_ids_from_sorted(batch: Batch, perm, key_channels):
    """Given a sort permutation over group keys, return (gid_sorted, ngroups,
    new_group_flags): group ids in sorted order, null-safe equality."""
    n = batch.capacity
    live = jnp.take(batch.mask(), perm, mode="clip")
    change = jnp.zeros(n, dtype=bool)
    for ch in key_channels:
        col = batch.columns[ch]
        d = jnp.take(col.data, perm, axis=0, mode="clip")
        prev = jnp.roll(d, 1, axis=0)
        neq = d != prev
        if neq.ndim > 1:  # long decimal limb planes: any limb differing
            neq = jnp.any(neq, axis=-1)
        if col.valid is not None:
            v = jnp.take(col.valid, perm, mode="clip")
            pv = jnp.roll(v, 1)
            neq = jnp.logical_or(jnp.logical_and(neq, jnp.logical_and(v, pv)), v != pv)
        change = jnp.logical_or(change, neq)
    first_live = jnp.logical_and(live, jnp.cumsum(live) == 1)
    new_group = jnp.logical_and(live, jnp.logical_or(change, first_live))
    new_group = jnp.logical_or(new_group, first_live)
    gid = jnp.cumsum(new_group) - 1
    gid = jnp.where(live, gid, n - 1)  # dead rows into last (masked) slot
    ngroups = jnp.sum(new_group)
    return gid, ngroups, new_group


#: A segmented reduction over at most this many segments runs DENSE — one
#: fused masked pass over the rows per segment, `reduce_n where(gid[n] == s,
#: value[n], identity)`.  Above it the reduction runs over the RUNS of a
#: non-decreasing group id where the caller has one (`Runs`: the
#: range-positional aggregation, `AggregationOperator._range_step`), and as
#: a scatter (`jax.ops.segment_*`) only for the callers that number their
#: groups some other way.  The TPU has no scatter hardware: XLA serialises
#: a scatter-add at 60-130 ns a row whatever the segment count (63-133 ms a
#: 2^20-row batch on a v5e), while the dense pass costs rows x segments
#: vector work and materialises nothing [rows, segments]-shaped.  Chosen by
#: the on-chip sweep in PERF.md section 6 (PR 27, tools/segment_sweep.py):
#: the largest swept count at which dense was at least 4x faster (12.4 ms
#: against 69.5 ms at 2049; 20.2 against 69.5 at 4097 is 3.4x).  Segment
#: counts are a capacity plus one dead slot, hence the odd number.
DENSE_SEGMENT_LIMIT = (1 << 11) + 1

_SEGMENT_SCATTER = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}


def _reduce_segments(values, gid, num_segments: int, op: str):
    """[num_segments] sum/min/max of a [n] plane by segment id; rows that
    must not count already hold the identity.  Out-of-range and negative
    ids drop and an empty segment reads the identity, on both lowerings;
    `num_segments` is static, so the choice is made while the step traces
    and `note_path` carries it to the launch span (`dense` | `scatter`)."""
    if num_segments > DENSE_SEGMENT_LIMIT:
        note_path("scatter")
        return _SEGMENT_SCATTER[op](values, gid, num_segments)
    note_path("dense")
    if op == "sum":
        identity = jnp.zeros((), values.dtype)
        reduce = partial(jnp.sum, dtype=values.dtype)
    else:
        identity = (_max_sentinel if op == "min" else _min_sentinel)(values.dtype)
        reduce = jnp.min if op == "min" else jnp.max
    if num_segments == 1:
        return reduce(jnp.where(gid == 0, values, identity))[None]
    onehot = gid[:, None] == jnp.arange(num_segments, dtype=gid.dtype)[None, :]
    return reduce(jnp.where(onehot, values[:, None], identity), axis=0)


def segment_reduce(values, gid, num_segments: int, kind: str, valid=None):
    """Null-skipping segmented reduction -> [num_segments].  kind:
    sum/min/max/count/any.  THE one place that lowers a segment op: over a
    `Runs` (a group id non-decreasing over the live rows, its run ends
    found once) a reduction over runs, at any segment count and never a
    scatter; over a plain [n] `gid`, dense masked reductions up to
    DENSE_SEGMENT_LIMIT segments and a scatter above (see
    `_reduce_segments`).  Same integers every way."""
    if isinstance(gid, Runs):
        assert num_segments == gid.slots, (num_segments, gid.slots)
        return _reduce_runs(values, gid, kind, valid)
    if kind == "count":
        w = jnp.ones(gid.shape, jnp.int64)
        if valid is not None:
            w = valid.astype(jnp.int64)
        return _reduce_segments(w, gid, num_segments, "sum")
    if kind == "any":
        # first VALID value per segment (any_value): min row index among valid
        n = values.shape[0]
        idx = jnp.arange(n, dtype=jnp.int64)
        if valid is not None:
            idx = jnp.where(valid, idx, n)
        first = _reduce_segments(idx, gid, num_segments, "min")
        return jnp.take(values, jnp.clip(first, 0, n - 1), mode="clip")
    if kind not in _SEGMENT_SCATTER:
        raise ValueError(kind)
    if valid is not None:
        if kind == "sum":
            values = jnp.where(valid, values, 0)
        elif kind == "min":
            values = jnp.where(valid, values, _max_sentinel(values.dtype))
        else:
            values = jnp.where(valid, values, _min_sentinel(values.dtype))
    return _reduce_segments(values, gid, num_segments, kind)


# -- reductions over the runs of a non-decreasing group id ----------------------

#: rows of one block of the two-level scans below: a row-wise scan of
#: [capacity / 4096, 4096] and one over the blocks' totals compile in 1-3 s
#: at 2^20-2^21 rows; ONE plane-wide `jnp.cumsum` of 2^19-2^20 rows
#: compiled for 18-34 s (tools/compact_sweep.py, PERF.md section 6, PR 31),
#: and a `lax.associative_scan` over 4096 lanes for 3-9 minutes (PR 33)
_SCAN_BLOCK = 4096


def _blocks(x, fill):
    width = min(_SCAN_BLOCK, x.shape[0])
    return jnp.pad(
        x, (0, -x.shape[0] % width), constant_values=fill
    ).reshape(-1, width)


def prefix_sum(x):
    """Inclusive running sum of a [n] plane, in its own dtype (integers
    wrap), in two levels."""
    b = _blocks(x, 0)
    within = jnp.cumsum(b, axis=1, dtype=x.dtype)
    total = within[:, -1]
    before = jnp.cumsum(total, dtype=x.dtype) - total
    return (within + before[:, None]).reshape(-1)[: x.shape[0]]


def running_max(x):
    """Inclusive running maximum of a [n] integer plane, in two levels."""
    low = _min_sentinel(x.dtype)
    within = jax.lax.cummax(_blocks(x, low), axis=1)
    before = jnp.concatenate(
        [low[None], jax.lax.cummax(within[:, -1])[:-1]]
    )
    return jnp.maximum(within, before[:, None]).reshape(-1)[: x.shape[0]]


@dataclass(frozen=True)
class Runs:
    """A group id that is non-decreasing over the live rows, as its RUNS:
    group k of the output is the k-th run, in id order, packed to the front.
    Dead rows may lie anywhere: each rides in the run of the live row before
    it and adds the identity.  Positions and ids are int32 (the chip
    emulates int64 as two u32 planes)."""

    rows: jnp.ndarray  # [n] bool: the live rows
    gid: jnp.ndarray  # [n] int32: the id of the newest live row, -1 before one
    last: jnp.ndarray  # [n] bool: the row closes a run
    src: jnp.ndarray  # [slots] int32: the row that closes the k-th run
    live: jnp.ndarray  # [slots] bool: k < number of runs

    @property
    def slots(self) -> int:
        return self.src.shape[0]

    def slot_of_rows(self):
        """[n] int32: the output slot of each row's run."""
        ends = prefix_sum(self.last.astype(jnp.int32)) - self.last
        return jnp.minimum(ends, self.slots - 1)


def run_ids(gid, live, slots: int) -> Runs:
    """`Runs` of a [n] integer `gid` (0 <= gid < 2^31 on live rows) that is
    non-decreasing over the rows `live` marks; `slots` is static and at
    least the number of distinct ids.  No scatter: the newest live id is a
    running maximum, and the run ends a compaction of a boundary mask
    (`slot_sources`)."""
    from trino_tpu.columnar.batch import slot_sources

    n = gid.shape[0]
    assert 0 < n < (1 << 31) and slots < (1 << 31), (n, slots)
    filled = running_max(jnp.where(live, gid, -1).astype(jnp.int32))
    after = jnp.concatenate([filled[1:], jnp.full(1, -2, jnp.int32)])
    last = jnp.logical_and(filled != after, filled >= 0)
    src, out_live = slot_sources(last, slots)
    return Runs(live, filled, last, src, out_live)


def _scan_runs(values, gid, op):
    """Inclusive scan of `op` over a [n] plane that restarts at every run
    of the non-decreasing `gid`: log2(n) shifted passes, each combining a
    row with the row 2^k before it while both lie in one run.  A run's
    result is a tree over its own rows only — it never depends on the rows
    before the run, which a difference of float prefix sums would."""
    n = values.shape[0]
    shift = 1
    while shift < n:
        earlier = jnp.concatenate([values[:shift], values[:-shift]])
        same = jnp.concatenate(
            [jnp.zeros(shift, bool), gid[shift:] == gid[:-shift]]
        )
        values = jnp.where(same, op(values, earlier), values)
        shift *= 2
    return values


def _reduce_runs(values, runs: Runs, kind: str, valid):
    """`segment_reduce` over `Runs` -> [runs.slots]; a slot past the last
    run reads the identity, as an empty segment does."""
    rows = runs.rows if valid is None else jnp.logical_and(valid, runs.rows)

    def run_totals(plane):
        # a run's sum is a difference of the running sum at two run ends;
        # integers wrap, so it is exact whenever the run's own sum fits
        ends = jnp.take(prefix_sum(plane), runs.src, mode="clip")
        before = jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
        return jnp.where(runs.live, ends - before, 0)

    def run_scan(plane, op, identity):
        ends = jnp.take(_scan_runs(plane, runs.gid, op), runs.src, mode="clip")
        return jnp.where(runs.live, ends, identity)

    if kind == "count":
        return run_totals(rows.astype(jnp.int32)).astype(jnp.int64)
    if kind == "any":
        n = runs.rows.shape[0]
        idx = jnp.where(rows, jnp.arange(n, dtype=jnp.int32), n)
        first = run_scan(idx, jnp.minimum, n)
        return jnp.take(values, first, axis=0, mode="clip")
    if kind == "sum":
        zero = jnp.zeros((), values.dtype)
        plane = jnp.where(rows, values, zero)
        if jnp.issubdtype(values.dtype, jnp.integer):
            return run_totals(plane)
        return run_scan(plane, jnp.add, zero)
    if kind not in ("min", "max"):
        raise ValueError(kind)
    identity = (_max_sentinel if kind == "min" else _min_sentinel)(values.dtype)
    op = jnp.minimum if kind == "min" else jnp.maximum
    return run_scan(jnp.where(rows, values, identity), op, identity)


def segment_values_of_rows(per_segment, gid):
    """[n]: each row's segment's entry of a `segment_reduce` result."""
    if isinstance(gid, Runs):
        gid = gid.slot_of_rows()
    return jnp.take(per_segment, gid, mode="clip")


def _max_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    if jnp.dtype(dtype) == jnp.dtype(bool):
        return jnp.asarray(True, dtype)  # bool_and identity
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def _min_sentinel(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(-jnp.inf, dtype)
    if jnp.dtype(dtype) == jnp.dtype(bool):
        return jnp.asarray(False, dtype)  # bool_or identity
    return jnp.asarray(jnp.iinfo(dtype).min, dtype)


def next_pow2(n: int, floor: int = 1024) -> int:
    c = floor
    while c < n:
        c <<= 1
    return c


def splitmix64(u):
    """The splitmix64 finalizer over uint64 arrays/scalars (works on numpy
    and traced jax values; uint64 wrap-around is the intended semantics).
    THE shared copy — serde/aggregation/generators still carry inline
    duplicates that compute the same bytes; new code should call this,
    and the duplicates can be folded into it at leisure."""
    import numpy as np

    with np.errstate(over="ignore"):
        u = (u ^ (u >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        u = (u ^ (u >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return u ^ (u >> np.uint64(31))
