"""Aggregation operator — sort-based grouped reduction.

Reference roles: HashAggregationOperator.java:49, AggregationOperator (global),
MultiChannelGroupByHash.java:216 (group ids), operator/aggregation/* (the
accumulator library).  TPU substitution (SURVEY.md §7): no per-row hash
probing — group ids come from a stable multi-key sort + key-change cumsum, and
accumulators are segmented reductions, all in one jitted finish step.

Modes mirror the reference's AggregationNode.Step:
  SINGLE  : raw rows -> final values
  PARTIAL : raw rows -> state columns (for exchange)
  FINAL   : state columns -> final values

`streaming=True` reduces every pushed batch immediately and keeps only the
per-batch group states (bounded memory for low-cardinality groupings like
TPC-H Q1); otherwise input is materialized and reduced once at finish.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import COMPACT, concat_batches, host_pull
from trino_tpu.ops.common import (
    DENSE_SEGMENT_LIMIT,
    SortKey,
    group_ids_from_sorted,
    multi_key_sort_perm,
    next_pow2,
    run_ids,
    running_max,
    segment_reduce,
)


#: process-level jitted-step cache: instances are per-query but configs
#: recur, so identical aggregation programs share one jit wrapper (the
#: AccumulatorCompiler class-cache analog)
_STEP_CACHE: dict = {}


@dataclass(frozen=True)
class AggSpec:
    """One SQL aggregate: name in {count, count_star, sum, min, max, avg,
    any_value, bool_and, bool_or, stddev_samp, stddev_pop, var_samp,
    var_pop, percentile}, arg = input channel (None for count_star)."""

    name: str
    arg: Optional[int]
    out_type: T.Type
    param: object = None  # percentile fraction
    arg2: Optional[int] = None  # second input channel (map_agg values)
    #: proof-licensed |partial sum| bound for decimal sum/avg (planner
    #: range certificate, plan.Aggregation.sum_bound): _sum128 compiles the
    #: single-plane i64 path with no runtime fits check when set
    sum_bound: Optional[int] = None


from trino_tpu.planner.functions import HOLISTIC_AGGS
from trino_tpu.telemetry.programs import jit_program, note_path

#: collect subset of the holistic aggregates (padded-array group state)
COLLECT_AGGS = ("array_agg", "map_agg", "listagg")

#: moment family: grouped state is (sum, sum-of-squares, count)
MOMENT = ("stddev_samp", "stddev_pop", "var_samp", "var_pop")

#: two-input (y, x) regression family: state is the raw-sum sextuple
BIVARIATE = ("covar_samp", "covar_pop", "corr", "regr_slope", "regr_intercept")

#: checksum's NULL-row contribution (the reference's PRIME64 role)
CHECKSUM_NULL_PRIME = 0x9E3779B185EBCA87


def _group_ranks(varg, gid_c, cap: int, nseg: int):
    """(pos_in_group, counts) for the collect-style scatters: the 0-based
    rank of each kept row (varg) within its group in sorted order, and the
    per-group kept-row counts.  Shared by _collect_one and _minmax_by_n."""
    rank_incl = jnp.cumsum(varg.astype(jnp.int64))
    base = segment_reduce(
        jnp.where(varg, rank_incl - 1, cap + 1), gid_c, nseg, "min"
    )
    pos_in_group = rank_incl - 1 - jnp.take(base, gid_c, mode="clip")
    counts = segment_reduce(None, gid_c, nseg, "count", valid=varg)
    return pos_in_group, counts


#: HyperLogLog registers per sketch: p=13 -> 8192 buckets, standard error
#: 1.04/sqrt(8192) ~= 1.15% (reference: ApproximateCountDistinctAggregation
#: defaults + state/HyperLogLogStateFactory.java:23)
HLL_P = 13
HLL_M = 1 << HLL_P


def _hll_hash(col: Column):
    """Per-row 64-bit hash of the column's VALUE — stable across workers
    (dictionary codes are producer-local, so dict values hash through a
    trace-time crc table, mirroring parallel/serde.stable_row_hash)."""
    import hashlib

    d = col.data
    if col.dictionary is not None:
        # full 64-bit value hash (blake2b/8): checksum() needs real 64-bit
        # entropy — a 32-bit crc birthday-collides at ~77k distinct values
        table = np.fromiter(
            (
                np.int64(
                    np.uint64(
                        int.from_bytes(
                            hashlib.blake2b(
                                v.encode() if isinstance(v, str) else bytes(v),
                                digest_size=8,
                            ).digest(),
                            "little",
                        )
                    )
                )
                for v in col.dictionary.values
            ),
            dtype=np.int64,
            count=len(col.dictionary.values),
        )
        h = jnp.take(jnp.asarray(table), jnp.asarray(d, jnp.int32), mode="clip")
    elif jnp.issubdtype(d.dtype, jnp.floating):
        # No float bitcasts (TPU x64-rewrite can't lower them) and no frexp
        # (it lowers THROUGH a bitcast): decompose via exp2/log2 instead.
        # The rounding at power-of-two boundaries is deterministic per value,
        # which is all a hash needs.  -0.0 collapses to 0.0, NaN to 0.
        f = d + 0.0
        f = jnp.where(jnp.isnan(f), jnp.float64(0.0), f)
        a = jnp.abs(f)
        expo = jnp.where(
            a > 0.0, jnp.floor(jnp.log2(jnp.where(a > 0.0, a, 1.0))), 0.0
        )
        expo = jnp.clip(expo, -1074.0, 1023.0)
        mant = jnp.where(a > 0.0, a * jnp.exp2(-expo), 0.0)  # in [1, 2)
        h = (
            (mant * (1 << 52)).astype(jnp.int64)
            ^ (expo.astype(jnp.int64) << 1)
            ^ jnp.where(f < 0.0, jnp.int64(1) << 62, jnp.int64(0))
        )
    else:
        h = d.astype(jnp.int64)
    # splitmix64 finalizer (python ints wrap via uint64 numpy constants)
    u = h.astype(jnp.uint64)
    u = (u ^ (u >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    u = (u ^ (u >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    u = u ^ (u >> np.uint64(31))
    return u


def _hll_registers(col: Column, valid) -> jnp.ndarray:
    """[HLL_M] int32 register vector over the valid rows of one column."""
    u = _hll_hash(col)
    bucket = (u >> np.uint64(64 - HLL_P)).astype(jnp.int64)
    rest = (u << np.uint64(HLL_P)) | np.uint64(1)  # sentinel stops rank at max
    # rank = leading zeros of `rest` + 1, via a branchless integer
    # bit-length cascade (pure shifts/compares — nothing the TPU
    # x64-rewrite can't lower, unlike frexp/bitcast)
    x = rest
    bitlen = jnp.zeros(rest.shape, jnp.int32)
    for s in (32, 16, 8, 4, 2, 1):
        y = x >> np.uint64(s)
        gt = y > 0
        bitlen = jnp.where(gt, bitlen + s, bitlen)
        x = jnp.where(gt, y, x)
    bitlen = bitlen + (x > 0).astype(jnp.int32)
    rank = 64 - bitlen + 1
    bucket = jnp.where(valid, bucket, HLL_M)
    return segment_reduce(
        jnp.where(valid, rank, 0), bucket, HLL_M + 1, "max"
    )[:HLL_M].astype(jnp.int32)


def _hll_estimate(registers) -> jnp.ndarray:
    """Registers [..., M] -> BIGINT cardinality (HLL raw estimator + the
    small-range linear-counting correction), vectorized over leading axes."""
    m = float(HLL_M)
    alpha = 0.7213 / (1.0 + 1.079 / m)
    r = jnp.maximum(registers.astype(jnp.float64), 0.0)
    z = jnp.sum(jnp.power(2.0, -r), axis=-1)
    raw = alpha * m * m / z
    v = jnp.sum(registers <= 0, axis=-1)
    linear = m * jnp.log(m / jnp.maximum(v, 1).astype(jnp.float64))
    est = jnp.where((raw <= 2.5 * m) & (v > 0), linear, raw)
    return jnp.round(est).astype(jnp.int64)


# primitive states per SQL aggregate (state kinds: sum/count/min/max/any)
def _primitives(spec: AggSpec):
    if spec.name == "approx_distinct":
        return [("hll", spec.arg)]
    if spec.name == "approx_percentile":
        # log-bucket quantile sketch (reference: qdigest states), merged by
        # elementwise count addition
        return [("qdigest", spec.arg)]
    if spec.name == "count_star":
        return [("count_star", None)]
    if spec.name == "count":
        return [("count", spec.arg)]
    if spec.name in ("sum", "avg"):
        return [("sum", spec.arg), ("count", spec.arg)]
    if spec.name in ("min", "bool_and"):
        return [("min", spec.arg), ("count", spec.arg)]
    if spec.name in ("max", "bool_or"):
        return [("max", spec.arg), ("count", spec.arg)]
    if spec.name == "any_value":
        return [("any", spec.arg), ("count", spec.arg)]
    if spec.name in MOMENT:
        # reference: operator/aggregation VarianceState (count/mean/m2 as
        # merged moments; here the raw-sum formulation merges by addition)
        return [("sum_f", spec.arg), ("sumsq", spec.arg), ("count", spec.arg)]
    if spec.name == "checksum":
        # order-independent wrapping sum of per-row value hashes
        # (reference: operator/aggregation/ChecksumAggregationFunction —
        # xor/sum of XXH64; ours sums 64-bit hashes, same contract: equal
        # multisets give equal checksums, mergeable by addition).  NULL rows
        # contribute a fixed prime (the reference's PRIME64), so NULL
        # placement changes the checksum and all-NULL input is non-null.
        return [("checksum", spec.arg), ("count_star", None)]
    if spec.name in BIVARIATE:
        # reference: operator/aggregation CovarianceState/CorrelationState —
        # raw-sum formulation, merged by addition; rows with EITHER side
        # null are skipped entirely (pairwise validity)
        return [
            ("bi_sum_1", spec.arg), ("bi_sum_2", spec.arg2),
            ("bi_sumsq_1", spec.arg), ("bi_sumsq_2", spec.arg2),
            ("bi_sum_12", spec.arg), ("bi_count", spec.arg),
        ]
    raise NotImplementedError(f"aggregate: {spec.name}")


def _state_types(spec: AggSpec, input_types) -> list[T.Type]:
    out = []
    for kind, arg in _primitives(spec):
        if kind == "hll":
            out.append(T.ArrayType(T.INTEGER))
        elif kind == "qdigest":
            out.append(T.ArrayType(T.BIGINT))
        elif kind in ("count", "count_star"):
            out.append(T.BIGINT)
        elif kind == "checksum":
            out.append(T.BIGINT)
        elif kind in ("sum_f", "sumsq")or kind.startswith("bi_sum"):
            out.append(T.DOUBLE)
        elif kind == "bi_count":
            out.append(T.BIGINT)
        elif kind == "sum":
            t = input_types[arg]
            if isinstance(t, T.DecimalType):
                # reference: DecimalSumAggregation — Int128 state, exact
                out.append(T.DecimalType(38, t.scale))
            elif t.name in ("double", "real"):
                out.append(T.DOUBLE)
            else:
                out.append(T.BIGINT)
        else:
            out.append(input_types[arg])
    return out


def _merge_primitives(spec: AggSpec):
    """How each state column merges in FINAL mode (state kind per column)."""
    prims = _primitives(spec)
    merged = []
    for kind, _ in prims:
        # counts and moment sums are already-reduced values: merge by adding;
        # HLL registers merge by elementwise max
        if kind in ("hll", "qdigest"):
            merged.append(kind)
        else:
            merged.append(
                "sum"
                if kind in ("count", "count_star", "sum_f", "sumsq", "checksum")
                or kind.startswith("bi_")
                else kind
            )
    return merged


def _reduce128(d, gid, nseg: int, kind: str, valid):
    """min/max/any over long-decimal limb planes -> [nseg, 2]."""
    from trino_tpu.types import int128 as i128

    if kind in ("min", "max"):
        h, l = i128.segment_minmax128(
            jnp.asarray(d[:, 0], jnp.int64),
            jnp.asarray(d[:, 1], jnp.int64),
            gid,
            nseg,
            valid,
            kind == "max",
        )
        return jnp.stack([h, l], axis=-1)
    if kind == "any":
        n = d.shape[0]
        idx = jnp.where(valid, jnp.arange(n, dtype=jnp.int64), n)
        first = segment_reduce(idx, gid, nseg, "min")
        return jnp.take(d, jnp.clip(first, 0, n - 1), axis=0, mode="clip")
    raise NotImplementedError(f"long decimal {kind}")


def _onehot_plane_sums(gid, live, planes, prod: int):
    """Per-plane [prod] per-group sums of [cap] planes against the [cap,
    prod] one-hot of the live rows' group ids: `sum_n where(onehot[n, g],
    plane[n], 0)`, one masked reduction per plane (XLA fuses them into one
    pass over the rows; nothing [cap, prod, K]-shaped is materialized).

    Integer planes reduce in int64 and are EXACT on every backend — never
    route them through f64: the TPU emulates it, and an f64 one-hot einsum
    returned Q1's sum(l_extendedprice) wrong in the 5th digit on a v5e (PR
    21).  Double planes reduce in f64, with the backend's float semantics.
    Module-level so tests/test_tpu_compile.py can AOT-compile exactly what
    `_onehot_direct_sums` runs."""
    onehot = jnp.logical_and(
        gid[:, None] == jnp.arange(prod, dtype=gid.dtype)[None, :],
        live[:, None],
    )
    return [
        jnp.sum(jnp.where(onehot, p[:, None], jnp.zeros((), p.dtype)), axis=0)
        for p in planes
    ]


def _rows(plane, perm):
    """`plane` in the step's row order: gathered through `perm`, or as it
    is where the step kept the input's order (`perm` None) — an identity
    gather still costs 7-14 ns a row on the chip."""
    if perm is None:
        return plane
    return jnp.take(plane, perm, axis=0, mode="clip")


def _range_gid(batch: Batch, channels, mins, sizes):
    """[capacity] int32 mixed-radix code of each row's group keys over the
    domain `mins`/`sizes` ([keys] int64, traced): per key the value minus
    its minimum, NULL the last code of a nullable key; lexicographic in the
    keys.  Exact where the domain's product fits POSITIONAL_LIMIT."""
    gid = jnp.zeros(batch.capacity, dtype=jnp.int32)
    for i, ch in enumerate(channels):
        col = batch.columns[ch]
        size_v = sizes[i] - (1 if col.valid is not None else 0)
        code = jnp.clip(
            col.data.astype(jnp.int64) - mins[i], 0, jnp.maximum(size_v - 1, 0)
        )
        if col.valid is not None:
            code = jnp.where(col.valid, code, size_v)
        gid = gid * sizes[i].astype(jnp.int32) + code.astype(jnp.int32)
    return gid


def _note_fastpath(path: str) -> None:
    """Record the trace-time decimal-sum path choice (proven |
    runtime_check | limb).  Called while a kernel TRACES: the count is per
    traced program, not per execution — the choice is static per compiled
    program, so warm replays add nothing and a warm run's zero
    runtime_check delta is a real guarantee."""
    from trino_tpu.telemetry.metrics import decimal_fastpath_counter

    decimal_fastpath_counter().labels(path).inc()


def _note_agg_path(path: str) -> None:
    """Record the grouped-aggregation kernel choice (pallas | onehot |
    segmented | positional | sort, and runs | sorted_runs for how the
    positional step reduced many groups; `segment_reduce` adds dense |
    scatter for the segment ops under it).  Called where a step chooses, which
    under jit is while it TRACES: the launch door remembers the choice on
    the program and replays it on every launch (`path=` on the `launch`
    span; `trino_tpu_aggregation_path_total` counts executions)."""
    note_path(path)


def _sum128(
    d, gid, nseg: int, valid, in_precision: int = None, sum_bound: int = None
):
    """Exact i128 segmented sum -> [nseg, 2] limb planes.  Input is either a
    short scaled-i64 column (1-D, widened) or long planes ([n, 2]).

    Fast paths, strongest proof first:

      * `sum_bound` — a range-certificate license (verify.numeric
        sum_certificate): every partial sum of every subset of contributing
        rows is statically bounded by |s| <= sum_bound < 2**63, from
        per-column generator stats / literal bounds x a sound total-row
        bound.  ONE i64 segment sum is provably exact: values individually
        fit i64 (|v| <= sum_bound), so the high limb is pure sign
        extension and never needs summing.
      * declared-precision proof — 10**in_precision * rows < 2**63 (static
        per trace): the type's range contract alone bounds the batch.
      * otherwise a fused runtime fits probe picks narrow/wide per batch
        under lax.cond (exact either way, but the probe and the compiled
        wide branch are the cost the certificates exist to delete).

    Every segment sum here and in types/int128 goes through
    `segment_reduce`: a dense masked reduction at few segments (`nseg` 1
    from `_global_reduce`, the direct path's `prod + 1`); above
    DENSE_SEGMENT_LIMIT a difference of prefix sums at run ends where
    `gid` is a `Runs` (`_range_step`), a scatter-add from the sorted
    numbering.  The integers are the same: a prefix sum wraps, a run's own
    sum fits whenever the licences below say a segment's does."""
    from trino_tpu.types import int128 as i128

    rows = d.shape[0]
    #: per-row magnitude under which `rows` addends provably sum inside i64
    thr = ((1 << 63) - 1) // max(rows, 1)
    licensed = sum_bound is not None and sum_bound < (1 << 63) - 1
    if d.ndim == 2:
        h = jnp.asarray(d[:, 0], jnp.int64)
        l = jnp.asarray(d[:, 1], jnp.int64)
        if valid is not None:
            h = jnp.where(valid, h, 0)
            l = jnp.where(valid, l, 0)
        if licensed or (
            in_precision is not None
            and (10**in_precision) * rows < (1 << 63)
        ):
            # STATIC narrow proof for limb-plane inputs (the CPU fallback
            # of the one-hot matmul path): |v| is bounded inside i64 by the
            # range certificate or by 10**p — the high limb is pure sign
            # extension by that bound — and every partial sum provably
            # stays inside i64, so ONE i64 segment sum is exact with no
            # runtime fits scan and no lax.cond (a widened-but-narrow
            # column never pays the limb-plane cost).
            _note_fastpath("proven")
            return jnp.stack(
                i128.widen64(segment_reduce(l, gid, nseg, "sum")),
                axis=-1,
            )
        # Runtime-adaptive narrow path (the common TPC-H shape: a product
        # typed decimal(25+) whose actual values are ~10 digits).  One cheap
        # FUSED pass proves the batch's values are i64 (high limb == sign
        # extension) and small enough that `rows` of them can't overflow an
        # i64 accumulator; lax.cond then runs a single segment sum instead
        # of the 3-4 chunk-plane sums.  Exact either way — the check reads
        # the data, not the (over-wide) declared precision.  The per-row
        # conjunction folds the three reductions the old form paid
        # (all/max/min) into one elementwise pass + one all-reduce.
        _note_fastpath("runtime_check")
        fits = jnp.all(
            jnp.logical_and(
                h == (l >> 63),
                jnp.logical_and(l < thr, l > -thr),
            )
        )
        hi_direct = (
            in_precision is not None
            and ((10**in_precision >> 64) + 1) * rows < (1 << 62)
        )

        def _fast(_):
            return i128.widen64(segment_reduce(l, gid, nseg, "sum"))

        def _wide(_):
            return i128.segment_sum128(
                h, l, gid, nseg, valid=None, hi_direct=hi_direct
            )

        h, l = jax.lax.cond(fits, _fast, _wide, None)
    else:
        d = jnp.asarray(d, jnp.int64)
        if valid is not None:
            d = jnp.where(valid, d, 0)
        if licensed or (
            in_precision is not None
            and (10**in_precision) * rows < (1 << 63)
        ):
            _note_fastpath("proven")
            red = segment_reduce(d, gid, nseg, "sum")
            h, l = i128.widen64(red)
        else:
            _note_fastpath("runtime_check")
            fits = jnp.logical_and(jnp.max(d) < thr, jnp.min(d) > -thr)

            def _fast(_):
                return i128.widen64(segment_reduce(d, gid, nseg, "sum"))

            def _wide(_):
                return i128.sum128_widened(d, gid, nseg, valid=None)

            h, l = jax.lax.cond(fits, _fast, _wide, None)
    return jnp.stack([h, l], axis=-1)


def _finalize(spec: AggSpec, states: list[Column]) -> Column:
    """Combine state columns into the SQL result column."""
    name = spec.name
    if name == "approx_distinct":
        return Column(_hll_estimate(states[0].data), T.BIGINT, None)
    if name == "approx_percentile":
        from trino_tpu.ops import qdigest as qd

        p = float(spec.param if spec.param is not None else 0.5)
        counts = states[0].data
        if counts.ndim == 2:
            counts = counts[0]
        val, total = qd.estimate(counts, p)
        out_t = spec.out_type
        if isinstance(out_t, T.DecimalType):
            if out_t.is_long:
                # float -> limb planes (values can exceed i64; same split
                # as the double->long-decimal cast)
                from trino_tpu.types.int128 import TWO64

                r = jnp.round(val * out_t.scale_factor)
                h = jnp.floor(r / float(TWO64)).astype(jnp.int64)
                lf = r - h.astype(jnp.float64) * float(TWO64)
                l = jnp.where(
                    lf >= float(1 << 63), lf - float(TWO64), lf
                ).astype(jnp.int64)
                return Column(
                    jnp.stack([h, l], axis=-1)[None, :],
                    out_t,
                    (total > 0)[None],
                )
            scaled = jnp.round(val * out_t.scale_factor).astype(jnp.int64)
            return Column(scaled[None], out_t, (total > 0)[None])
        return Column(
            val.astype(out_t.np_dtype)[None], out_t, (total > 0)[None]
        )
    if name in ("count", "count_star"):
        return Column(states[0].data, T.BIGINT, None)
    if name == "checksum":
        return Column(states[0].data, T.BIGINT, states[1].data > 0)
    if name in BIVARIATE:
        s1, s2 = states[0].data, states[1].data
        s11, s22 = states[2].data, states[3].data
        s12, cnt = states[4].data, states[5].data
        n = cnt.astype(jnp.float64)
        nn = jnp.maximum(n, 1.0)
        # raw-sum forms (reference: CovarianceState.getCovariance etc)
        co_m = s12 - s1 * s2 / nn  # n * covar_pop
        v1_m = jnp.maximum(s11 - s1 * s1 / nn, 0.0)
        v2_m = jnp.maximum(s22 - s2 * s2 / nn, 0.0)
        if name == "covar_pop":
            return Column(co_m / nn, T.DOUBLE, cnt > 0)
        if name == "covar_samp":
            return Column(co_m / jnp.maximum(n - 1.0, 1.0), T.DOUBLE, cnt > 1)
        if name == "corr":
            denom = jnp.sqrt(v1_m * v2_m)
            ok = jnp.logical_and(cnt > 1, denom > 0)
            return Column(co_m / jnp.where(ok, denom, 1.0), T.DOUBLE, ok)
        if name == "regr_slope":
            ok = jnp.logical_and(cnt > 1, v2_m > 0)
            return Column(co_m / jnp.where(ok, v2_m, 1.0), T.DOUBLE, ok)
        # regr_intercept = (sum_y - slope * sum_x) / n
        ok = jnp.logical_and(cnt > 1, v2_m > 0)
        slope = co_m / jnp.where(ok, v2_m, 1.0)
        return Column((s1 - slope * s2) / nn, T.DOUBLE, ok)
    if name in MOMENT:
        s, sq, cnt = states[0].data, states[1].data, states[2].data
        n = cnt.astype(jnp.float64)
        m2 = sq - jnp.where(cnt > 0, s * s / jnp.maximum(n, 1.0), 0.0)
        m2 = jnp.maximum(m2, 0.0)  # guard tiny negative rounding residue
        if name in ("var_pop", "stddev_pop"):
            var = m2 / jnp.maximum(n, 1.0)
            valid = cnt > 0
        else:
            var = m2 / jnp.maximum(n - 1.0, 1.0)
            valid = cnt > 1
        out = jnp.sqrt(var) if name.startswith("stddev") else var
        return Column(out, T.DOUBLE, valid)
    value, cnt = states[0], states[1]
    nonempty = cnt.data > 0
    valid = nonempty
    if name == "avg":
        if isinstance(spec.out_type, T.DecimalType) and value.data.ndim == 2:
            # Int128 sum state / count (reference: DecimalAverageAggregation,
            # divide via the schoolbook limb division in types/int128) —
            # count is data-dependent, so divide limb-wise by folding the
            # divisor in via float seeding is not exact; instead use the
            # exact path: q = divmod by count done in two 63-bit halves.
            from trino_tpu.types import int128 as i128

            h = value.data[:, 0]
            l = value.data[:, 1]
            den = jnp.where(nonempty, cnt.data, 1)
            qh, ql, r = i128.divmod128_by_vec(h, l, den)
            half = jnp.where(2 * jnp.abs(r) >= den, 1, 0)
            neg = h < 0
            bump = jnp.where(neg, -half, half)
            qh2, ql2 = i128.add128(qh, ql, bump >> 63, bump)
            if spec.out_type.is_long:
                data = jnp.stack([qh2, ql2], axis=-1)
            else:
                data = ql2  # avg of short input fits the short result
        elif isinstance(spec.out_type, T.DecimalType):
            num = value.data
            den = jnp.where(nonempty, cnt.data, 1)
            sign = jnp.sign(num)
            q = jnp.abs(num) // den
            r = jnp.abs(num) - q * den
            data = sign * (q + jnp.where(2 * r >= den, 1, 0))
        else:
            data = value.data.astype(jnp.float64) / jnp.where(nonempty, cnt.data, 1)
        return Column(data.astype(spec.out_type.np_dtype), spec.out_type, valid)
    # sum/min/max/any_value/bool_*
    data = value.data
    if data.ndim == 2 and isinstance(spec.out_type, T.DecimalType):
        if not spec.out_type.is_long:
            # caller declared a short result: values are asserted to fit,
            # so the low limb carries them exactly
            data = data[:, 1]
        elif (
            isinstance(value.type, T.DecimalType)
            and value.type.scale != spec.out_type.scale
        ):
            from trino_tpu.types import int128 as i128

            h, l = i128.rescale128(
                data[:, 0], data[:, 1], value.type.scale, spec.out_type.scale
            )
            data = jnp.stack([h, l], axis=-1)
    return Column(
        data.astype(spec.out_type.np_dtype),
        spec.out_type,
        valid,
        states[0].dictionary,
    )


def _logical_double(d, t: T.Type):
    """Raw device values -> logical float64 (decimal cents get descaled)."""
    out = d.astype(jnp.float64)
    if isinstance(t, T.DecimalType) and t.scale:
        out = out / (10.0 ** t.scale)
    return out


def _masked_reduce(data, valid, kind: str):
    """Whole-column null-skipping reduction to a scalar (global aggregation)."""
    from trino_tpu.ops.common import _max_sentinel, _min_sentinel

    if kind in ("count", "count_star"):
        return jnp.sum(valid, dtype=jnp.int64)
    if kind == "sum":
        return jnp.sum(jnp.where(valid, data, 0))
    if kind == "min":
        return jnp.min(jnp.where(valid, data, _max_sentinel(data.dtype)))
    if kind == "max":
        return jnp.max(jnp.where(valid, data, _min_sentinel(data.dtype)))
    if kind == "any":
        idx = jnp.argmax(valid)
        return data[idx]
    raise ValueError(kind)


def _pad_device(batch: Batch, cap: int) -> Batch:
    n = batch.capacity
    if n == cap:
        return batch
    pad = cap - n
    cols = []
    for c in batch.columns:
        if c.data.ndim > 1:  # array/map columns: pad rows, keep width
            data = jnp.concatenate(
                [c.data, jnp.zeros((pad, c.data.shape[1]), dtype=c.data.dtype)]
            )
        else:
            data = jnp.concatenate([c.data, jnp.zeros(pad, dtype=c.data.dtype)])
        valid = (
            None
            if c.valid is None
            else jnp.concatenate([c.valid, jnp.zeros(pad, dtype=bool)])
        )
        lengths = (
            None
            if c.lengths is None
            else jnp.concatenate([c.lengths, jnp.zeros(pad, jnp.int32)])
        )
        cols.append(Column(data, c.type, valid, c.dictionary, lengths))
    mask = jnp.concatenate([batch.mask(), jnp.zeros(pad, dtype=bool)])
    return Batch(cols, mask)


class MarkDistinctOperator:
    """Appends a boolean column that is True on the first live occurrence of
    each distinct key combination (reference: operator/MarkDistinctOperator
    .java + MarkDistinctHash).  TPU substitution: multi-key sort + key-change
    flags scattered back to row order — one static-shape program, no hash
    table."""

    def __init__(self, key_channels: Sequence[int]):
        self.key_channels = list(key_channels)
        self._acc: list[Batch] = []
        key = ("mark_distinct", tuple(self.key_channels))
        if key not in _STEP_CACHE:
            _STEP_CACHE[key] = jit_program(self._mark_step, "agg_mark")
        self._step = _STEP_CACHE[key]

    def _mark_step(self, batch: Batch) -> Batch:
        cap = batch.capacity
        perm = multi_key_sort_perm(
            batch, [SortKey(ch) for ch in self.key_channels]
        )
        _, _, new_group = group_ids_from_sorted(batch, perm, self.key_channels)
        pos = jnp.arange(cap, dtype=jnp.int64)
        inv = jnp.zeros(cap, dtype=jnp.int64).at[perm].set(pos)
        mark = jnp.take(new_group, inv, mode="clip")
        cols = list(batch.columns) + [Column(mark, T.BOOLEAN, None)]
        return Batch(cols, batch.row_mask)

    def process(self, stream):
        for b in stream:
            self._acc.append(b)
        if not self._acc:
            return
        big = self._acc[0] if len(self._acc) == 1 else concat_batches(self._acc)
        big = _pad_device(big, next_pow2(big.capacity, floor=1))
        yield self._step(big)


class AggregationOperator:
    def __init__(
        self,
        group_channels: Sequence[int],
        aggregates: Sequence[AggSpec],
        input_types: Sequence[T.Type],
        mode: str = "single",  # single | partial | final | merge
        streaming: bool = False,
        fold_every: Optional[int] = None,
        memory_ctx=None,
        use_pallas: bool = False,
        pre_step=None,
        pre_key=None,
        pre_jit=None,
    ):
        # merge: states in -> states out (used to combine partial outputs)
        assert mode in ("single", "partial", "final", "merge")
        if group_channels and any(
            s.name in ("approx_distinct", "approx_percentile")
            for s in aggregates
        ):
            # grouped sketches would need [groups, HLL_M] register state;
            # the planner rewrites grouped approx_distinct to exact DISTINCT
            # count instead, so this is unreachable from SQL
            raise NotImplementedError("grouped approx_distinct")
        self.group_channels = list(group_channels)
        self.aggregates = list(aggregates)
        self.input_types = list(input_types)
        self.mode = mode
        self.streaming = streaming
        self.fold_every = fold_every if fold_every is not None else self.FOLD_EVERY
        self.memory_ctx = memory_ctx
        #: opt-in Pallas MXU kernel for eligible direct-path aggregations
        #: (ops/pallas_agg.py); float32 accumulation, so restricted to
        #: DOUBLE/REAL sums + counts where f32 matmul precision is acceptable
        self.use_pallas = use_pallas
        #: fused upstream projection: applied INSIDE the jitted reduce step
        #: so projection outputs (e.g. decimal products) never round-trip
        #: through memory between the project and the partial aggregation
        self._pre = pre_step
        self._pre_key = pre_key
        #: jitted standalone projection (for paths that must materialize the
        #: projected batch OUTSIDE the fused reduce, e.g. the positional
        #: group path whose eligibility reads concrete key stats)
        self._pre_jit = pre_jit
        self._acc: list[Batch] = []
        self._per_batch: Optional["AggregationOperator"] = None
        self._unfused_twin: Optional["AggregationOperator"] = None
        key = (
            tuple(self.group_channels),
            tuple(self.aggregates),
            tuple(t.name for t in self.input_types),
            mode,
            use_pallas,
            pre_key,
        )
        cached = _STEP_CACHE.get(key)
        if cached is None:
            cached = jit_program(
                self._reduce_step, "agg_reduce", static_argnames=("out_cap",)
            )
            _STEP_CACHE[key] = cached
        self._step = cached

    # -- the jitted kernel ---------------------------------------------------

    #: group-domain cap for the sort-free direct path (positional segments)
    DIRECT_GROUP_LIMIT = 4096

    #: group-domain cap for the range-positional path (min/max-offset mixed
    #: radix).  Segment ops at 16M slots are ~0.2s-class; beyond that the
    #: sort path (or, later, aggregation waves) takes over.
    POSITIONAL_LIMIT = 1 << 24

    def _direct_group_info(self, batch: Batch, src_channels=None):
        """(sizes, prod) when every group key is a small-domain code column
        (dictionary or boolean) — the BigintGroupByHash analog: group id is
        the mixed-radix code index, no sort needed (reference:
        operator/BigintGroupByHash.java's dense small-domain fast path).

        `src_channels`: when the input projection is FUSED into this
        operator, the group keys' pre-projection channels in the raw batch
        (group projections are identity InputRefs in that case)."""
        sizes = []
        chans = src_channels if src_channels is not None else self.group_channels
        for ch in chans:
            c = batch.columns[ch]
            if c.dictionary is not None:
                n = len(c.dictionary.values)
            elif c.type is T.BOOLEAN:
                n = 2
            else:
                return None
            sizes.append(n + 1)  # one extra slot for NULL
        prod = 1
        for s in sizes:
            prod *= s
        if not 0 < prod <= self.DIRECT_GROUP_LIMIT:
            return None
        return sizes, prod

    def _direct_reduce(self, batch: Batch, sizes, prod: int) -> Batch:
        gch = self.group_channels
        cap = batch.capacity
        live = batch.mask()
        gid = jnp.zeros(cap, dtype=jnp.int64)
        for ch, size in zip(gch, sizes):
            c = batch.columns[ch]
            code = c.data.astype(jnp.int64)
            if c.valid is not None:
                code = jnp.where(c.valid, code, size - 1)
            gid = gid * size + jnp.clip(code, 0, size - 1)
        gid = jnp.where(live, gid, prod)
        nseg = prod + 1
        occupancy = segment_reduce(None, gid, nseg, "count", valid=live)[:prod]
        out_live = occupancy > 0
        # decode positional slot -> group key codes
        idx = jnp.arange(prod, dtype=jnp.int64)
        divs = []
        d = 1
        for size in reversed(sizes):
            divs.append(d)
            d *= size
        divs.reverse()
        cols: list[Column] = []
        for (ch, size), div in zip(zip(gch, sizes), divs):
            c = batch.columns[ch]
            code = (idx // div) % size
            valid = None
            if c.valid is not None:
                valid = code < (size - 1)
            cols.append(
                Column(code.astype(c.data.dtype), c.type, valid, c.dictionary)
            )
        states = None
        if self.use_pallas:
            states = self._pallas_direct_sums(batch, live, gid, prod)
        if states is not None:
            _note_agg_path("pallas")
        else:
            states = self._onehot_direct_sums(batch, live, gid, prod)
            if states is not None:
                _note_agg_path("onehot")
        if states is not None:
            for spec, state_cols in zip(self.aggregates, states):
                if self.mode == "partial":
                    cols.extend(state_cols)
                else:
                    cols.append(_finalize(spec, state_cols))
            return Batch(cols, out_live)
        _note_agg_path("segmented")
        for spec in self.aggregates:
            state_cols = self._reduce_one(batch, spec, None, live, gid, nseg, prod)
            if self.mode in ("partial", "merge"):
                cols.extend(state_cols)
            else:
                cols.append(_finalize(spec, state_cols))
        return Batch(cols, out_live)

    #: one-hot path bounds: groups (one-hot width) and rows (32-bit chunk
    #: sums must stay inside i64: 2**32 chunks * 2**30 rows = 2**62).  The
    #: row bound was 2**21 while the sums rode f64 (2**53 mantissa); a
    #: mesh worker's stacked scan batch (SF1 lineitem on one chip: 2**23
    #: rows) then fell to the per-aggregate "segmented" reductions, which
    #: were scatter-adds then and are dense too since `segment_reduce`
    #: chooses (they still pay `_sum128`'s probe per aggregate)
    ONEHOT_GROUP_LIMIT = 32
    ONEHOT_ROW_LIMIT = 1 << 30

    def _onehot_direct_sums(self, batch: Batch, live, gid, prod: int):
        """EXACT one-hot aggregation (default on the direct path off-CPU):
        every sum/count is a masked reduction of a [cap] plane against the
        [cap, G] one-hot of the group ids, fused by XLA into one pass over
        the rows — instead of K scatter-adds, which the TPU has no hardware
        for and serialises at 74-127 ns a row (Q1 SF1 warm on a v5e: 4.49 s
        that way).  The occupancy count in front of it (`_direct_reduce`)
        is the same kind of reduction, through `segment_reduce`.

        It is exact: integer inputs split into 32-bit chunk planes summed
        in int64, and the chunks recombine into i64/i128 with carries; only
        DOUBLE/REAL sums accumulate in floating point.  Returns per-spec
        primitive STATE columns (same layout as _reduce_one) or None when
        ineligible.

        Reference role: the grouped-sum loop of operator/aggregation/
        DecimalSumAggregation + GroupedAccumulator, reshaped for hardware
        that prefers wide vector reductions over row-at-a-time
        accumulation."""
        cap = batch.capacity
        if prod > self.ONEHOT_GROUP_LIMIT or cap > self.ONEHOT_ROW_LIMIT:
            return None
        if self.mode not in ("single", "partial"):
            return None
        if not self.aggregates:
            return None  # pure dedupe (e.g. DISTINCT pre-aggregation)
        # the shared one-hot over chunk planes is the accelerator
        # formulation; CPU's scalar pipelines prefer one reduction per
        # aggregate (the "segmented" path)
        if jax.default_backend() == "cpu" and not getattr(
            self, "force_onehot", False
        ):
            return None
        for spec in self.aggregates:
            if spec.name not in ("sum", "avg", "count", "count_star"):
                return None
            if spec.name in ("sum", "avg"):
                t = self.input_types[spec.arg]
                if not (
                    isinstance(t, T.DecimalType)
                    or t.name
                    in ("tinyint", "smallint", "integer", "bigint", "double", "real")
                ):
                    return None

        m32 = jnp.int64(0xFFFFFFFF)
        planes = []  # [cap] arrays: int64 (counts, chunks) or f64 (doubles)
        plan = []  # per spec: list of (prim_kind, chunk_layout, plane_idx..)

        def _valid_plane(col):
            v = live
            if col is not None and col.valid is not None:
                v = jnp.logical_and(v, col.valid)
            return v

        for spec in self.aggregates:
            prims = []
            if spec.name == "count_star":
                prims.append(("count", "count", (len(planes),)))
                planes.append(live.astype(jnp.int64))
            elif spec.name == "count":
                col = batch.columns[spec.arg]
                v = _valid_plane(col)
                prims.append(("count", "count", (len(planes),)))
                planes.append(v.astype(jnp.int64))
            else:  # sum / avg -> (sum, count) primitive states
                col = batch.columns[spec.arg]
                v = _valid_plane(col)
                t = self.input_types[spec.arg]
                st = _state_types(spec, self.input_types)[0]
                if t.name in ("double", "real"):
                    d = jnp.where(v, col.data.astype(jnp.float64), 0.0)
                    prims.append(("sum", "f64", (len(planes),)))
                    planes.append(d)
                elif col.data.ndim == 2:  # long decimal input
                    h = jnp.where(v, col.data[:, 0], 0)
                    l = jnp.where(v, col.data[:, 1], 0)
                    i0 = len(planes)
                    planes.extend(
                        [
                            l & m32,
                            (l >> 32) & m32,
                            h & m32,
                            h >> 32,
                        ]
                    )
                    prims.append(("sum", "i128", (i0, i0 + 1, i0 + 2, i0 + 3)))
                else:
                    d = jnp.where(v, jnp.asarray(col.data, jnp.int64), 0)
                    i0 = len(planes)
                    planes.extend(
                        [
                            d & m32,
                            d >> 32,  # signed top chunk
                        ]
                    )
                    kind = (
                        "i128"
                        if isinstance(st, T.DecimalType) and st.is_long
                        else "i64"
                    )
                    prims.append(("sum", kind + "_2", (i0, i0 + 1)))
                prims.append(("count", "count", (len(planes),)))
                planes.append(v.astype(jnp.int64))
            plan.append((spec, prims))

        S = _onehot_plane_sums(gid, live, planes, prod)  # K x [G]

        from trino_tpu.types import int128 as i128

        out_states: list = []
        for spec, prims in plan:
            state_cols = []
            sts = _state_types(spec, self.input_types)
            for (kind, layout, idx), st in zip(prims, sts):
                if layout == "count":
                    state_cols.append(Column(S[idx[0]], T.BIGINT))
                elif layout == "f64":
                    state_cols.append(Column(S[idx[0]], st))
                elif layout == "i64_2":
                    state_cols.append(
                        Column((S[idx[1]] << 32) + S[idx[0]], st)
                    )
                elif layout == "i128_2":
                    hi, lo = i128.recombine2(S[idx[0]], S[idx[1]])
                    state_cols.append(
                        Column(jnp.stack([hi, lo], axis=-1), st)
                    )
                else:  # i128 (4 chunk planes)
                    hi, lo = i128.recombine4(*(S[i] for i in idx))
                    state_cols.append(
                        Column(jnp.stack([hi, lo], axis=-1), st)
                    )
            out_states.append(state_cols)
        return out_states

    def _pallas_direct_sums(self, batch: Batch, live, gid, prod: int):
        """MXU one-hot-matmul fast path (ops/pallas_agg.py) when every
        aggregate is a float sum/avg or a count; returns per-spec primitive
        STATE columns (same layout as `_onehot_direct_sums`) or None when
        ineligible.  Off the `tpu` platform the kernel runs in interpret
        mode (CPU tests); on it, a kernel Mosaic refuses raises — there is
        no giving way to the XLA path."""
        if self.mode not in ("single", "partial"):
            return None
        for spec in self.aggregates:
            if spec.name in ("count_star", "count"):
                continue
            if spec.name in ("sum", "avg") and spec.arg is not None:
                if self.input_types[spec.arg].name in ("double", "real"):
                    continue
            return None
        cap = batch.capacity
        from trino_tpu.ops.pallas_agg import _BLOCK, grouped_sums_pallas

        block = min(_BLOCK, cap)
        # f32 accumulation: counts stay exact only below 2^24 increments, so
        # cap the eligible batch size (beyond it the sort-based path runs)
        if cap % block != 0 or prod > 512 or cap > (1 << 24):
            return None

        # value matrix: one column per needed quantity
        mats = []
        plan = []  # (spec, count column, value column or None)
        ones = None
        for spec in self.aggregates:
            if spec.name == "count_star":
                if ones is None:
                    ones = len(mats)
                    mats.append(jnp.ones(cap, jnp.float32))
                plan.append((spec, ones, None))
                continue
            c = batch.columns[spec.arg]
            v = c.valid_mask() if c.valid is not None else None
            cnt_col = len(mats)
            mats.append(
                (v if v is not None else jnp.ones(cap, bool)).astype(jnp.float32)
            )
            if spec.name == "count":
                plan.append((spec, cnt_col, None))
                continue
            data = c.data.astype(jnp.float32)
            if v is not None:
                data = jnp.where(v, data, 0.0)
            plan.append((spec, cnt_col, len(mats)))
            mats.append(data)
        sums = grouped_sums_pallas(
            gid.astype(jnp.int32),
            live,
            jnp.stack(mats, axis=1),
            n_groups=prod,
            interpret=jax.default_backend() != "tpu",
        )  # [prod, len(mats)]
        out = []
        for spec, cnt_col, val_col in plan:
            count = Column(sums[:, cnt_col].astype(jnp.int64), T.BIGINT)
            if val_col is None:
                out.append([count])
            else:
                st = _state_types(spec, self.input_types)[0]
                out.append(
                    [Column(sums[:, val_col].astype(jnp.float64), st), count]
                )
        return out

    # -- range-positional (sort-free) path -----------------------------------

    def _positional_static_eligible(self, batch: Batch) -> bool:
        """Static (type-level) eligibility for the range-positional path:
        every group key is an int-family scalar (ints, dates, decimals,
        dictionary codes, bools) — the generalized BigintGroupByHash dense
        path (reference: operator/BigintGroupByHash.java), with the dense
        domain discovered from data min/max instead of assumed."""
        if not self.group_channels:
            return False
        if any(s.name in HOLISTIC_AGGS for s in self.aggregates):
            # holistic aggregates need the sorted numbering (percentile,
            # collect) or joint key/value selection (min_by/max_by)
            return False
        for ch in self.group_channels:
            col = batch.columns[ch]
            if col.lengths is not None:
                return False
            if col.data.ndim > 1:
                return False  # long-decimal limb planes: sort path handles
            dt = col.data.dtype
            if not (jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_):
                return False
        return True

    def _key_stats(self, batch: Batch):
        """Jitted per-key (min, max) over live, non-null key values, and
        whether the live rows are non-decreasing in the group keys, taken
        lexicographically with NULL last: the order of `_range_gid` over
        the domain these same bounds span.  (Meaningful only where that
        domain fits POSITIONAL_LIMIT; `_positional_try` reads it nowhere
        else.)"""
        key = ("keystats", tuple(self.group_channels))
        step = _STEP_CACHE.get(key)
        if step is None:
            chans = tuple(self.group_channels)

            def stats(batch: Batch):
                live = batch.mask()
                mins, maxs, sizes = [], [], []
                for ch in chans:
                    col = batch.columns[ch]
                    d = col.data.astype(jnp.int64)
                    v = live
                    if col.valid is not None:
                        v = jnp.logical_and(v, col.valid)
                    big = jnp.iinfo(jnp.int64).max
                    lo = jnp.min(jnp.where(v, d, big))
                    hi = jnp.max(jnp.where(v, d, -big))
                    mins.append(lo)
                    maxs.append(hi)
                    sizes.append(
                        jnp.where(hi >= lo, hi - lo + 1, 0)
                        + (0 if col.valid is None else 1)
                    )
                mins, maxs = jnp.stack(mins), jnp.stack(maxs)
                gid = jnp.where(
                    live, _range_gid(batch, chans, mins, jnp.stack(sizes)), -1
                )
                before = jnp.concatenate(
                    [jnp.full(1, -1, jnp.int32), running_max(gid)[:-1]]
                )
                return mins, maxs, jnp.all(jnp.where(live, gid >= before, True))

            step = jit_program(stats, "agg_key_stats")
            _STEP_CACHE[key] = step
        return step(batch)

    def _positional_try(self, batch: Batch) -> Optional[Batch]:
        """Sort-free grouped reduction when the key domain is dense enough:
        gid = mixed-radix positional code from per-key (min, size), group
        values decoded back from it.  One host sync for the key stats and
        the rows' order; sizes/mins stay traced so data changes do not
        retrace."""
        import numpy as np

        if not self._positional_static_eligible(batch):
            return None
        mins, maxs, ordered = host_pull(self._key_stats(batch), "group_stats")
        prod = 1
        sizes = []
        for i, ch in enumerate(self.group_channels):
            nullable = batch.columns[ch].valid is not None
            size = int(maxs[i]) - int(mins[i]) + 1
            if size < 0:
                size = 0  # empty/all-null key: only the null slot remains
            size += 1 if nullable else 0
            if size <= 0:
                return None
            sizes.append(size)
            prod *= size
            if prod > self.POSITIONAL_LIMIT:
                return None
        # a domain much larger than the input wastes O(prod) segment slots
        if prod > max(1 << 16, 8 * batch.capacity):
            return None
        nseg = next_pow2(prod, floor=16)
        # how the groups reduce is chosen from the (static) slot count and
        # the order the rows were observed in, nothing else: few slots
        # dense; many as runs of the group id, sorted first where the rows
        # did not arrive in its order
        if nseg + 1 <= DENSE_SEGMENT_LIMIT:
            form = "dense"
        else:
            form = "runs" if ordered else "sorted_runs"
        # (a program a form: a launch's `path=` is what its program chose
        # while tracing, and must name the one form that launch ran)
        key = (
            "range",
            tuple(self.group_channels),
            tuple(self.aggregates),
            tuple(t.name for t in self.input_types),
            self.mode,
            form,
        )
        step = _STEP_CACHE.get(key)
        if step is None:
            step = jit_program(
                self._range_step, "agg_range", static_argnames=("out_cap", "form")
            )
            _STEP_CACHE[key] = step
        out = step(
            batch,
            jnp.asarray(mins),
            jnp.asarray(np.asarray(sizes, dtype=np.int64)),
            out_cap=int(nseg),
            form=form,
        )
        # the dense form's output is sparse (slot = group id, occupancy-
        # masked), the run forms' packed; compact when the live groups are
        # far below the domain so downstream sorts stay small
        ng = out.num_rows_host()
        cc = next_pow2(max(ng, 1), floor=16)
        if cc * 2 <= nseg:
            out = COMPACT(out, out_capacity=cc)
        return out

    def _range_step(
        self, batch: Batch, mins, sizes, out_cap: int, form: str
    ) -> Batch:
        """Grouped reduction by the positional code `_range_gid`, `form`
        (static) as `_positional_try` chose it.  `dense`: slot = code, the
        segment reductions dense masked passes.  `runs`: the rows arrive in
        code order, so a group is a run of rows and the k-th group the k-th
        run (`ops/common.Runs`).  `sorted_runs`: one stable sort of the
        32-bit code with the row number as payload, each reduced plane
        gathered through it, then the same.  No form scatters."""
        gch = self.group_channels
        cap = batch.capacity
        assert out_cap <= self.POSITIONAL_LIMIT and cap < (1 << 31), (out_cap, cap)
        live = batch.mask()
        gid = jnp.where(live, _range_gid(batch, gch, mins, sizes), out_cap)
        _note_agg_path("positional")
        perm = None
        if form == "dense":
            nseg = out_cap + 1
            segments = gid
            occupancy = segment_reduce(None, gid, nseg, "count", valid=live)
            out_live = occupancy[:out_cap] > 0
            code = jnp.arange(out_cap, dtype=jnp.int64)
        else:
            _note_agg_path(form)
            if form == "sorted_runs":
                gid, perm = jax.lax.sort(
                    (gid.astype(jnp.uint32), jnp.arange(cap, dtype=jnp.int32)),
                    num_keys=1,
                    is_stable=True,
                )
                live = gid < out_cap
            nseg = out_cap
            segments = run_ids(gid, live, out_cap)
            out_live = segments.live
            code = jnp.take(segments.gid, segments.src, mode="clip").astype(
                jnp.int64
            )
        # decode group code -> group key values (traced div/mod chain)
        sizes_list = [sizes[i] for i in range(len(gch))]
        divs = []
        d = jnp.ones((), dtype=jnp.int64)
        for size in reversed(sizes_list):
            divs.append(d)
            d = d * size
        divs.reverse()
        cols: list[Column] = []
        for i, ch in enumerate(gch):
            col = batch.columns[ch]
            digit = (code // divs[i]) % sizes_list[i]
            valid = None
            if col.valid is not None:
                valid = digit < (sizes_list[i] - 1)
            data = (digit + mins[i]).astype(col.data.dtype)
            cols.append(Column(data, col.type, valid, col.dictionary))
        for spec in self.aggregates:
            state_cols = self._reduce_one(
                batch, spec, perm, live, segments, nseg, out_cap
            )
            if self.mode in ("partial", "merge"):
                cols.extend(state_cols)
            else:
                cols.append(_finalize(spec, state_cols))
        return Batch(cols, out_live)

    def _reduce_full(self, big: Batch) -> Batch:
        """One-shot reduction of a batch: compact away dead slack first
        (join outputs / filtered feeds can be mostly dead), then the
        positional path if the key domain allows, else the sorted step."""
        n = big.num_rows_host()
        cap = next_pow2(max(n, 1), floor=1)
        if cap < big.capacity:
            big = COMPACT(big, out_capacity=cap)
        else:
            cap = next_pow2(big.capacity, floor=1)
            big = _pad_device(big, cap)
        # collect aggregates (array_agg/map_agg) need a data-dependent padded
        # width: run the step EAGERLY so the width sync is legal
        if any(s.name in COLLECT_AGGS for s in self.aggregates):
            return self._reduce_step(big, out_cap=cap)
        # the in-jit small-domain direct path needs no host sync; prefer it
        # when statically eligible (dict/bool keys).  A fused projection
        # (self._pre) means `big` is RAW input: the positional fallback
        # would inspect pre-projection channels, so skip it — _step applies
        # the projection inside its own trace.
        if (
            self._pre is None
            and self.group_channels
            and self._direct_group_info(big) is None
        ):
            out = self._positional_try(big)
            if out is not None:
                return out
        return self._step(big, out_cap=cap)

    def _reduce_step(self, batch: Batch, out_cap: int) -> Batch:
        if self._pre is not None:
            batch = self._pre(batch)
        gch = self.group_channels
        if not gch:
            return self._global_reduce(batch)
        direct = None
        if not any(
            s.name in HOLISTIC_AGGS
            for s in self.aggregates
        ):
            # holistic group ids must come from the sort-based numbering
            direct = self._direct_group_info(batch)
        if direct is not None:
            return self._direct_reduce(batch, *direct)
        _note_agg_path("sort")
        perm = multi_key_sort_perm(batch, [SortKey(ch) for ch in gch])
        gid, ngroups, new_group = group_ids_from_sorted(batch, perm, gch)
        live = jnp.take(batch.mask(), perm, mode="clip")
        gid_c = jnp.minimum(gid, out_cap)
        nseg = out_cap + 1
        out_live = jnp.arange(out_cap, dtype=jnp.int64) < ngroups
        cols: list[Column] = []
        # group key columns: value at each group's first row
        first_idx = jnp.where(new_group, gid_c, out_cap)
        for ch in gch:
            col = batch.columns[ch]
            d = jnp.take(col.data, perm, axis=0, mode="clip")
            if d.ndim > 1:  # long-decimal limb planes: scatter rows
                key_out = (
                    jnp.zeros((nseg,) + d.shape[1:], dtype=col.data.dtype)
                    .at[first_idx]
                    .set(d, mode="drop")[:out_cap]
                )
            else:
                key_out = (
                    jnp.zeros(nseg, dtype=col.data.dtype)
                    .at[first_idx]
                    .set(d, mode="drop")[:out_cap]
                )
            valid = None
            if col.valid is not None:
                v = jnp.take(col.valid, perm, mode="clip")
                valid = (
                    jnp.zeros(nseg, dtype=bool)
                    .at[first_idx]
                    .set(v, mode="drop")[:out_cap]
                )
            cols.append(Column(key_out, col.type, valid, col.dictionary))
        # aggregate states/values
        for spec in self.aggregates:
            if spec.name in HOLISTIC_AGGS:
                if self.mode != "single":
                    raise NotImplementedError(
                        f"{spec.name} requires single-stage aggregation"
                    )
                if spec.name == "percentile":
                    cols.append(self._percentile_one(batch, spec, out_cap))
                elif spec.name == "listagg":
                    cols.append(self._listagg_one(batch, spec, out_cap))
                elif spec.name in ("min_by", "max_by"):
                    cols.append(
                        self._minmax_by_one(
                            batch, spec, perm, live, gid_c, nseg, out_cap
                        )
                    )
                else:
                    cols.append(
                        self._collect_one(batch, spec, perm, live, gid_c, nseg, out_cap)
                    )
                continue
            state_cols = self._reduce_one(
                batch, spec, perm, live, gid_c, nseg, out_cap
            )
            if self.mode in ("partial", "merge"):
                cols.extend(state_cols)
            else:
                cols.append(_finalize(spec, state_cols))
        return Batch(cols, out_live)

    def _collect_one(
        self, batch: Batch, spec: AggSpec, perm, live, gid_c, nseg, out_cap
    ) -> Column:
        """array_agg / map_agg: scatter each group's run into a padded
        rectangular array (reference: operator/aggregation/
        ArrayAggregationFunction + MapAggAggregationFunction group state).

        Runs EAGERLY (outside jit): the padded width K is the max group
        size, a data-dependent shape that costs one host sync.  NULL inputs
        are skipped — the rectangular layout tracks nulls per-array, not
        per-element (documented deviation; the reference keeps them)."""
        import numpy as np

        cap = batch.capacity
        col = batch.columns[spec.arg]
        if (
            spec.name == "array_agg"
            and spec.arg2 is not None
            and spec.param is not None
        ):
            # array_agg(x ORDER BY k): re-sort by (group keys, k) so the
            # scatter positions below follow the requested element order
            # (the _percentile_one re-sort pattern); group numbering is
            # unchanged because the group keys stay most significant
            asc, nf = spec.param
            keys = [SortKey(ch) for ch in self.group_channels] + [
                SortKey(spec.arg2, asc, nf)
            ]
            perm = multi_key_sort_perm(batch, keys)
            live = jnp.take(batch.mask(), perm, mode="clip")
            if self.group_channels:
                gid, _, _ = group_ids_from_sorted(
                    batch, perm, self.group_channels
                )
                gid_c = gid
            else:
                gid_c = jnp.zeros(cap, dtype=jnp.int64)
        d = jnp.take(col.data, perm, axis=0, mode="clip")
        varg = live
        if col.valid is not None:
            varg = jnp.logical_and(varg, jnp.take(col.valid, perm, mode="clip"))
        vcol = None
        dictionary = col.dictionary
        if spec.name == "map_agg":
            vcol = batch.columns[spec.arg2]
            vd = jnp.take(vcol.data, perm, axis=0, mode="clip")
            if vcol.valid is not None:
                varg = jnp.logical_and(
                    varg, jnp.take(vcol.valid, perm, mode="clip")
                )
            if col.dictionary is not None and vcol.dictionary is not None:
                from trino_tpu.columnar.dictionary import union_many

                dictionary, (tk, tv) = union_many(
                    [col.dictionary, vcol.dictionary]
                )
                if tk is not None:
                    d = jnp.take(jnp.asarray(tk), jnp.asarray(d, jnp.int32), mode="clip")
                if tv is not None:
                    vd = jnp.take(jnp.asarray(tv), jnp.asarray(vd, jnp.int32), mode="clip")
            elif vcol.dictionary is not None:
                dictionary = vcol.dictionary
        if jnp.ndim(d) > 1:
            raise NotImplementedError(
                f"{spec.name} over a long-decimal argument "
                "(cast to decimal(18,s) or double first)"
            )
        # within-group rank over kept rows
        pos_in_group, counts = _group_ranks(varg, gid_c, cap, nseg)
        kmax = int(host_pull(jnp.max(counts[:out_cap]), "capacity"))  # the one host sync
        k = next_pow2(max(kmax, 1), floor=1)
        scatter_g = jnp.where(varg, gid_c, nseg)  # drop non-kept rows
        scatter_p = jnp.clip(pos_in_group, 0, k - 1)
        lengths = counts[:out_cap].astype(jnp.int32)
        if spec.name == "array_agg":
            et = spec.out_type.element
            out = (
                jnp.zeros((nseg + 1, k), dtype=et.np_dtype)
                .at[scatter_g, scatter_p]
                .set(jnp.asarray(d, et.np_dtype), mode="drop")
            )
            return Column(
                out[:out_cap], spec.out_type, None, dictionary, lengths
            )
        mt = spec.out_type  # MapType: packed [out_cap, 2k]
        dt = mt.np_dtype
        keys = (
            jnp.zeros((nseg + 1, k), dtype=dt)
            .at[scatter_g, scatter_p]
            .set(jnp.asarray(d, dt), mode="drop")
        )
        vals = (
            jnp.zeros((nseg + 1, k), dtype=dt)
            .at[scatter_g, scatter_p]
            .set(jnp.asarray(vd, dt), mode="drop")
        )
        packed = jnp.concatenate([keys[:out_cap], vals[:out_cap]], axis=1)
        return Column(packed, mt, None, dictionary, lengths)

    def _listagg_one(self, batch: Batch, spec: AggSpec, out_cap: int) -> Column:
        """listagg(value, sep) WITHIN GROUP (ORDER BY k) — reference:
        operator/aggregation/listagg/.  Eager: rows sort by
        (group keys, order key) on device; the per-group string join is
        host work by nature (strings live in dictionaries)."""
        import numpy as np

        from trino_tpu.columnar.dictionary import StringDictionary

        gch = self.group_channels
        col = batch.columns[spec.arg]
        if col.dictionary is None:
            raise TypeError("listagg requires a varchar argument")
        sep, asc, nf = (
            spec.param
            if isinstance(spec.param, tuple)
            else (spec.param or "", True, False)
        )
        keys = [SortKey(ch) for ch in gch]
        if spec.arg2 is not None:
            keys.append(SortKey(spec.arg2, ascending=asc, nulls_first=nf))
        perm2 = multi_key_sort_perm(batch, keys)
        if gch:
            gid2, _, _ = group_ids_from_sorted(batch, perm2, gch)
            gid_h = host_pull(gid2, "dictionary")
        else:
            gid_h = np.zeros(batch.capacity, dtype=np.int64)
        live = jnp.take(batch.mask(), perm2, mode="clip")
        if col.valid is not None:
            live = jnp.logical_and(
                live, jnp.take(col.valid, perm2, mode="clip")
            )
        codes = jnp.take(col.data, perm2, mode="clip")
        live_h, codes_h = host_pull((live, codes), "dictionary")
        sep = str(sep)
        values = col.dictionary.values
        joined = [""] * out_cap
        parts: dict = {}
        for i in np.flatnonzero(live_h):
            g = int(gid_h[i])
            if g < out_cap:
                parts.setdefault(g, []).append(values[int(codes_h[i])])
        valid_out = np.zeros(out_cap, dtype=bool)
        for g, vs in parts.items():
            joined[g] = sep.join(vs)
            valid_out[g] = True
        d = StringDictionary.from_unsorted(joined)
        out_codes = d.encode(joined)
        return Column(
            np.asarray(out_codes, dtype=np.int32),
            spec.out_type,
            valid_out if not valid_out.all() else None,
            d,
        )

    def _minmax_by_n(self, batch: Batch, spec: AggSpec, nseg, out_cap) -> Column:
        """min_by/max_by(value, key, n): the values at each group's n
        extreme keys, as a padded array in key order (reference:
        MinMaxByNAggregation's TypedHeap — a sort-based engine takes the
        first n of the key-sorted run instead).  NULL keys and NULL values
        are skipped (rectangular arrays carry no per-element nulls — the
        array_agg deviation)."""
        n = int(spec.param)
        want_min = spec.name == "min_by"
        cap = batch.capacity
        kcol = batch.columns[spec.arg2]
        vcol = batch.columns[spec.arg]
        keys = [SortKey(ch) for ch in self.group_channels] + [
            SortKey(spec.arg2, want_min)
        ]
        perm = multi_key_sort_perm(batch, keys)
        live = jnp.take(batch.mask(), perm, mode="clip")
        if self.group_channels:
            gid, _, _ = group_ids_from_sorted(batch, perm, self.group_channels)
            gid_c = gid
        else:
            gid_c = jnp.zeros(cap, dtype=jnp.int64)
        varg = live
        if kcol.valid is not None:
            varg = jnp.logical_and(varg, jnp.take(kcol.valid, perm, mode="clip"))
        if vcol.valid is not None:
            varg = jnp.logical_and(varg, jnp.take(vcol.valid, perm, mode="clip"))
        pos_in_group, counts = _group_ranks(varg, gid_c, cap, nseg)
        keep = jnp.logical_and(varg, pos_in_group < n)
        scatter_g = jnp.where(keep, gid_c, nseg)
        scatter_p = jnp.clip(pos_in_group, 0, n - 1)
        vd = jnp.take(vcol.data, perm, mode="clip")
        et = spec.out_type.element
        out = (
            jnp.zeros((nseg + 1, n), dtype=et.np_dtype)
            .at[scatter_g, scatter_p]
            .set(jnp.asarray(vd, et.np_dtype), mode="drop")
        )
        lengths = jnp.minimum(counts[:out_cap], n).astype(jnp.int32)
        return Column(
            out[:out_cap], spec.out_type, None, vcol.dictionary, lengths
        )

    def _minmax_by_one(
        self, batch: Batch, spec: AggSpec, perm, live, gid_c, nseg, out_cap
    ) -> Column:
        """min_by/max_by(value, key): the VALUE at each group's extreme KEY
        (reference: MinMaxByNAggregation, N=1).  Jit-safe: extreme key via
        segment reduce, then the first row achieving it selects the value.
        Rows with NULL keys are skipped; ties pick the first sorted row."""
        if spec.param is not None:
            return self._minmax_by_n(batch, spec, nseg, out_cap)
        from trino_tpu.ops.common import _max_sentinel, _min_sentinel

        cap = batch.capacity
        vcol = batch.columns[spec.arg]
        kcol = batch.columns[spec.arg2]
        kd = jnp.take(kcol.data, perm, mode="clip")
        vkey = live
        if kcol.valid is not None:
            vkey = jnp.logical_and(vkey, jnp.take(kcol.valid, perm, mode="clip"))
        want_min = spec.name == "min_by"
        sent = (
            _max_sentinel(kd.dtype) if want_min else _min_sentinel(kd.dtype)
        )
        keyed = jnp.where(vkey, kd, sent)
        if want_min and jnp.issubdtype(kd.dtype, jnp.floating):
            # NaN orders as largest (same rule the sort path uses), so for
            # min it must only win when every key is NaN — remap to +inf
            # instead of letting segment_min propagate it
            keyed = jnp.where(jnp.isnan(keyed), jnp.inf, keyed)
        kext = segment_reduce(
            keyed, gid_c, nseg, "min" if want_min else "max"
        )
        pos = jnp.arange(cap, dtype=jnp.int64)
        kext_g = jnp.take(kext, gid_c, mode="clip")
        match = keyed == kext_g
        if jnp.issubdtype(kd.dtype, jnp.floating):
            # segment min/max propagate NaN keys; NaN != NaN would then match
            # no row and silently select a padded one
            match = jnp.logical_or(
                match, jnp.logical_and(jnp.isnan(keyed), jnp.isnan(kext_g))
            )
        at_ext = jnp.logical_and(vkey, match)
        first = segment_reduce(
            jnp.where(at_ext, pos, cap), gid_c, nseg, "min"
        )
        idx = jnp.clip(first[:out_cap], 0, cap - 1)
        vd = jnp.take(vcol.data, perm, axis=0, mode="clip")
        out = jnp.take(vd, idx, axis=0, mode="clip")
        has_key = (
            segment_reduce(None, gid_c, nseg, "count", valid=vkey)[:out_cap] > 0
        )
        valid = has_key
        if vcol.valid is not None:
            vvalid = jnp.take(
                jnp.take(vcol.valid, perm, mode="clip"), idx, mode="clip"
            )
            valid = jnp.logical_and(valid, vvalid)
        return Column(out, spec.out_type, valid, vcol.dictionary)

    def _percentile_one(self, batch: Batch, spec: AggSpec, out_cap: int) -> Column:
        """Exact per-group percentile: re-sort by (group keys, value) and
        pick the nearest-rank row of each group (reference role:
        ApproximateLongPercentileAggregations via qdigest — a sort-based
        engine computes the exact rank instead)."""
        gch = self.group_channels
        cap = batch.capacity
        col = batch.columns[spec.arg]
        keys = [SortKey(ch) for ch in gch] + [SortKey(spec.arg)]
        perm2 = multi_key_sort_perm(batch, keys)
        gid2, _, _ = group_ids_from_sorted(batch, perm2, gch)
        live2 = jnp.take(batch.mask(), perm2, mode="clip")
        varg = live2
        if col.valid is not None:
            varg = jnp.logical_and(varg, jnp.take(col.valid, perm2, mode="clip"))
        pos = jnp.arange(cap, dtype=jnp.int64)
        gid_c = jnp.minimum(gid2, out_cap)
        nseg = out_cap + 1
        # nulls sort last within the group: the group's first live row starts
        # the non-null run, whose length is the valid count
        start = segment_reduce(jnp.where(varg, pos, cap), gid_c, nseg, "min")
        nvalid = segment_reduce(None, gid_c, nseg, "count", valid=varg)
        p = float(spec.param if spec.param is not None else 0.5)
        target = start + jnp.round(
            p * jnp.maximum(nvalid - 1, 0).astype(jnp.float64)
        ).astype(jnp.int64)
        d_sorted = jnp.take(col.data, perm2, axis=0, mode="clip")
        val = jnp.take(
            d_sorted, jnp.clip(target[:out_cap], 0, cap - 1), axis=0, mode="clip"
        )
        return Column(val, spec.out_type, nvalid[:out_cap] > 0, col.dictionary)

    def _bivariate_series(self, batch, spec, kind, perm, live):
        """(per-row series, pairwise-valid mask) for one bi_* primitive."""
        cx = batch.columns[spec.arg]
        cy = batch.columns[spec.arg2]
        dx = _logical_double(_rows(cx.data, perm), cx.type)
        dy = _logical_double(_rows(cy.data, perm), cy.type)
        v = live
        if cx.valid is not None:
            v = jnp.logical_and(v, _rows(cx.valid, perm))
        if cy.valid is not None:
            v = jnp.logical_and(v, _rows(cy.valid, perm))
        series = {
            "bi_sum_1": dx,
            "bi_sum_2": dy,
            "bi_sumsq_1": dx * dx,
            "bi_sumsq_2": dy * dy,
            "bi_sum_12": dx * dy,
            "bi_count": jnp.ones(dx.shape, jnp.int64),
        }[kind]
        return series, v

    def _reduce_one(self, batch, spec, perm, live, gid, nseg, out_cap):
        if self.mode in ("final", "merge"):
            prims = list(zip(_merge_primitives(spec), _primitives(spec)))
            # state columns arrive as consecutive input channels starting at arg
            state_cols = []
            ch = spec.arg
            for kind, _ in prims:
                col = batch.columns[ch]
                d = _rows(col.data, perm)
                v = live
                if col.valid is not None:
                    v = jnp.logical_and(v, _rows(col.valid, perm))
                if (
                    kind == "sum"
                    and isinstance(col.type, T.DecimalType)
                    and col.type.is_long
                ):
                    # merging Int128 partial-sum states
                    red2 = _sum128(
                        d, gid, nseg, v, sum_bound=spec.sum_bound
                    )[:out_cap]
                    state_cols.append(Column(red2, col.type, None))
                    ch += 1
                    continue
                if (
                    d.ndim == 2
                    and isinstance(col.type, T.DecimalType)
                    and kind in ("min", "max", "any")
                ):
                    red2 = _reduce128(d, gid, nseg, kind, v)[:out_cap]
                    state_cols.append(Column(red2, col.type, None))
                    ch += 1
                    continue
                red = segment_reduce(d, gid, nseg, kind, valid=v)[:out_cap]
                state_cols.append(Column(red, col.type, None, col.dictionary))
                ch += 1
            return state_cols
        out = []
        for kind, arg in _primitives(spec):
            if kind == "count_star":
                red = segment_reduce(
                    jnp.ones(batch.capacity, jnp.int64), gid, nseg, "count", valid=live
                )[:out_cap]
                out.append(Column(red, T.BIGINT, None))
                continue
            if kind == "checksum":
                col = batch.columns[arg]
                h = _hll_hash(col).astype(jnp.int64)  # stable value hash
                h = _rows(h, perm)
                if col.valid is not None:
                    nullp = jnp.int64(np.int64(np.uint64(CHECKSUM_NULL_PRIME)))
                    h = jnp.where(_rows(col.valid, perm), h, nullp)
                red = segment_reduce(
                    jnp.where(live, h, 0), gid, nseg, "sum", valid=live
                )[:out_cap]
                out.append(Column(red, T.BIGINT, None))
                continue
            if kind.startswith("bi_"):
                series, v = self._bivariate_series(batch, spec, kind, perm, live)
                if kind == "bi_count":
                    red = segment_reduce(series, gid, nseg, "count", valid=v)[:out_cap]
                    out.append(Column(red, T.BIGINT, None))
                else:
                    red = segment_reduce(series, gid, nseg, "sum", valid=v)[:out_cap]
                    out.append(Column(red, T.DOUBLE, None))
                continue
            col = batch.columns[arg]
            d = _rows(col.data, perm)
            v = live
            if col.valid is not None:
                v = jnp.logical_and(v, _rows(col.valid, perm))
            st = _state_types(spec, self.input_types)[len(out)]
            if kind in ("sum_f", "sumsq"):
                dl = _logical_double(d, col.type)
                if kind == "sumsq":
                    dl = dl * dl
                red = segment_reduce(dl, gid, nseg, "sum", valid=v)[:out_cap]
                out.append(Column(red, T.DOUBLE, None))
                continue
            if kind == "sum" and isinstance(st, T.DecimalType) and st.is_long:
                prec = (
                    col.type.precision
                    if isinstance(col.type, T.DecimalType)
                    else None
                )
                red2 = _sum128(
                    d, gid, nseg, v, in_precision=prec,
                    sum_bound=spec.sum_bound,
                )[:out_cap]
                out.append(Column(red2, st, None))
                continue
            if (
                d.ndim == 2
                and isinstance(col.type, T.DecimalType)
                and kind in ("min", "max", "any")
            ):
                red2 = _reduce128(d, gid, nseg, kind, v)[:out_cap]
                out.append(Column(red2, st, None))
                continue
            if kind == "sum":
                # widen BEFORE reducing: int32 inputs must accumulate in int64
                d = d.astype(st.np_dtype)
            red = segment_reduce(d, gid, nseg, kind, valid=v)[:out_cap]
            out.append(
                Column(red.astype(st.np_dtype), st, None, col.dictionary)
            )
        return out

    def _global_reduce(self, batch: Batch) -> Batch:
        """No group keys: one output row (present even for empty input)."""
        live = batch.mask()
        cols = []
        for spec in self.aggregates:
            if spec.name in ("min_by", "max_by"):
                if self.mode != "single":
                    raise NotImplementedError(
                        f"{spec.name} requires single-stage aggregation"
                    )
                cap0 = batch.capacity
                perm0 = jnp.arange(cap0, dtype=jnp.int64)
                gid0 = jnp.zeros(cap0, dtype=jnp.int64)
                cols.append(
                    self._minmax_by_one(batch, spec, perm0, live, gid0, 2, 1)
                )
                continue
            if spec.name in COLLECT_AGGS:
                if self.mode != "single":
                    raise NotImplementedError(
                        f"{spec.name} requires single-stage aggregation"
                    )
                if spec.name == "listagg":
                    cols.append(self._listagg_one(batch, spec, 1))
                    continue
                # one global group: reuse the grouped collect with gid=0
                cap = batch.capacity
                perm = jnp.arange(cap, dtype=jnp.int64)
                gid_c = jnp.zeros(cap, dtype=jnp.int64)
                cols.append(
                    self._collect_one(batch, spec, perm, live, gid_c, 2, 1)
                )
                continue
            if spec.name == "percentile":
                if self.mode != "single":
                    raise NotImplementedError(
                        "percentile requires single-stage aggregation"
                    )
                col = batch.columns[spec.arg]
                v = live
                if col.valid is not None:
                    v = jnp.logical_and(v, col.valid)
                # sort values with invalid rows last
                perm = multi_key_sort_perm(
                    Batch(list(batch.columns), v), [SortKey(spec.arg)]
                )
                n = jnp.sum(v)
                p = float(spec.param if spec.param is not None else 0.5)
                idx = jnp.round(
                    p * jnp.maximum(n - 1, 0).astype(jnp.float64)
                ).astype(jnp.int64)
                d_sorted = jnp.take(col.data, perm, axis=0, mode="clip")
                val = jnp.take(
                    d_sorted, jnp.clip(idx, 0, batch.capacity - 1), axis=0
                )
                cols.append(
                    Column(val[None], spec.out_type, (n > 0)[None], col.dictionary)
                )
                continue
            states = []
            if self.mode in ("final", "merge"):
                ch = spec.arg
                for kind in _merge_primitives(spec):
                    col = batch.columns[ch]
                    v = live
                    if col.valid is not None:
                        v = jnp.logical_and(v, col.valid)
                    if kind == "hll":
                        # elementwise max of register rows (mergeable state)
                        sent = jnp.iinfo(jnp.int32).min
                        regs = jnp.max(
                            jnp.where(v[:, None], col.data, sent), axis=0
                        )
                        states.append(
                            Column(
                                regs[None, :],
                                T.ArrayType(T.INTEGER),
                                None,
                                lengths=jnp.full(1, HLL_M, jnp.int32),
                            )
                        )
                        ch += 1
                        continue
                    if kind == "qdigest":
                        from trino_tpu.ops import qdigest as qd

                        counts = jnp.sum(
                            jnp.where(v[:, None], col.data, 0), axis=0
                        )
                        states.append(
                            Column(
                                counts[None, :],
                                T.ArrayType(T.BIGINT),
                                None,
                                lengths=jnp.full(1, qd.NBUCKETS, jnp.int32),
                            )
                        )
                        ch += 1
                        continue
                    if (
                        kind == "sum"
                        and isinstance(col.type, T.DecimalType)
                        and col.type.is_long
                    ):
                        gid0 = jnp.zeros(col.data.shape[0], dtype=jnp.int64)
                        states.append(
                            Column(
                                _sum128(
                                    col.data, gid0, 1, v,
                                    sum_bound=spec.sum_bound,
                                ),
                                col.type, None,
                            )
                        )
                        ch += 1
                        continue
                    if (
                        col.data.ndim == 2
                        and isinstance(col.type, T.DecimalType)
                        and kind in ("min", "max", "any")
                    ):
                        gid0 = jnp.zeros(col.data.shape[0], dtype=jnp.int64)
                        states.append(
                            Column(
                                _reduce128(col.data, gid0, 1, kind, v),
                                col.type,
                                None,
                            )
                        )
                        ch += 1
                        continue
                    states.append(
                        Column(
                            _masked_reduce(col.data, v, kind)[None],
                            col.type,
                            None,
                            col.dictionary,
                        )
                    )
                    ch += 1
            else:
                for kind, arg in _primitives(spec):
                    if kind == "count_star":
                        states.append(
                            Column(jnp.sum(live, dtype=jnp.int64)[None], T.BIGINT, None)
                        )
                        continue
                    if kind == "checksum":
                        col = batch.columns[arg]
                        h = _hll_hash(col).astype(jnp.int64)
                        if col.valid is not None:
                            nullp = jnp.int64(
                                np.int64(np.uint64(CHECKSUM_NULL_PRIME))
                            )
                            h = jnp.where(col.valid, h, nullp)
                        states.append(
                            Column(
                                jnp.sum(jnp.where(live, h, 0))[None],
                                T.BIGINT,
                                None,
                            )
                        )
                        continue
                    if kind.startswith("bi_"):
                        series, v = self._bivariate_series(
                            batch, spec, kind, None, live
                        )
                        if kind == "bi_count":
                            states.append(
                                Column(
                                    jnp.sum(v, dtype=jnp.int64)[None],
                                    T.BIGINT,
                                    None,
                                )
                            )
                        else:
                            states.append(
                                Column(
                                    jnp.sum(jnp.where(v, series, 0.0))[None],
                                    T.DOUBLE,
                                    None,
                                )
                            )
                        continue
                    col = batch.columns[arg]
                    v = live
                    if col.valid is not None:
                        v = jnp.logical_and(v, col.valid)
                    if kind == "hll":
                        regs = _hll_registers(col, v)
                        states.append(
                            Column(
                                regs[None, :],
                                T.ArrayType(T.INTEGER),
                                None,
                                lengths=jnp.full(1, HLL_M, jnp.int32),
                            )
                        )
                        continue
                    if kind == "qdigest":
                        from trino_tpu.ops import qdigest as qd

                        if col.data.ndim == 2:  # long-decimal limb planes
                            from trino_tpu.types import int128 as i128

                            f = i128.to_float128(
                                col.data[:, 0], col.data[:, 1]
                            ) / float(col.type.scale_factor)
                        else:
                            f = _logical_double(col.data, col.type)
                        counts = qd.histogram(f, v)
                        states.append(
                            Column(
                                counts[None, :],
                                T.ArrayType(T.BIGINT),
                                None,
                                lengths=jnp.full(1, qd.NBUCKETS, jnp.int32),
                            )
                        )
                        continue
                    st = _state_types(spec, self.input_types)[len(states)]
                    d = col.data
                    if kind in ("sum_f", "sumsq"):
                        d = _logical_double(d, col.type)
                        if kind == "sumsq":
                            d = d * d
                        kind = "sum"
                    elif kind == "sum" and isinstance(st, T.DecimalType) and st.is_long:
                        gid0 = jnp.zeros(d.shape[0], dtype=jnp.int64)
                        prec = (
                            col.type.precision
                            if isinstance(col.type, T.DecimalType)
                            else None
                        )
                        states.append(
                            Column(
                                _sum128(
                                    d, gid0, 1, v, in_precision=prec,
                                    sum_bound=spec.sum_bound,
                                ),
                                st,
                                None,
                            )
                        )
                        continue
                    elif (
                        d.ndim == 2
                        and isinstance(col.type, T.DecimalType)
                        and kind in ("min", "max", "any")
                    ):
                        gid0 = jnp.zeros(d.shape[0], dtype=jnp.int64)
                        states.append(
                            Column(_reduce128(d, gid0, 1, kind, v), st, None)
                        )
                        continue
                    elif kind == "sum":
                        d = d.astype(st.np_dtype)  # widen before reducing
                    states.append(
                        Column(
                            _masked_reduce(d, v, kind)[None].astype(st.np_dtype),
                            st,
                            None,
                            col.dictionary,
                        )
                    )
            if self.mode in ("partial", "merge"):
                cols.extend(states)
            else:
                cols.append(_finalize(spec, states))
        return Batch(cols, jnp.ones(1, dtype=bool))

    # -- host-side streaming -------------------------------------------------

    def _batch_reducer(self) -> "AggregationOperator":
        """Per-batch operator for streaming: raw rows -> states, or (when this
        op's input is already states) states -> states."""
        per_mode = "merge" if self.mode in ("final", "merge") else "partial"
        op = AggregationOperator(
            self.group_channels,
            self.aggregates,
            self.input_types,
            mode=per_mode,
            use_pallas=self.use_pallas and per_mode == "partial",
            pre_step=self._pre if per_mode == "partial" else None,
            pre_key=self._pre_key if per_mode == "partial" else None,
            pre_jit=self._pre_jit if per_mode == "partial" else None,
        )
        op._group_src_channels = getattr(self, "_group_src_channels", None)
        return op

    #: fold accumulated per-batch states after this many batches (bounds
    #: device memory at ~FOLD_EVERY batch capacities, the revoke analog)
    FOLD_EVERY = 8

    def reduce_batch(self, batch: Batch) -> Batch:
        """One input batch -> its partial-state batch.  Dict/bool
        small-domain keys take the in-jit direct path (no host syncs, the
        Q1 shape); otherwise _reduce_full compacts dead slack and tries the
        positional path (one scalar sync)."""
        if self._per_batch is None:
            self._per_batch = self._batch_reducer()
        per_batch = self._per_batch
        if per_batch._direct_group_info(
            batch, src_channels=getattr(per_batch, "_group_src_channels", None)
        ) is not None:
            return per_batch._step(batch, out_cap=batch.capacity)
        if per_batch._pre is not None and per_batch._pre_jit is not None:
            # non-direct group keys (e.g. bigint orderkeys): the positional
            # path needs the PROJECTED batch for key stats, so materialize
            # the projection once and reduce through an unfused twin.
            # group_channels/input_types/spec.arg all describe the
            # POST-projection layout already (the fused op applies pre
            # first inside its own step), so the twin's config is correct
            # for the projected batch it is fed.
            if self._unfused_twin is None:
                self._unfused_twin = AggregationOperator(
                    per_batch.group_channels,
                    per_batch.aggregates,
                    per_batch.input_types,
                    mode=per_batch.mode,
                )
            return self._unfused_twin._reduce_full(
                per_batch._pre_jit(batch)
            )
        return per_batch._reduce_full(batch)

    def push(self, batch: Batch) -> None:
        """Accumulate one input batch (streamed per-batch reduction when
        `streaming`)."""
        if self.streaming:
            self._acc.append(self.reduce_batch(batch))
            if len(self._acc) >= self.fold_every:
                self._fold_states()
        else:
            self._acc.append(batch)
        if self.memory_ctx is not None:
            from trino_tpu.runtime.memory import (
                ExceededMemoryLimitException,
                batches_bytes,
            )

            try:
                self.memory_ctx.set_bytes(batches_bytes(self._acc))
            except ExceededMemoryLimitException:
                # graceful-degradation hook: folding compacts accumulated
                # states to live groups, often freeing enough to fit; only
                # re-raise when pressure survives the fold (the wave
                # fallback's / killer's signal)
                if not self.streaming or len(self._acc) <= 1:
                    raise
                self._fold_states()
                self.memory_ctx.set_bytes(batches_bytes(self._acc))

    def state_bytes(self) -> int:
        from trino_tpu.runtime.memory import batches_bytes

        return batches_bytes(self._acc)

    def process(self, stream):
        for batch in stream:
            self.push(batch)
        out = self.finish()
        if self.memory_ctx is not None:
            self.memory_ctx.close()
        yield out

    def _fold_states(self) -> None:
        """Merge accumulated state batches into one, compacted to live size."""
        merged = self._combine(concat_batches(self._acc), "merge")
        n = merged.num_rows_host()
        self._acc = [COMPACT(merged, out_capacity=next_pow2(max(n, 1), floor=1))]

    def finish(self) -> Batch:
        if not self._acc:
            empty = self._empty_input()
            if self._pre is not None:
                # _empty_input is in POST-projection layout; the fused pre
                # expects raw channels, so reduce with an unfused twin
                twin = AggregationOperator(
                    self.group_channels,
                    self.aggregates,
                    self.input_types,
                    mode=self.mode,
                )
                return twin.finish()
            if any(s.name in COLLECT_AGGS for s in self.aggregates):
                return self._reduce_step(empty, out_cap=max(1, empty.capacity))
            return self._step(empty, out_cap=max(1, empty.capacity))
        big = self._acc[0] if len(self._acc) == 1 else concat_batches(self._acc)
        if self.streaming:
            out_mode = "merge" if self.mode in ("partial", "merge") else "final"
            return self._combine(big, out_mode)
        return self._reduce_full(big)

    def _combine(self, states_batch: Batch, out_mode: str) -> Batch:
        """Re-reduce a batch of state rows (group keys + state columns)."""
        merger = AggregationOperator(
            list(range(len(self.group_channels))),
            [
                AggSpec(
                    s.name, self._state_channel(i), s.out_type,
                    param=s.param, sum_bound=s.sum_bound,
                )
                for i, s in enumerate(self.aggregates)
            ],
            [c.type for c in states_batch.columns],
            mode=out_mode,
        )
        return merger._reduce_full(states_batch)

    def _state_channel(self, agg_index: int) -> int:
        ch = len(self.group_channels)
        for s in self.aggregates[:agg_index]:
            ch += len(_primitives(s))
        return ch

    def _empty_input(self) -> Batch:
        import numpy as np

        cols = [
            Column(np.zeros(1, dtype=t.np_dtype), t, np.zeros(1, dtype=bool))
            for t in self.input_types
        ]
        return Batch(cols, np.zeros(1, dtype=bool))
