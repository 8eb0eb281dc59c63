"""Join operators (reference: operator/join/* — HashBuilderOperator.java,
LookupJoinOperator.java + JoinProbe, NestedLoopJoinOperator.java,
HashSemiJoinOperator via SetBuilderOperator).

TPU substitution (SURVEY.md §7): no per-row open-addressing probe.  The build
side is materialized dense and *sorted once* by its key columns in
``set_build`` — the analog of the reference's one-time PagesHash construction
(operator/join/PagesHash.java: addressing built once, probed many times).
Each probe batch then locates its contiguous run of matching build rows with a
vectorized lexicographic *binary search* over the sorted build keys
(O(P·log B) fully-parallel compares — the streamed-probe analog of
LookupJoinOperator.java), and a cumsum-based row expansion emits the joined
rows.  All static-shape XLA: the only host round-trip per probe batch is one
scalar (the match count) used to pick the pow2-bucketed output capacity, the
analog of the reference's page-size-bounded join output building.

Dictionary-encoded (varchar) keys: build and probe may carry different
dictionaries, whose codes are not directly comparable.  The probe codes are
recoded host-side into the build dictionary's code space through a cached
i32 table (absent values -> -1, which can never equal a build code, so they
simply match nothing) — the analog of DictionaryBlock id remapping.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import COMPACT, concat_batches, host_pull
from trino_tpu.ops.common import next_pow2
from trino_tpu.telemetry.metrics import join_null_keys_counter
from trino_tpu.telemetry.programs import jit_program
from trino_tpu.telemetry.spans import NULL_TRACER, now


def _dense_build(batches: list[Batch], types: Sequence[T.Type]) -> tuple[Batch, int]:
    """Materialize the build side: concat + compact to pow2(live)."""
    if not batches:
        cols = [Column(np.zeros(1, dtype=t.np_dtype), t, np.zeros(1, dtype=bool)) for t in types]
        return Batch(cols, np.zeros(1, dtype=bool)), 0
    big = batches[0] if len(batches) == 1 else concat_batches(batches)
    n = big.num_rows_host()
    cap = next_pow2(max(n, 1), floor=1)
    return COMPACT(big, out_capacity=cap), n


def _canon_build_keys(build: Batch, key_channels: Sequence[int]):
    """Canonical key arrays + combined nomatch mask for a build side."""
    nomatch = jnp.logical_not(build.mask())
    canon = []
    for ch in key_channels:
        col = build.columns[ch]
        ds, nm = _canon_data(col)
        if col.valid is not None:
            nomatch = jnp.logical_or(nomatch, jnp.logical_not(col.valid))
        if nm is not None:
            nomatch = jnp.logical_or(nomatch, nm)
        canon.extend(ds)
    return canon, nomatch


def _lex_sort_perm(canon, nomatch, cap: int):
    """Stable lexicographic permutation: keys ascending, nomatch rows last."""
    perm = jnp.arange(cap, dtype=jnp.int64)
    for d in reversed(canon):
        order = jnp.argsort(jnp.take(d, perm, mode="clip"), stable=True)
        perm = perm[order]
    return perm[jnp.argsort(jnp.take(nomatch, perm, mode="clip"), stable=True)]


def _canon_data(col: Column):
    """([comparable-form arrays], extra-nomatch mask or None) for one key
    column.  Long decimals expand into TWO canon arrays (high limb, then
    low limb in unsigned order) so every downstream consumer — lex sort,
    binary search, composite packing — treats them as an extra key.

    SQL `=` never matches NULL, and float NaN keys never equal anything
    (reference DoubleOperators.equal is IEEE ==), so both are folded into the
    per-row `nomatch` flag instead of riding sentinel orderings.
    """
    d = col.data
    if isinstance(col.type, T.DecimalType) and col.type.is_long:
        sign = jnp.int64(np.int64(-(2**63)))
        if d.ndim == 1:
            # short-valued rows under a long type (e.g. a window sum):
            # widen so BOTH join sides contribute the same two canon arrays
            d64 = jnp.asarray(d, jnp.int64)
            return [d64 >> 63, d64 ^ sign], None
        return [d[:, 0], d[:, 1] ^ sign], None
    if d.dtype == jnp.bool_:
        d = d.astype(jnp.int8)
    nm = None
    if jnp.issubdtype(d.dtype, jnp.floating):
        nm = jnp.isnan(d)
        d = jnp.where(nm, jnp.zeros_like(d), d)
    return [d], nm


def _sort_build_device(build: Batch, key_channels: Sequence[int]):
    """Device-only build indexing (PagesHash-build analog; vmappable for the
    per-shard SPMD path).  Returns (sorted build Batch, sorted canonical key
    arrays, n_match device scalar).  Rows are physically reordered so that
    key-matchable rows (live, non-NULL, non-NaN keys) occupy [0, n_match)
    in lexicographic key order; everything else sorts after."""
    cap = build.capacity
    canon, nomatch = _canon_build_keys(build, key_channels)
    perm = _lex_sort_perm(canon, nomatch, cap)
    n_match = jnp.sum(jnp.logical_not(nomatch), dtype=jnp.int64)
    sorted_build = build.gather(perm)
    sorted_canon = [jnp.take(d, perm, mode="clip") for d in canon]
    return sorted_build, sorted_canon, n_match


def _canon_probe_device(probe: Batch, key_channels: Sequence[int], build_canon=None):
    """Device-only probe canonicalization WITHOUT dictionary recode (the
    caller guarantees directly comparable codes, e.g. after the SPMD path's
    up-front dictionary unification).  Returns (key arrays, nomatch mask)."""
    nomatch = jnp.logical_not(probe.mask())
    arrs = []
    for ch in key_channels:
        col = probe.columns[ch]
        if col.valid is not None:
            nomatch = jnp.logical_or(nomatch, jnp.logical_not(col.valid))
        ds, nm = _canon_data(col)
        if nm is not None:
            nomatch = jnp.logical_or(nomatch, nm)
        for d in ds:
            if build_canon is not None:
                bd = build_canon[len(arrs)]
                if d.dtype != bd.dtype:
                    # promoted dtype, never narrowing (see _probe_canonical)
                    d = d.astype(jnp.promote_types(d.dtype, bd.dtype))
            arrs.append(d)
    return arrs, nomatch


def _prepare_sorted_build(build: Batch, key_channels: Sequence[int]):
    """Host wrapper over the build sort: pulls n_match to host and records
    per-key build dictionaries for probe recoding.

    Fast path (host-only; set_build runs eagerly so a scalar sync is fine):
    when every canonical key is int-family and the combined (nomatch, keys)
    value range fits 62 bits, all sort keys pack into ONE composite int64 —
    one argsort instead of nkeys+1 stable passes."""
    cap = build.capacity
    canon, nomatch = _canon_build_keys(build, key_channels)
    perm = None
    table = None
    n_match = int(host_pull(jnp.sum(jnp.logical_not(nomatch)), "capacity"))
    if all(jnp.issubdtype(d.dtype, jnp.integer) for d in canon):
        imax = jnp.iinfo(jnp.int64).max
        mins, widths = [], []
        total = 1
        for d in canon:
            d64 = d.astype(jnp.int64)
            # nomatch rows must not widen the packed range
            mn, mx = (int(x) for x in host_pull((
                jnp.min(jnp.where(nomatch, imax, d64)),
                jnp.max(jnp.where(nomatch, -imax, d64)),
            ), "group_stats"))
            mins.append(mn)
            widths.append(mx - mn + 1)
            total *= mx - mn + 1
        if 0 < total <= (1 << 62) and all(w > 0 for w in widths):
            composite = jnp.zeros(cap, dtype=jnp.int64)
            for d, mn, w in zip(canon, mins, widths):
                composite = composite * w + (d.astype(jnp.int64) - mn)
            composite = jnp.where(nomatch, total, composite)
            perm = jnp.argsort(composite, stable=True)
            tcap = direct_table_cap(total, n_match)
            if tcap is not None:
                start_t, count_t = _direct_table(
                    jnp.take(composite, perm, mode="clip"), total, tcap
                )
                table = (
                    jnp.asarray(np.asarray(mins, dtype=np.int64)),
                    jnp.asarray(np.asarray(widths, dtype=np.int64)),
                    start_t,
                    count_t,
                )
    if perm is None:
        perm = _lex_sort_perm(canon, nomatch, cap)
    sorted_build = build.gather(perm)
    sorted_canon = [jnp.take(d, perm, mode="clip") for d in canon]
    dicts = [build.columns[ch].dictionary for ch in key_channels]
    return sorted_build, sorted_canon, n_match, dicts, table


def _build_recode_table(probe_dict, build_dict) -> Optional[jnp.ndarray]:
    """i32[|probe_dict|] mapping probe codes -> build codes (-1 = absent).
    None means codes are already directly comparable."""
    if probe_dict is None or build_dict is None:
        return None
    if probe_dict is build_dict or probe_dict == build_dict:
        return None
    table = np.full(len(probe_dict), -1, dtype=np.int32)
    # iterate the smaller dictionary (PatternDictionary values are lazy and
    # potentially huge; code_of stays O(log n) on both kinds)
    if len(build_dict) <= len(probe_dict):
        for bc, v in enumerate(build_dict.values):
            pc = probe_dict.code_of(v)
            if pc >= 0:
                table[pc] = bc
    else:
        for pc, v in enumerate(probe_dict.values):
            table[pc] = build_dict.code_of(v)
    return jnp.asarray(table)


#: packed-domain cap for direct-addressed probe tables (2 i32 arrays)
TABLE_DOMAIN_LIMIT = 1 << 25
#: a domain this small gets its tables (16 MB) however few of its codes the
#: build holds; above it only a build that fills 1/64 of the domain does.
#: The sorted locate costs 2 x log2(build) dependent gathers a probe row
#: where the tables cost two: a 27 440-row build over customer_demographics'
#: 1.92 M keys took 2.9 us a probe row sorted (v5e, PERF.md section 6, PR 30)
TABLE_SPARSE_LIMIT = 1 << 21


def direct_table_cap(total: int, n_match: int) -> Optional[int]:
    """The static size of the direct start/count tables over a packed key
    domain of `total` codes that `n_match` build rows can match, or None
    when the domain is too large, or too sparse for its rows, to be worth
    them: the sorted locate then.  The one rule of the local build and the
    mesh join (which reads `total` off its dynamic-filter summary)."""
    if 0 < total <= TABLE_DOMAIN_LIMIT and total <= max(
        64 * n_match, TABLE_SPARSE_LIMIT
    ):
        return next_pow2(total, floor=16)
    return None


def _direct_table(c_sorted, total, tcap: int):
    """Direct-addressed probe tables over a packed key domain (the
    PagesHash open-addressing analog, but positional): for each of the
    `tcap` composite codes, the first row holding it in the sorted build
    and how many rows do.  `c_sorted` is the build's composite code in its
    sorted row order, `total` (or more) on a row no key can match."""
    cap = c_sorted.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int64)
    cs = jnp.minimum(c_sorted, tcap)
    start_t = jax.ops.segment_min(
        jnp.where(c_sorted < total, pos, cap), cs, tcap + 1
    )[:tcap].astype(jnp.int32)
    count_t = jax.ops.segment_sum(
        (c_sorted < total).astype(jnp.int32), cs, tcap + 1
    )[:tcap]
    return start_t, count_t


def _pack_codes(canon, nomatch, mins, widths):
    """(composite code, nomatch) of key rows over the packed domain of
    `mins` / `widths`: a key outside its range matches nothing."""
    code = jnp.zeros(canon[0].shape[0], dtype=jnp.int64)
    for i, d in enumerate(canon):
        k = d.astype(jnp.int64) - mins[i]
        nomatch = jnp.logical_or(
            nomatch, jnp.logical_or(k < 0, k >= widths[i])
        )
        code = code * widths[i] + jnp.clip(k, 0, jnp.maximum(widths[i] - 1, 0))
    return code, nomatch


def _locate_table(probe_canon, probe_nomatch, mins, widths, start_t, count_t):
    """O(1)-per-row probe: composite code -> (start, count) table gather."""
    code, nomatch = _pack_codes(probe_canon, probe_nomatch, mins, widths)
    idx = jnp.clip(code, 0, start_t.shape[0] - 1)
    start = jnp.take(start_t, idx, mode="clip").astype(jnp.int64)
    count = jnp.where(
        nomatch, 0, jnp.take(count_t, idx, mode="clip").astype(jnp.int64)
    )
    return jnp.where(nomatch, 0, start), count


def _locate_by_table(build_canon, n_match, probe_canon, probe_nomatch,
                     mins, widths, tcap: int):
    """`_locate_sorted`'s contract by direct table, for a build already in
    `_sort_build_device`'s order whose key domain (`mins`, `widths`: run-time
    arrays, so a moved domain compiles nothing) `direct_table_cap` admitted
    at `tcap` codes: the tables are built here, in the caller's program,
    and each probe row costs two gathers, not 2 x log2(cap_b)."""
    cap = build_canon[0].shape[0]
    total = jnp.prod(widths)
    code, nomatch = _pack_codes(
        build_canon, jnp.arange(cap, dtype=jnp.int64) >= n_match, mins, widths
    )
    start_t, count_t = _direct_table(
        jnp.where(nomatch, total, code), total, tcap
    )
    return _locate_table(
        probe_canon, probe_nomatch, mins, widths, start_t, count_t
    )


def _locate_sorted(build_canon, n_match, probe_canon, probe_nomatch, cap_b: int):
    """Per probe row: (start, count) of its matching run in sorted-build row
    space.  Two vectorized binary searches (lower/upper bound) over the
    lexicographically sorted [0, n_match) prefix; log2(cap_b)+1 fixed
    iterations, no data-dependent control flow."""
    P = probe_canon[0].shape[0]
    nm = jnp.asarray(n_match, dtype=jnp.int64)
    iters = max(1, int(cap_b).bit_length())

    def bounds(le: bool):
        lo0 = jnp.zeros(P, dtype=jnp.int64)
        hi0 = jnp.full(P, nm, dtype=jnp.int64)

        def body(_, st):
            lo, hi = st
            active = lo < hi
            mid = (lo + hi) >> 1
            lt = jnp.zeros(P, dtype=bool)
            eq = jnp.ones(P, dtype=bool)
            for bk, pk in zip(build_canon, probe_canon):
                bv = jnp.take(bk, mid, mode="clip")
                lt = jnp.logical_or(lt, jnp.logical_and(eq, bv < pk))
                eq = jnp.logical_and(eq, bv == pk)
            go_right = jnp.logical_or(lt, eq) if le else lt
            lo2 = jnp.where(go_right, mid + 1, lo)
            hi2 = jnp.where(go_right, hi, mid)
            return jnp.where(active, lo2, lo), jnp.where(active, hi2, hi)

        lo, _ = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
        return lo

    lo = bounds(False)
    hi = bounds(True)
    count = jnp.where(probe_nomatch, 0, hi - lo)
    start = jnp.where(probe_nomatch, 0, lo)
    return start, count


def _null_keys(probe_live, probe_nomatch):
    """Live probe rows whose key can match nothing because it is NULL (or
    NaN): one more output of the local operators' locate programs, so the
    count rides the launch and the pull a join makes anyway."""
    return jnp.sum(jnp.logical_and(probe_live, probe_nomatch), dtype=jnp.int64)


def _locate_table_step(probe_live, probe_canon, probe_nomatch, *table):
    return (
        *_locate_table(probe_canon, probe_nomatch, *table),
        _null_keys(probe_live, probe_nomatch),
    )


def _locate_sorted_step(
    probe_live, build_canon, n_match, probe_canon, probe_nomatch, cap_b: int
):
    return (
        *_locate_sorted(build_canon, n_match, probe_canon, probe_nomatch, cap_b),
        _null_keys(probe_live, probe_nomatch),
    )


#: process-level jitted-step cache (cross-query reuse; see filter_project).
#: CONTRACT: a cached step must read NO per-query state off `self` — only
#: configuration captured in its cache key; per-query data (the build batch,
#: null flags) is passed as explicit arguments.
_STEP_CACHE: dict = {}


def _jit_cached(key, factory):
    if key is None:
        return factory()
    if key not in _STEP_CACHE:
        _STEP_CACHE[key] = factory()
    return _STEP_CACHE[key]


class JoinSpan:
    """The `join` span of one join operator in one statement: from the
    operator's first probe batch to its last output, with `kind`,
    `strategy` (the locate step the build chose; `partition_waves` for a
    build over the memory budget), `keys` (its equi-key columns),
    `build_rows` and, where the operator
    reads them anyway, `probe_rows`, `out_rows` (matches emitted, before a
    residual filter) and `null_keys` (probe rows dropped for a NULL key).
    Made while planning, on the statement's thread; the stream may then
    run on a feed thread (a join below another join runs on its probe
    prefetch), whose lane the span is pushed on instead: the operator's
    `feed_launch` and `feed_pull` spans nest under it there.  A hash join
    makes its span before its probe feed, so that the feed's lane hangs
    under it, and names its strategy once the build is indexed
    (`chose()`)."""

    __slots__ = ("tracer", "sp")

    def __init__(self, kind: str, op=None, strategy: str = "", keys: int = 1):
        from trino_tpu.runtime.lifecycle import current_query
        from trino_tpu.telemetry.programs import recording_tracer

        ctx = current_query()
        self.tracer = (
            ctx is not None and recording_tracer(ctx) or NULL_TRACER
        )
        #: None when tracing is off: `wrap` then only counts NULL keys
        self.sp = self.tracer.held(
            "join", under="execute", kind=kind, keys=keys
        )
        if op is not None or strategy:
            self.chose(op, strategy)

    def chose(self, op=None, strategy: str = "") -> None:
        """The build's choice: `op`'s locate step and build rows, or a
        `strategy` with no operator (partition_waves)."""
        if self.sp is None:
            return
        if op is not None:
            self.sp.attrs["strategy"] = op.strategy
            self.sp.attrs["build_rows"] = op.build_rows
        else:
            self.sp.attrs["strategy"] = strategy

    def wrap(self, process, probe_stream, op=None):
        """`process(probe_stream)` under the span: the operator's own work
        on a batch is inside it (its launches and pulls nest there, on
        whichever recording thread runs the stream), the probe side's
        stays outside.  `op`: the operator whose counts the span takes
        when the stream is done."""
        tracer, sp = self.tracer, self.sp
        done = object()
        started = False

        def tap():
            nonlocal started
            it = iter(probe_stream)
            while True:
                with tracer.left(sp):
                    b = next(it, done)
                if b is done:
                    return
                if sp is not None and not started:
                    started = True
                    sp.start_s = sp.end_s = now()
                yield b

        out = process(tap())
        try:
            while True:
                with tracer.entered(sp):
                    b = next(out, done)
                if b is done:
                    return
                if sp is not None:
                    sp.end_s = now()
                yield b
        finally:
            out.close()  # an abandoned stream (LIMIT) releases its build now
            nulls = getattr(op, "null_keys", 0)
            if nulls:
                join_null_keys_counter().inc(nulls)
            if sp is not None and hasattr(op, "out_rows"):
                sp.attrs.update(
                    probe_rows=op.probe_rows, out_rows=op.out_rows,
                    null_keys=op.null_keys,
                )


class _SortedBuildJoinBase:
    """Shared machinery: build-once sorted index + binary-search probe."""

    def __init__(self, probe_key_channels, build_key_channels):
        self.probe_keys = list(probe_key_channels)
        self.build_keys = list(build_key_channels)
        self.build: Optional[Batch] = None
        self._build_canon = None
        self._n_match = 0
        self._key_dicts = [None] * len(self.build_keys)
        self._table = None
        self._recode: dict = {}  # key index -> {id(probe_dict): (dict, table)}
        self._locate = _jit_cached(
            ("locate", len(self.build_keys)),
            lambda: jit_program(
                _locate_sorted_step, "join_locate_sorted",
                static_argnames=("cap_b",),
            ),
        )
        self._locate_t = _jit_cached(
            ("locate_table", len(self.build_keys)),
            lambda: jit_program(_locate_table_step, "join_locate_table"),
        )

    @property
    def strategy(self) -> str:
        """The locate step this build chose (a launch step's name)."""
        return (
            "join_locate_table" if self._table is not None
            else "join_locate_sorted"
        )

    @property
    def build_rows(self) -> int:
        """Build rows a probe key can match (live, key not NULL)."""
        return self._n_match

    def release_build(self) -> None:
        """Drop every device reference to the indexed build side (the
        memory-revocation hook, reference HashBuilderOperator
        .startMemoryRevoke: once the build has been spilled host-side the
        operator releases its HBM so the pool reservation it gave back is
        physically real).  The operator is unusable afterwards; callers
        switch to partition-wave execution against the spilled build."""
        self.build = None
        self._build_canon = None
        self._n_match = 0
        self._table = None
        self._recode = {}

    def _index_build(self, build: Batch) -> None:
        (
            self.build,
            self._build_canon,
            self._n_match,
            self._key_dicts,
            self._table,
        ) = _prepare_sorted_build(build, self.build_keys)
        self._recode = {}

    def _recode_for(self, i: int, probe_dict):
        cache = self._recode.setdefault(i, {})
        hit = cache.get(id(probe_dict))
        if hit is not None:
            return hit[1]
        table = _build_recode_table(probe_dict, self._key_dicts[i])
        cache[id(probe_dict)] = (probe_dict, table)  # pin dict: id stays valid
        return table

    def _probe_canonical(self, probe: Batch):
        """Probe key arrays in the build's comparable domain + nomatch mask.
        Runs eagerly (a handful of gathers) so dictionary recode tables stay
        out of jit cache keys."""
        nomatch = jnp.logical_not(probe.mask())
        arrs = []
        for i, ch in enumerate(self.probe_keys):
            col = probe.columns[ch]
            if col.valid is not None:
                nomatch = jnp.logical_or(nomatch, jnp.logical_not(col.valid))
            if col.dictionary is not None and self._key_dicts[i] is not None:
                table = self._recode_for(i, col.dictionary)
                d = col.data.astype(jnp.int32)
                if table is not None:
                    d = jnp.take(table, d, mode="clip")
                arrs.append(d)
                continue
            ds, nm = _canon_data(col)
            if nm is not None:
                nomatch = jnp.logical_or(nomatch, nm)
            for d in ds:
                # compare in the PROMOTED dtype: narrowing a wide probe key
                # to the build dtype would wrap out-of-range values onto
                # valid build keys (e.g. BIGINT 2^32+5 = INTEGER 5) and
                # fabricate matches
                bd = self._build_canon[len(arrs)]
                if d.dtype != bd.dtype:
                    d = d.astype(jnp.promote_types(d.dtype, bd.dtype))
                arrs.append(d)
        return arrs, nomatch

    def _locate_batch(self, probe: Batch):
        """(start, count) of each probe row's matching run, and the live
        probe rows whose key is NULL: SQL `=` matches none of them."""
        pc, pn = self._probe_canonical(probe)
        if self._table is not None:
            mins, widths, start_t, count_t = self._table
            return self._locate_t(
                probe.mask(), pc, pn, mins, widths, start_t, count_t
            )
        return self._locate(
            probe.mask(), self._build_canon, self._n_match, pc, pn,
            cap_b=self.build.capacity,
        )


class HashJoinOperator(_SortedBuildJoinBase):
    """Equi join. Probe = left side (streamed), build = right (materialized);
    output columns = probe columns ++ build columns (reference: JoinNode output
    = left ++ right, build on right per LocalExecutionPlanner.visitJoin).

    kind: inner | left | full.  (right joins are planned as flipped left
    joins; cross joins use NestedLoopJoinOperator.)
    """

    def __init__(
        self,
        kind: str,
        probe_key_channels: Sequence[int],
        build_key_channels: Sequence[int],
        build_types: Sequence[T.Type],
        probe_types: Sequence[T.Type] = (),
        residual=None,
        residual_key=None,
    ):
        """`residual`: optional fn(candidate Batch: probe++build cols) -> bool
        mask, the non-equi join conjuncts (reference: JoinNode.filter /
        JoinFilterFunctionCompiler).  Outer-join semantics: a probe row whose
        matches all fail the residual still emits one null-padded row.
        `residual_key`: hashable identity of the residual (e.g. the expr key)
        enabling cross-query reuse of the jitted expand step."""
        assert kind in ("inner", "left", "full")
        super().__init__(probe_key_channels, build_key_channels)
        self.kind = kind
        self.build_types = list(build_types)
        self._probe_types_cache = list(probe_types)
        self.residual = residual
        self._build_rows = 0
        self._build_matched = None  # bool[cap_b], for full outer
        #: what `_join_batch` reads to the host anyway, summed over the
        #: probe: the `join` span's attributes
        self.probe_rows = self.out_rows = self.null_keys = 0
        cache_key = None
        if residual is None or residual_key is not None:
            cache_key = (
                "expand", kind, tuple(self.probe_keys), tuple(self.build_keys),
                tuple(t.name for t in self.build_types), residual_key,
            )
        self._expand = _jit_cached(
            cache_key, lambda: jit_program(
                self._expand_step, "join_expand",
                static_argnames=("out_cap", "cap_b"),
            )
        )
        self._expand_unique = _jit_cached(
            None if cache_key is None else ("uniq",) + cache_key[1:],
            lambda: jit_program(
                self._expand_unique_step, "join_expand_unique",
                static_argnames=("cap_b",),
            ),
        )

    def set_build(self, batches: list[Batch]) -> None:
        build, self._build_rows = _dense_build(batches, self.build_types)
        self._index_build(build)
        if self.kind == "full":
            self._build_matched = jnp.zeros(self.build.capacity, dtype=bool)

    def _expand_step(
        self, probe: Batch, build: Batch, start, count, build_matched,
        out_cap: int, cap_b: int, total_emit
    ):
        emit = count if self.kind == "inner" else jnp.where(probe.mask(), jnp.maximum(count, 1), 0)
        offsets = jnp.cumsum(emit) - emit
        cap_p = probe.capacity
        has = emit > 0
        seed = (
            jnp.zeros(out_cap, dtype=jnp.int64)
            .at[jnp.where(has, offsets, out_cap)]
            .max(jnp.arange(cap_p, dtype=jnp.int64), mode="drop")
        )
        ids = jax.lax.cummax(seed)  # out slot -> probe slot
        j = jnp.arange(out_cap, dtype=jnp.int64) - offsets[ids]
        matched = j < count[ids]
        build_row = jnp.clip(start[ids] + j, 0, cap_b - 1)
        out_live = jnp.arange(out_cap, dtype=jnp.int64) < total_emit
        pcols = [
            Column(
                jnp.take(c.data, ids, axis=0, mode="clip"),
                c.type,
                None if c.valid is None else jnp.take(c.valid, ids, mode="clip"),
                c.dictionary,
            )
            for c in probe.columns
        ]
        bvalid_base = jnp.logical_and(matched, out_live)
        bcols = [
            Column(
                jnp.take(c.data, build_row, axis=0, mode="clip"),
                c.type,
                bvalid_base
                if c.valid is None
                else jnp.logical_and(bvalid_base, jnp.take(c.valid, build_row, mode="clip")),
                c.dictionary,
            )
            for c in build.columns
        ]
        keep_match = jnp.logical_and(matched, out_live)
        if self.residual is not None:
            candidate = Batch(list(pcols) + list(bcols), out_live)
            keep_match = jnp.logical_and(keep_match, self.residual(candidate))
            if self.kind == "inner":
                out_live = keep_match
            else:
                # probe rows with emitted matches but zero residual survivors
                # degrade their first slot to an unmatched (null-build) row
                surv = jax.ops.segment_sum(
                    keep_match.astype(jnp.int64), ids, probe.capacity
                )
                to_null = jnp.logical_and(
                    jnp.logical_and(j == 0, surv[ids] == 0), out_live
                )
                out_live = jnp.logical_and(out_live, jnp.logical_or(keep_match, to_null))
                bcols = [
                    Column(
                        c.data,
                        c.type,
                        jnp.logical_and(
                            keep_match, c.valid if c.valid is not None else True
                        ),
                        c.dictionary,
                    )
                    for c in bcols
                ]
        new_matched = None
        if self.kind == "full":
            new_matched = build_matched.at[
                jnp.where(keep_match, build_row, cap_b)
            ].set(True, mode="drop")
        return Batch(list(pcols) + list(bcols), out_live), new_matched

    def _expand_unique_step(
        self, probe: Batch, build: Batch, start, count, build_matched, cap_b: int
    ):
        """FK->PK fast path: every probe row has at most one match, so output
        rows are the probe rows IN PLACE (no cumsum expansion, no probe
        gathers) and only build columns are gathered — the dominant join
        shape in TPC workloads (reference analog: PagesHash with single-row
        key runs probed by LookupJoinOperator)."""
        matched = jnp.logical_and(count > 0, probe.mask())
        build_row = jnp.clip(start, 0, cap_b - 1)
        bcols = [
            Column(
                jnp.take(c.data, build_row, axis=0, mode="clip"),
                c.type,
                matched
                if c.valid is None
                else jnp.logical_and(matched, jnp.take(c.valid, build_row, mode="clip")),
                c.dictionary,
            )
            for c in build.columns
        ]
        keep_match = matched
        out_live = probe.mask() if self.kind != "inner" else matched
        if self.residual is not None:
            candidate = Batch(list(probe.columns) + list(bcols), out_live)
            keep_match = jnp.logical_and(keep_match, self.residual(candidate))
            if self.kind == "inner":
                out_live = keep_match
            else:
                # non-matching residual degrades the row to null-build
                bcols = [
                    Column(
                        c.data,
                        c.type,
                        jnp.logical_and(
                            keep_match, c.valid if c.valid is not None else True
                        ),
                        c.dictionary,
                    )
                    for c in bcols
                ]
        new_matched = None
        if self.kind == "full":
            new_matched = build_matched.at[
                jnp.where(keep_match, build_row, cap_b)
            ].set(True, mode="drop")
        return Batch(list(probe.columns) + list(bcols), out_live), new_matched

    def _join_batch(self, probe: Batch) -> Batch:
        cap_b = self.build.capacity
        start, count, null = self._locate_batch(probe)
        maxc, total_inner, probe_live, null = (
            int(x) for x in host_pull(
                (jnp.max(count), jnp.sum(count), probe.count(), null),
                "capacity",
            )
        )
        self.probe_rows += probe_live
        self.null_keys += null
        if maxc <= 1:
            out, new_matched = self._expand_unique(
                probe, self.build, start, count, self._build_matched, cap_b=cap_b
            )
            if new_matched is not None:
                self._build_matched = new_matched
            n_out = total_inner if self.kind == "inner" else probe_live
            self.out_rows += n_out
            cc = next_pow2(max(n_out, 1), floor=1024)
            if cc * 2 <= out.capacity:
                # selective join: hand downstream a dense batch, not a
                # mostly-dead full-capacity one
                out = COMPACT(out, out_capacity=cc)
            return out
        if self.kind == "inner":
            total = total_inner
        else:
            total = int(host_pull(
                jnp.sum(jnp.where(probe.mask(), jnp.maximum(count, 1), 0)),
                "capacity",
            ))
        self.out_rows += total
        out_cap = next_pow2(max(total, 1), floor=1024)
        out, new_matched = self._expand(
            probe, self.build, start, count, self._build_matched,
            out_cap=out_cap, cap_b=cap_b, total_emit=total,
        )
        if new_matched is not None:
            self._build_matched = new_matched
        return out

    def process(self, stream):
        assert self.build is not None, "set_build() before process()"
        for probe in stream:
            yield self._join_batch(probe)
        if self.kind == "full":
            yield self._unmatched_build()

    def _unmatched_build(self) -> Batch:
        """FULL OUTER tail: build rows never matched, probe columns NULL."""
        b = self.build
        live = jnp.logical_and(b.mask(), jnp.logical_not(self._build_matched))
        ncols = []
        for t in self._probe_types_cache:
            ncols.append(
                Column(
                    jnp.zeros(b.capacity, dtype=t.np_dtype),
                    t,
                    jnp.zeros(b.capacity, dtype=bool),
                    None,
                )
            )
        return Batch(ncols + list(b.columns), live)


class NestedLoopJoinOperator:
    """Cross join (reference: NestedLoopJoinOperator.java): every probe row ×
    every build row, via the same cumsum expansion with constant counts."""

    def __init__(self, build_types: Sequence[T.Type]):
        self.build_types = list(build_types)
        self.build: Optional[Batch] = None
        self._nb = 0
        self._step = _jit_cached(
            ("nested", tuple(t.name for t in self.build_types)),
            lambda: jit_program(
                self._expand, "join_nested_expand",
                static_argnames=("out_cap", "nb"),
            ),
        )

    strategy = "join_nested_expand"

    @property
    def build_rows(self) -> int:
        return self._nb

    def set_build(self, batches: list[Batch]) -> None:
        self.build, self._nb = _dense_build(batches, self.build_types)

    def _expand(self, probe: Batch, build: Batch, out_cap: int, nb: int, total_emit):
        cap_p = probe.capacity
        emit = jnp.where(probe.mask(), nb, 0)
        offsets = jnp.cumsum(emit) - emit
        has = emit > 0
        seed = (
            jnp.zeros(out_cap, dtype=jnp.int64)
            .at[jnp.where(has, offsets, out_cap)]
            .max(jnp.arange(cap_p, dtype=jnp.int64), mode="drop")
        )
        ids = jax.lax.cummax(seed)
        j = jnp.arange(out_cap, dtype=jnp.int64) - offsets[ids]
        out_live = jnp.arange(out_cap, dtype=jnp.int64) < total_emit
        pcols = [
            Column(
                jnp.take(c.data, ids, axis=0, mode="clip"),
                c.type,
                None if c.valid is None else jnp.take(c.valid, ids, mode="clip"),
                c.dictionary,
            )
            for c in probe.columns
        ]
        bcols = [
            Column(
                jnp.take(c.data, j, axis=0, mode="clip"),
                c.type,
                None if c.valid is None else jnp.take(c.valid, j, mode="clip"),
                c.dictionary,
            )
            for c in build.columns
        ]
        return Batch(list(pcols) + list(bcols), out_live)

    def process(self, stream):
        assert self.build is not None
        for probe in stream:
            if self._nb == 0:
                continue
            total = probe.num_rows_host() * self._nb
            out_cap = next_pow2(max(total, 1), floor=1024)
            yield self._step(
                probe, self.build, out_cap=out_cap, nb=self._nb, total_emit=total
            )


class SemiJoinOperator(_SortedBuildJoinBase):
    """Appends a boolean `mark` column: source key ∈ filtering-side keys.

    null_aware=True gives SQL IN null semantics — mark is NULL when the
    source key is NULL, or when there is no match but the filtering side
    contains a NULL (reference: HashSemiJoinOperator + SetBuilderOperator's
    containsNull handling).  null_aware=False is EXISTS: plain boolean.

    `residual`: optional fn(candidate Batch: source++filtering cols) -> bool
    mask for correlated EXISTS conjuncts (reference: the filter function of
    JoinNode produced for correlated exists, e.g. TPC-H Q21's
    l2.l_suppkey <> l1.l_suppkey); a row is marked iff some key-matching
    filtering row also passes the residual.
    """

    def __init__(
        self,
        source_key_channel: int,
        filtering_key_channel: int,
        filtering_types: Sequence[T.Type],
        null_aware: bool = True,
        residual=None,
        residual_key=None,
    ):
        super().__init__([source_key_channel], [filtering_key_channel])
        self.filtering_types = list(filtering_types)
        self.null_aware = null_aware
        self.residual = residual
        self._filter_has_null = False
        self._mark = _jit_cached(
            ("mark", null_aware, source_key_channel, filtering_key_channel),
            lambda: jit_program(
                self._mark_step, "join_semi_mark", static_argnames=("has_null",)
            ),
        )
        res_key = (
            None
            if (residual is not None and residual_key is None)
            else ("mark_res", null_aware, source_key_channel, filtering_key_channel,
                  tuple(t.name for t in self.filtering_types), residual_key)
        )
        self._mark_res = _jit_cached(
            res_key,
            lambda: jit_program(
                self._mark_residual_step, "join_semi_mark_residual",
                static_argnames=("cap_b", "out_cap", "has_null"),
            ),
        )

    def set_build(self, batches: list[Batch]) -> None:
        build, _ = _dense_build(batches, self.filtering_types)
        col = build.columns[self.build_keys[0]]
        if col.valid is not None:
            has_null = jnp.any(
                jnp.logical_and(build.mask(), jnp.logical_not(col.valid))
            )
            self._filter_has_null = bool(has_null)
        self._index_build(build)

    def _mark_from_matched(self, probe: Batch, matched, has_null: bool) -> Batch:
        key = probe.columns[self.probe_keys[0]]
        key_valid = key.valid if key.valid is not None else jnp.ones(probe.capacity, bool)
        if not self.null_aware:
            mark_valid = None
        elif has_null:
            mark_valid = jnp.logical_and(key_valid, matched)
        else:
            mark_valid = key_valid
        return probe.append_column(Column(matched, T.BOOLEAN, mark_valid))

    def _mark_step(self, probe: Batch, count, has_null: bool) -> Batch:
        return self._mark_from_matched(probe, count > 0, has_null)

    def _mark_residual_step(
        self, probe: Batch, build: Batch, start, count,
        cap_b: int, out_cap: int, total_emit, has_null: bool
    ) -> Batch:
        """Expand key-matching candidates, apply residual, any() per row."""
        offsets = jnp.cumsum(count) - count
        cap_p = probe.capacity
        has = count > 0
        seed = (
            jnp.zeros(out_cap, dtype=jnp.int64)
            .at[jnp.where(has, offsets, out_cap)]
            .max(jnp.arange(cap_p, dtype=jnp.int64), mode="drop")
        )
        ids = jax.lax.cummax(seed)
        j = jnp.arange(out_cap, dtype=jnp.int64) - offsets[ids]
        in_range = jnp.logical_and(
            j < count[ids], jnp.arange(out_cap, dtype=jnp.int64) < total_emit
        )
        build_row = jnp.clip(start[ids] + j, 0, cap_b - 1)
        pcols = [
            Column(
                jnp.take(c.data, ids, axis=0, mode="clip"),
                c.type,
                None if c.valid is None else jnp.take(c.valid, ids, mode="clip"),
                c.dictionary,
            )
            for c in probe.columns
        ]
        bcols = [
            Column(
                jnp.take(c.data, build_row, axis=0, mode="clip"),
                c.type,
                in_range
                if c.valid is None
                else jnp.logical_and(in_range, jnp.take(c.valid, build_row, mode="clip")),
                c.dictionary,
            )
            for c in build.columns
        ]
        candidate = Batch(list(pcols) + list(bcols), in_range)
        keep = jnp.logical_and(in_range, self.residual(candidate))
        surv = jax.ops.segment_sum(keep.astype(jnp.int64), ids, cap_p)
        return self._mark_from_matched(probe, surv > 0, has_null)

    def process(self, stream):
        assert self.build is not None
        cap_b = self.build.capacity
        for probe in stream:
            start, count, _ = self._locate_batch(probe)
            if self.residual is None:
                yield self._mark(probe, count, has_null=self._filter_has_null)
            else:
                total = int(host_pull(jnp.sum(count), "capacity"))
                out_cap = next_pow2(max(total, 1), floor=1024)
                yield self._mark_res(
                    probe, self.build, start, count,
                    cap_b=cap_b, out_cap=out_cap, total_emit=total,
                    has_null=self._filter_has_null,
                )
