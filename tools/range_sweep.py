"""On-chip sweep that chose how the range-positional aggregation
(`ops/aggregation.AggregationOperator._range_step`) reduces over MANY
groups (PERF.md §6, PR 33).

Times the engine's own step, whole — group code, reductions, key decode —
at the shapes the ledger's PR 32 lines name, three ways:

  * `scatter`: the form this sweep retired, `jax.ops.segment_*` into
    `out_cap + 1` slots, one scatter a reduced plane (the step's `dense`
    form with `DENSE_SEGMENT_LIMIT` forced to 0: yesterday's program but
    for its identity gathers, so the reference is, if anything, flattered);
  * `runs`: rows in group order — prefix sums read at run ends
    (`ops/common.Runs`);
  * `sorted_runs`: the same rows shuffled — one stable 32-bit sort with the
    row number as payload and a gather a reduced plane, then `runs`.

`scatter` is timed on both inputs.  One JSON line per point: milliseconds
a call (median of `--reps`), compile seconds (every program compiles side
by side, `--compile-threads` at a time), the compiled program's temp bytes, and whether the live output
rows, in key order, equal the scatter's (floats: largest relative
difference).  Then, per shape, what the order check added to
`agg_key_stats`.

    chiprun -- python tools/range_sweep.py

Refuses to run off a TPU: a CPU timing is not a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: (capacity, out_cap, mode): Q18's per-split step and its fold (ledger, PR
#: 32, `jit_agg_range (u32[262145],…)` and `(u32[2097153],…)`), and the
#: smallest shapes above the dense limit
SHAPES = (
    (1 << 20, 1 << 18, "partial"),
    (1 << 21, 1 << 21, "merge"),
    (1 << 19, 1 << 12, "partial"),
    (1 << 19, 1 << 16, "partial"),
)
LIVE_SHARES = (0.7, 0.1)
KINDS = ("sum", "min", "dsum")  # licensed decimal sum, bigint min, DOUBLE sum


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--shapes", default="",
                    help="cap:out_cap:mode,... instead of the ledger's shapes")
    ap.add_argument("--compile-threads", type=int, default=10)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: the line is stamped with the platform")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trino_tpu  # noqa: F401  (enables x64)
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.ops import common
    from trino_tpu.ops.aggregation import AggregationOperator, AggSpec

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"range_sweep: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    dec, long_dec = T.DecimalType(15, 2), T.DecimalType(38, 2)

    def operator(kind: str, mode: str) -> AggregationOperator:
        if mode == "merge":  # key, sum state (limb planes), count state
            spec = AggSpec("sum", 1, long_dec, sum_bound=10**15)
            return AggregationOperator(
                [0], [spec], [T.BIGINT, long_dec, T.BIGINT], mode="merge"
            )
        spec, vtype = {
            "sum": (AggSpec("sum", 1, long_dec, sum_bound=10**15), dec),
            "min": (AggSpec("min", 1, T.BIGINT), T.BIGINT),
            "dsum": (AggSpec("sum", 1, T.DOUBLE), T.DOUBLE),
        }[kind]
        return AggregationOperator([0], [spec], [T.BIGINT, vtype], mode="partial")

    def rows_of(kind, mode, cap, out_cap, share, seed):
        """(ordered batch, the same rows shuffled, mins, sizes): keys
        ascending over [7, 7 + 0.9 * out_cap), dead rows interleaved."""
        rng = np.random.default_rng(seed)
        keys = np.sort(rng.integers(0, int(out_cap * 0.9), cap)) + 7
        live = rng.random(cap) < share
        live[[0, -1]] = True
        vals = rng.integers(1, 5001, cap)
        cols = [keys]
        if mode == "merge":
            cols += [np.stack([np.zeros(cap, np.int64), vals], axis=1),
                     rng.integers(1, 8, cap)]
        elif kind == "dsum":
            cols += [vals / 100.0]
        else:
            cols += [vals]
        order = rng.permutation(cap)
        op = operator(kind, mode)

        def batch(idx):
            return Batch(
                [Column(jnp.asarray(c[idx]), t, None)
                 for c, t in zip(cols, op.input_types)],
                jnp.asarray(live[idx]),
            )

        lo, hi = int(keys[live].min()), int(keys[live].max())
        mins = jnp.asarray(np.asarray([lo], np.int64))
        sizes = jnp.asarray(np.asarray([hi - lo + 1], np.int64))
        return batch(np.arange(cap)), batch(order), mins, sizes

    def lowered(kind, mode, out_cap, form, example):
        op = operator(kind, mode)
        limit = common.DENSE_SEGMENT_LIMIT

        def step(batch, mins, sizes):
            return op._range_step(
                batch, mins, sizes, out_cap=out_cap,
                form="dense" if form == "scatter" else form,
            )

        # the limit is read while the step traces: trace one form at a time
        common.DENSE_SEGMENT_LIMIT = 0 if form == "scatter" else limit
        try:
            return jax.jit(step).lower(*example)
        finally:
            common.DENSE_SEGMENT_LIMIT = limit

    def compiled(low):
        t0 = time.perf_counter()
        exe = low.compile()
        return exe, time.perf_counter() - t0

    def timed(exe, *operands):
        out = jax.block_until_ready(exe(*operands))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(exe(*operands))
            walls.append(time.perf_counter() - t0)
        return out, round(statistics.median(walls) * 1e3, 4)

    def live_rows(out: Batch):
        """The live output rows as one [rows, planes] float/int table in key
        order (a positional and a packed output then compare equal)."""
        mask = np.asarray(out.mask())
        planes = []
        for c in out.columns:
            d = np.asarray(c.data)[mask]
            planes.extend(d.T if d.ndim == 2 else [d])
        table = np.stack(planes, axis=1)
        return table[np.argsort(table[:, 0], kind="stable")]

    shapes = SHAPES
    if args.shapes:
        shapes = tuple(
            (int(c), int(o), m)
            for c, o, m in (s.split(":") for s in args.shapes.split(","))
        )
    stamp = {"platform": dev.platform, "device_kind": dev.device_kind}
    seed = 33
    forms = ("scatter", "runs", "sorted_runs")
    points = [
        (cap, out_cap, mode, kind)
        for cap, out_cap, mode in shapes
        for kind in (KINDS if mode == "partial" else ("sum",))
    ]
    # trace one at a time (see `lowered`), compile every program side by side
    lows = []
    for cap, out_cap, mode, kind in points:
        example = rows_of(kind, mode, cap, out_cap, LIVE_SHARES[0], seed)
        lows.extend(
            lowered(kind, mode, out_cap, form, (example[0],) + example[2:])
            for form in forms
        )
    with ThreadPoolExecutor(args.compile_threads) as pool:
        done = iter(list(pool.map(compiled, lows)))
    programs = {point: dict(zip(forms, (next(done) for _ in forms))) for point in points}

    for cap, out_cap, mode in shapes:
        for kind in KINDS if mode == "partial" else ("sum",):
            built = programs[cap, out_cap, mode, kind]
            exes = {f: exe for f, (exe, _) in built.items()}
            compile_s = {f: s for f, (_, s) in built.items()}
            for share in LIVE_SHARES:
                seed += 1
                ordered, shuffled, mins, sizes = rows_of(
                    kind, mode, cap, out_cap, share, seed
                )
                ref, scatter_ms = timed(exes["scatter"], ordered, mins, sizes)
                _, scatter_shuffled_ms = timed(
                    exes["scatter"], shuffled, mins, sizes
                )
                runs, runs_ms = timed(exes["runs"], ordered, mins, sizes)
                srt, sorted_ms = timed(exes["sorted_runs"], shuffled, mins, sizes)
                want = live_rows(ref)
                line = {
                    "cap": cap, "out_cap": out_cap, "mode": mode, "kind": kind,
                    "live_share": share, "groups": int(want.shape[0]),
                    "scatter_ms": scatter_ms,
                    "scatter_shuffled_ms": scatter_shuffled_ms,
                    "runs_ms": runs_ms, "sorted_runs_ms": sorted_ms,
                    "compile_s": {f: round(s, 2) for f, s in compile_s.items()},
                    "temp_bytes": {
                        f: int(e.memory_analysis().temp_size_in_bytes)
                        for f, e in exes.items()
                    },
                }
                for name, got in (("runs", runs), ("sorted_runs", srt)):
                    got = live_rows(got)
                    if got.shape != want.shape:
                        line[name + "_equal"] = False
                    elif kind == "dsum":
                        line[name + "_max_rel_diff"] = float(
                            np.max(np.abs(got - want) / np.maximum(np.abs(want), 1))
                        )
                    else:
                        line[name + "_equal"] = bool((got == want).all())
                print(json.dumps({**line, **stamp}), flush=True)

        # what the order check costs `agg_key_stats`, against min/max alone
        op = operator("sum", mode)
        ordered, shuffled, _, _ = rows_of("sum", mode, cap, out_cap, 0.7, seed)

        def minmax(batch):
            live = batch.mask()
            d = batch.columns[0].data
            big = jnp.iinfo(jnp.int64).max
            return jnp.min(jnp.where(live, d, big)), jnp.max(jnp.where(live, d, -big))

        old = jax.jit(minmax).lower(ordered).compile()
        t0 = time.perf_counter()
        op._key_stats(ordered)  # builds and compiles the program
        stats_compile_s = time.perf_counter() - t0
        _, old_ms = timed(old, ordered)
        (_, _, flag_o), new_ms = timed(lambda b: op._key_stats(b), ordered)
        (_, _, flag_s), _ = timed(lambda b: op._key_stats(b), shuffled)
        print(json.dumps({
            "cap": cap, "program": "agg_key_stats", "minmax_only_ms": old_ms,
            "with_order_ms": new_ms, "compile_s": round(stats_compile_s, 2),
            "ordered_reads": bool(flag_o), "shuffled_reads": bool(flag_s),
            **stamp,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
