"""On-chip sweep that chose how the repartition places and counts its rows
(`parallel/exchange.bucketize`, `_counts_kernel`; PERF.md §6, PR 36).

Times one worker's half of the exchange in front of the collective — row
hash, destination, send buffer — on one device, no mesh, two ways:

  * `old`: the form this sweep retired, kept here only as the reference: a
    stable int64 `argsort` by destination, `jax.ops.segment_min` into
    `n_workers + 1` slots for each destination's first position, and one
    `.at[flat].set` a plane into `n_workers * slot_cap + 1` slots;
  * `new`: the engine's `bucketize` — `slot_sources(dest == d, slot_cap)` a
    destination and one gather a plane;

and the counts pass the same two ways (`jax.ops.segment_sum` into
`n_workers + 1` slots against `segment_reduce`'s dense compare-and-sum).
`sources` is `new` without its column gathers (hash, destinations, the
`slot_sources`), so the difference prices the gathers.

One JSON line a point: milliseconds a call (median of `--reps`), compile
seconds (`new` programs compile one at a time, so theirs price; the `old`
ones side by side, `--compile-threads` at a time, so theirs rank), the
compiled program's temp bytes, and whether every plane of the two send
buffers is equal.

    chiprun -- python tools/exchange_sweep.py

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

N_WORKERS = 4
#: (rows a worker, slot_cap): the mesh cell's probe side (ledger, PR 33:
#: `jit_fused_exchange_x (u32[1048577],…)` = 4 x 2^18 + 1 slots from 2^21
#: rows), a half-size input, and an exchange at the 64-slot floor
SHAPES = ((1 << 21, 1 << 18), (1 << 20, 1 << 18), (1 << 16, 64))
LIVE_SHARES = (0.1, 0.5, 1.0)
COLUMN_SETS = ("int64x3", "mixed10")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--shapes", default="",
                    help="cap:slot_cap,... instead of the ledger's shapes")
    ap.add_argument("--compile-threads", type=int, default=6)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: the line is stamped with the platform")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trino_tpu  # noqa: F401  (enables x64)
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.columnar.batch import slot_sources
    from trino_tpu.columnar.dictionary import StringDictionary
    from trino_tpu.parallel import exchange as ex

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"exchange_sweep: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    words = StringDictionary(["a", "b", "c", "d"])

    def rows_of(columns: str, cap: int, share: float, seed: int) -> Batch:
        """Random keys (channel 0), the live share spread evenly."""
        rng = np.random.default_rng(seed)

        def i64():
            return Column(rng.integers(-(1 << 40), 1 << 40, cap), T.BIGINT)

        cols = [i64(), i64(), i64()]
        if columns == "mixed10":
            cols += [
                i64(),
                Column(rng.integers(0, 1 << 20, cap), T.DecimalType(12, 2),
                       valid=rng.random(cap) < 0.9),
                Column(rng.integers(0, 1 << 20, cap), T.BIGINT,
                       valid=rng.random(cap) < 0.5),
                Column(rng.integers(8000, 11000, cap).astype(np.int32), T.DATE),
                Column(rng.integers(8000, 11000, cap).astype(np.int32), T.DATE),
                Column(rng.integers(0, 1 << 62, (cap, 2)), T.DecimalType(38, 2)),
                Column(rng.integers(0, 4, cap).astype(np.int32), T.VARCHAR,
                       dictionary=words),
            ]
        return Batch(cols, rng.random(cap) < share).device_put()

    # -- the retired form, whole (parallel/exchange.py before PR 36) ----------

    def old_dest(b: Batch):
        h = ex._hash_rows(b, [0])
        dest = (h % jnp.uint64(N_WORKERS)).astype(jnp.int64)
        return jnp.where(b.mask(), dest, N_WORKERS)

    def old_counts(b: Batch):
        dest = old_dest(b)
        return jax.ops.segment_sum(
            jnp.ones_like(dest), dest, N_WORKERS + 1
        )[:N_WORKERS]

    def old_place(b: Batch, slot_cap: int) -> Batch:
        cap = b.capacity
        dest = old_dest(b)
        order = jnp.argsort(dest, stable=True)
        d_sorted = dest[order]
        pos = jnp.arange(cap, dtype=jnp.int64)
        first = jax.ops.segment_min(pos, d_sorted, N_WORKERS + 1)
        slot = pos - first[jnp.clip(d_sorted, 0, N_WORKERS)]
        valid_slot = jnp.logical_and(d_sorted < N_WORKERS, slot < slot_cap)
        flat = jnp.where(
            valid_slot, d_sorted * slot_cap + slot, N_WORKERS * slot_cap
        )

        def scatter(plane, fill):
            out = jnp.full(
                (N_WORKERS * slot_cap + 1,) + plane.shape[1:], fill, plane.dtype
            )
            out = out.at[flat].set(plane[order], mode="drop")
            return out[:-1].reshape((N_WORKERS, slot_cap) + plane.shape[1:])

        cols = [
            Column(
                scatter(c.data, jnp.asarray(0, c.data.dtype)), c.type,
                None if c.valid is None else scatter(c.valid, False),
                c.dictionary,
            )
            for c in b.columns
        ]
        return Batch(cols, scatter(b.mask(), False))

    # -- the engine's form ------------------------------------------------------

    def new_counts(b: Batch):
        stacked = jax.tree.map(lambda x: x[None], b)
        return ex._counts_kernel([0], N_WORKERS)(stacked)[0]

    def new_place(b: Batch, slot_cap: int) -> Batch:
        dest = ex._destinations(b, [0], N_WORKERS)
        return ex.bucketize(b, dest, N_WORKERS, slot_cap)

    def new_sources(b: Batch, slot_cap: int):
        dest = ex._destinations(b, [0], N_WORKERS)
        return [slot_sources(dest == d, slot_cap) for d in range(N_WORKERS)]

    def compiled(low):
        t0 = time.perf_counter()
        exe = low.compile()
        return exe, time.perf_counter() - t0

    def timed(exe, batch):
        out = jax.block_until_ready(exe(batch))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(exe(batch))
            walls.append(time.perf_counter() - t0)
        return out, round(statistics.median(walls) * 1e3, 4)

    def equal(a, b) -> bool:
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        return len(la) == len(lb) and all(
            x.dtype == y.dtype and x.shape == y.shape
            and bool(jnp.array_equal(x, y))
            for x, y in zip(la, lb)
        )

    shapes = SHAPES
    if args.shapes:
        shapes = tuple(
            tuple(int(v) for v in s.split(":")) for s in args.shapes.split(",")
        )
    stamp = {"platform": dev.platform, "device_kind": dev.device_kind}
    points = [(cap, slot, cols) for cap, slot in shapes for cols in COLUMN_SETS]

    examples: dict = {}

    def lower(fn, cap, slot, cols):
        if (cap, cols) not in examples:
            examples[cap, cols] = rows_of(cols, cap, 0.5, 0)
        example = examples[cap, cols]
        if slot is None:
            return jax.jit(fn).lower(example)
        return jax.jit(lambda b: fn(b, slot)).lower(example)

    programs: dict = {}
    # the engine's programs one at a time, so their compile seconds price
    for cap, slot, cols in points:
        programs[cap, slot, cols, "new"] = compiled(lower(new_place, cap, slot, cols))
        programs[cap, slot, cols, "sources"] = compiled(
            lower(new_sources, cap, slot, cols)
        )
    for cap, slot in shapes:
        programs[cap, "new_counts"] = compiled(lower(new_counts, cap, None, "int64x3"))
    # the reference's side by side: its int64 argsort compiles for minutes
    old_keys = [(cap, slot, cols, "old") for cap, slot, cols in points]
    old_lows = [lower(old_place, cap, slot, cols) for cap, slot, cols, _ in old_keys]
    old_keys += [(cap, "old_counts") for cap, _ in shapes]
    old_lows += [lower(old_counts, cap, None, "int64x3") for cap, _ in shapes]
    with ThreadPoolExecutor(args.compile_threads) as pool:
        programs.update(zip(old_keys, pool.map(compiled, old_lows)))
    examples.clear()

    seed = 36
    for cap, slot, cols in points:
        built = {f: programs[cap, slot, cols, f] for f in ("old", "new", "sources")}
        for share in LIVE_SHARES:
            seed += 1
            batch = rows_of(cols, cap, share, seed)
            ref, old_ms = timed(built["old"][0], batch)
            got, new_ms = timed(built["new"][0], batch)
            _, sources_ms = timed(built["sources"][0], batch)
            sent = int(jnp.sum(got.row_mask))
            print(json.dumps({
                "cap": cap, "slot_cap": slot, "n_workers": N_WORKERS,
                "columns": cols, "live_share": share, "rows_sent": sent,
                "rows_cut": int(jnp.sum(batch.row_mask)) - sent,
                "old_ms": old_ms, "new_ms": new_ms, "sources_ms": sources_ms,
                "equal": equal(ref, got),
                "compile_s": {f: round(s, 2) for f, (_, s) in built.items()},
                "temp_bytes": {
                    f: int(e.memory_analysis().temp_size_in_bytes)
                    for f, (e, _) in built.items()
                },
                **stamp,
            }), flush=True)
    for cap, _ in shapes:
        seed += 1
        batch = rows_of("int64x3", cap, 0.5, seed)
        (old_exe, old_s), (new_exe, new_s) = (
            programs[cap, "old_counts"], programs[cap, "new_counts"]
        )
        ref, old_ms = timed(old_exe, batch)
        got, new_ms = timed(new_exe, batch)
        print(json.dumps({
            "cap": cap, "program": "exchange_counts", "n_workers": N_WORKERS,
            "old_ms": old_ms, "new_ms": new_ms, "equal": equal(ref, got),
            "compile_s": {"old": round(old_s, 2), "new": round(new_s, 2)},
            **stamp,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
