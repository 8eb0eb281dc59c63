"""On-chip sweep that chose how `Batch.compact_device` finds each output
slot's source row (`columnar/batch.slot_sources`; PERF.md §6, PR 31).

Times a whole compaction — source rows, then the gather of `ncols` int64
columns — at the (capacity, out_capacity) pairs the ledger's PR 30 lines
name, with the source rows found five ways:

  * `blocksort`: the engine's own `slot_sources`, a one-key sort in blocks;
  * `scatter`: the form this sweep retired, `zeros(outc + 1, int64)
    .at[idx].set(arange(cap))` over int64 positions (the reference answer);
  * `dense`: a masked sum of row numbers per slot, rows x slots vector work
    (timed only up to `--dense-max` slots); 0.3 ms a call faster than
    `blocksort` below about 400 slots, slower above;
  * `sort`: one `lax.sort` of the whole u32 key plane; as fast, but 11-17 s
    of compile a variant against 3-5 s;
  * `search`: `searchsorted(cumsum(mask), 1..outc)`; loses from 8 192 slots.

One JSON line per point: milliseconds a call (median of `--reps`), compile
seconds (a point's forms compile side by side), the compiled program's temp
bytes (a materialised [rows, slots] plane would show there) and whether the
output equals the scatter's.  First, per capacity, what one long `cumsum`
costs to compile (why `dense` counts in two levels and `slot_sources` only
over its blocks' totals).

    chiprun -- python tools/compact_sweep.py

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

#: (capacity, out_capacity) as `jit_compact`'s fusions read in the ledger
SHAPES = (
    (1 << 19, 1 << 10), (1 << 19, 1 << 13), (1 << 19, 1 << 15),
    (1 << 19, 1 << 16), (1 << 19, 1 << 17),
    (1 << 20, 1), (1 << 20, 8), (1 << 20, 1 << 12), (1 << 20, 1 << 17),
    (1 << 20, 1 << 19),
    (1 << 17, 1 << 13),
)
LIVE_SHARES = (0.001, 0.2, 0.9)  # of out_capacity
NCOLS = (1, 10)
#: the shapes at which ten columns are gathered as well as one
WIDE_SHAPES = ((1 << 19, 1 << 10), (1 << 19, 1 << 17), (1 << 20, 1 << 19))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--dense-max", type=int, default=1 << 13,
                    help="largest out_capacity the dense form is timed at")
    ap.add_argument("--shapes", default="",
                    help="cap:outc,... instead of the ledger's shapes")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: the line is stamped with the platform")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trino_tpu  # noqa: F401  (enables x64)
    from trino_tpu.columnar.batch import slot_sources

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"compact_sweep: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    def scatter(m, outc):
        idx = jnp.where(m, jnp.cumsum(m) - 1, outc)
        return jnp.zeros(outc + 1, dtype=jnp.int64).at[idx].set(
            jnp.arange(m.shape[0], dtype=jnp.int64), mode="drop"
        )[:outc]

    def running_count(m, lanes=512):
        x = jnp.pad(m, (0, -m.shape[0] % lanes)).reshape(-1, lanes)
        within = jnp.cumsum(x, axis=1, dtype=jnp.int32)
        total = within[:, -1]
        return (within + (jnp.cumsum(total) - total)[:, None]).reshape(-1)[
            : m.shape[0]
        ]

    def dense(m, outc):
        rows = jnp.arange(m.shape[0], dtype=jnp.int32)
        slot = jnp.where(m, running_count(m) - 1, outc)
        if outc == 1:
            return jnp.sum(jnp.where(slot == 0, rows, 0), dtype=jnp.int32)[None]
        hit = slot[:, None] == jnp.arange(outc, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(hit, rows[:, None], 0), axis=0, dtype=jnp.int32)

    def live_of(m, outc):
        return jnp.arange(outc, dtype=jnp.int32) < jnp.sum(m, dtype=jnp.int32)

    def sort(m, outc):
        rows = jnp.arange(m.shape[0], dtype=jnp.uint32)
        key = jax.lax.sort(jnp.where(m, rows, rows | jnp.uint32(1 << 31)))
        key = jnp.pad(key, (0, max(0, outc - m.shape[0])))[:outc]
        return jnp.where(
            live_of(m, outc), key & jnp.uint32(0x7FFF_FFFF), 0
        ).astype(jnp.int32)

    def search(m, outc):
        count = jnp.cumsum(m, dtype=jnp.int32)
        inv = jnp.searchsorted(
            count, jnp.arange(1, outc + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)
        return jnp.where(live_of(m, outc), inv, 0)

    forms = {
        "scatter": scatter,
        "dense": dense,
        "sort": sort,
        "blocksort": lambda m, outc: slot_sources(m, outc)[0],
        "search": search,
    }

    def timed(exe, *operands):
        """(output, milliseconds a call: median of `--reps` after one warm call)"""
        out = jax.block_until_ready(exe(*operands))
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(exe(*operands))
            walls.append(time.perf_counter() - t0)
        return out, round(statistics.median(walls) * 1e3, 4)

    def compiled(form, cap, outc, ncols):
        def step(m, cols):
            inv = forms[form](m, outc)
            return inv, [jnp.take(c, inv, axis=0, mode="clip") for c in cols]

        m = jax.ShapeDtypeStruct((cap,), jnp.bool_)
        cols = [jax.ShapeDtypeStruct((cap,), jnp.int64)] * ncols
        t0 = time.perf_counter()
        exe = jax.jit(step).lower(m, cols).compile()
        return exe, time.perf_counter() - t0

    shapes = SHAPES
    if args.shapes:
        shapes = tuple(
            tuple(int(x) for x in s.split(":")) for s in args.shapes.split(",")
        )
    rng = np.random.default_rng(31)
    for cap in sorted({c for c, _ in shapes}):
        line = {"platform": dev.platform, "cap": cap}
        for name, fn in (
            ("cumsum_int64", lambda m: jnp.cumsum(m)),
            ("cumsum_int32", lambda m: jnp.cumsum(m, dtype=jnp.int32)),
            ("running_count", running_count),
        ):
            t0 = time.perf_counter()
            exe = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((cap,), jnp.bool_)
            ).compile()
            compile_s = time.perf_counter() - t0
            m = jnp.asarray(rng.random(cap) < 0.2)
            _, ms = timed(exe, m)
            line[name] = {"ms": ms, "compile_s": round(compile_s, 2)}
        print(json.dumps(line), flush=True)
    points = [(c, o, NCOLS[0]) for c, o in shapes] + [
        (c, o, n) for c, o in shapes if (c, o) in WIDE_SHAPES for n in NCOLS[1:]
    ]
    for cap, outc, ncols in points:
        cols = [
            jnp.asarray(rng.integers(-(1 << 40), 1 << 40, cap, dtype=np.int64))
            for _ in range(ncols)
        ]
        # a point's forms compile side by side (the compiler runs
        # one thread a program, so each wall is its own)
        names = [f for f in forms if f != "dense" or outc <= args.dense_max]
        with ThreadPoolExecutor(len(names)) as pool:
            exes = dict(zip(names, pool.map(
                lambda f: compiled(f, cap, outc, ncols), names
            )))
        for share in LIVE_SHARES:
            live_rows = min(cap, max(1, round(share * outc)))
            mask = np.zeros(cap, dtype=bool)
            mask[rng.choice(cap, live_rows, replace=False)] = True
            m = jnp.asarray(mask)
            line = {
                "platform": dev.platform, "device_kind": dev.device_kind,
                "cap": cap, "outc": outc, "ncols": ncols,
                "live_share": share, "live_rows": live_rows,
            }
            want = None
            for f, (exe, compile_s) in exes.items():
                out, ms = timed(exe, m, cols)
                got = [np.asarray(x) for x in jax.tree_util.tree_leaves(out)]
                if want is None:
                    want = got
                line[f] = {
                    "ms": ms,
                    "compile_s": round(compile_s, 2),
                    "temp_bytes": int(
                        exe.memory_analysis().temp_size_in_bytes
                    ),
                    "equal": all(
                        np.array_equal(a, b) for a, b in zip(got, want)
                    ),
                }
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
