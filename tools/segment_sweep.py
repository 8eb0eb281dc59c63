"""On-chip sweep that chose `ops/common.DENSE_SEGMENT_LIMIT` (PERF.md §6).

Times `segment_reduce` — the engine's own helper, its limit forced to
"always dense" and to "always scatter" — for an int64 sum of 2^20 rows
into `nseg` segments, at two live shares, and prints one JSON line per
point: milliseconds a call (median of `--reps`), the compiled program's
temp bytes (a materialised [rows, nseg] one-hot would show there), and
whether both lowerings returned the same integers.

    chiprun -- python tools/segment_sweep.py

Refuses to run off a TPU: a CPU timing is not a device number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NSEGS = (1, 2, 13, 33, 129, 513, 1025, 2049, 4097)
LIVE_SHARES = (0.02, 0.98)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--kind", default="sum", choices=("sum", "min", "count"))
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: the line is stamped with the platform")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import trino_tpu  # noqa: F401  (enables x64)
    from trino_tpu.ops import common

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"segment_sweep: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1

    rng = np.random.default_rng(27)
    values = jnp.asarray(
        rng.integers(-(1 << 40), 1 << 40, args.rows, dtype=np.int64)
    )

    def timed(limit: int, nseg: int, gid, live):
        common.DENSE_SEGMENT_LIMIT = limit
        fn = jax.jit(
            lambda v, g, ok: common.segment_reduce(v, g, nseg, args.kind, valid=ok)
        )
        compiled = fn.lower(values, gid, live).compile()
        out = compiled(values, gid, live).block_until_ready()
        walls = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            compiled(values, gid, live).block_until_ready()
            walls.append(time.perf_counter() - t0)
        temp = compiled.memory_analysis().temp_size_in_bytes
        return statistics.median(walls) * 1e3, int(temp), np.asarray(out)

    for nseg in NSEGS:
        for share in LIVE_SHARES:
            live = jnp.asarray(rng.random(args.rows) < share)
            # dead rows carry the out-of-range id, as the operators' do
            gid = jnp.where(
                live, jnp.asarray(rng.integers(0, nseg, args.rows)), nseg
            ).astype(jnp.int64)
            dense_ms, dense_temp, a = timed(1 << 62, nseg, gid, live)
            scatter_ms, scatter_temp, b = timed(0, nseg, gid, live)
            print(json.dumps({
                "platform": dev.platform, "device_kind": dev.device_kind,
                "kind": args.kind, "rows": args.rows, "nseg": nseg,
                "live_share": share, "dense_ms": round(dense_ms, 4),
                "scatter_ms": round(scatter_ms, 4),
                "scatter_over_dense": round(scatter_ms / dense_ms, 2),
                "dense_temp_bytes": dense_temp,
                "scatter_temp_bytes": scatter_temp,
                "equal": bool(np.array_equal(a, b)),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
