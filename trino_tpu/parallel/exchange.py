"""Collective exchange kernels (the data plane).

Reference roles (SURVEY.md §5.8): PartitionedOutputOperator/PagePartitioner +
ExchangeOperator/DirectExchangeClient become a hash-bucketize + all_to_all;
BroadcastOutputBuffer becomes all_gather; the final gather to the coordinator
is a host device_get.  Wire format: none needed — batches stay device-resident
columnar arrays; only dictionary codes must be pre-unified (stack_batches).

Shape discipline: all_to_all needs a static per-destination slot capacity.
A first jitted phase counts rows per (worker, destination); the host takes
the max and picks the pow2 slot capacity; the second jitted phase performs
the exchange (the reference's two-step "reserve then append" PagePartitioner
pattern, with the host sync standing in for buffer backpressure).

Neither phase scatters: the TPU serialises a scatter at 50-90 ns a SOURCE
row whatever comes out (three int64 columns of 2^21 rows: 3 x 192 ms a
statement, and 126 ms for a `segment_*` into five slots; PERF.md section 6,
PR 36).  The counts are a dense compare-and-sum (`segment_reduce`); the
bucketize is one stable compaction a destination (`slot_sources`) and one
gather a plane (`bucketize`).  Nothing is sorted by destination.  What a
consumer may rely on: a destination receives each sender's rows in the
sender's row order, senders in worker order.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.columnar import Batch, Column
from trino_tpu.columnar.batch import host_pull, slot_sources
from trino_tpu.ops.common import next_pow2, segment_reduce
from trino_tpu.parallel.spmd import WorkerMesh

_MIX = np.uint64(0x9E3779B97F4A7C15)
#: FNV offset basis seeding the row hash; shared with the host-side layout
#: mirror (partitioning/layout.host_bucket_hash) — the two MUST stay equal
#: or bucketed scans stop co-locating with repartition exchanges
HASH_INIT = np.uint64(1469598103934665603)
#: NULL key sentinel (nulls group together, SQL GROUP BY semantics)
_NULL_HASH = 0xDEADBEEF


def _hash_rows(batch: Batch, key_channels: Sequence[int]) -> jnp.ndarray:
    """64-bit row hash over key columns; NULL hashes as a distinct constant.
    Mirrored host-side by partitioning/layout.host_bucket_hash."""
    cap = batch.capacity
    h = jnp.full(cap, HASH_INIT, dtype=jnp.uint64)
    for ch in key_channels:
        c = batch.columns[ch]
        v = c.data
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int8)
        # long-decimal limb planes: mix each limb as its own word
        planes = (
            [v[:, i] for i in range(v.shape[1])] if v.ndim == 2 else [v]
        )
        for p in planes:
            bits = p.astype(jnp.int64).astype(jnp.uint64)
            if c.valid is not None:
                bits = jnp.where(c.valid, bits, jnp.uint64(_NULL_HASH))
            x = (bits ^ (bits >> 33)) * _MIX
            x = x ^ (x >> 29)
            h = (h ^ x) * _MIX
    return h


def _destinations(batch: Batch, key_channels: Sequence[int], n_workers: int):
    """[cap] int32: the worker each row's key hashes to; a dead row reads
    `n_workers`, which is nobody."""
    h = _hash_rows(batch, key_channels)
    dest = (h % jnp.uint64(n_workers)).astype(jnp.int32)
    return jnp.where(batch.mask(), dest, n_workers)


def bucketize(batch: Batch, dest, n_workers: int, slot_cap: int) -> Batch:
    """The send buffer of one worker: a Batch whose planes are
    [n_workers, slot_cap(, k)], piece `d` holding the rows with
    `dest == d` packed to the front in row order — the first `slot_cap` of
    them; its mask says which slots hold a row.  A dead slot is zero, not
    valid and not live, so the bytes a receiver sees are deterministic.

    `n_workers` stable compactions and one gather a plane, never a scatter
    (`slot_sources`; tools/exchange_sweep.py, PERF.md section 6, PR 36).
    Needs no mesh: a test and the sweep call it on one device."""
    pieces = [slot_sources(dest == d, slot_cap) for d in range(n_workers)]
    inv = jnp.stack([i for i, _ in pieces])
    live = jnp.stack([l for _, l in pieces])

    def place(plane):  # [cap(, k)] -> [n_workers, slot_cap(, k)]
        got = jnp.take(plane, inv, axis=0, mode="clip")
        keep = live.reshape(live.shape + (1,) * (got.ndim - live.ndim))
        return jnp.where(keep, got, jnp.zeros((), got.dtype))

    cols = [
        Column(
            place(c.data), c.type,
            None if c.valid is None else place(c.valid), c.dictionary,
        )
        for c in batch.columns
    ]
    return Batch(cols, live)


def _counts_kernel(key_channels, n_workers):
    def kernel(stacked: Batch):
        b = jax.tree.map(lambda x: x[0], stacked)
        dest = _destinations(b, key_channels, n_workers)
        # dead rows count into the slot past the workers', which is dropped
        counts = segment_reduce(None, dest, n_workers + 1, "count")
        return counts[None, :n_workers]

    return kernel


def _exchange_kernel(key_channels, n_workers, slot_cap):
    def kernel(stacked: Batch):
        b = jax.tree.map(lambda x: x[0], stacked)
        dest = _destinations(b, key_channels, n_workers)
        sent = bucketize(b, dest, n_workers, slot_cap)

        # the collective: piece d goes to worker d; received[w] = from worker w
        def ship(plane):
            got = jax.lax.all_to_all(
                plane, "workers", split_axis=0, concat_axis=0
            )
            return got.reshape((1, -1) + got.shape[2:])

        return jax.tree.map(ship, sent)

    return kernel


def exchange_slot_cap(
    stacked: Batch, key_channels: Sequence[int], wm: WorkerMesh,
    profile=None, fid: Optional[int] = None,
) -> int:
    """Phase 1 of the two-step exchange: a (cached) jitted counts pass, one
    tiny [W, W] host sync, and the pow2 slot-capacity bucket.  The bucket is
    what lets the fused phase-2 program cache across executions.  `profile`
    attributes the counts sync as capacity-sizing collective bytes and
    closes the compile event a cold counts pass opens (this call runs
    OUTSIDE the runner's instrumented `_call` window)."""
    from trino_tpu.parallel.spmd import TRACE_CACHE, cached_spmd_step, mesh_key
    from trino_tpu.telemetry import now
    from trino_tpu.telemetry.compile_events import OBSERVATORY

    r0 = TRACE_CACHE.retraces
    t0 = now()
    counts_fn = cached_spmd_step(
        wm,
        ("exchange_counts", tuple(key_channels), wm.n),
        lambda: _counts_kernel(key_channels, wm.n),
        collective=True,
    )
    counts = host_pull(counts_fn(stacked), "capacity")  # [W, W]
    if TRACE_CACHE.retraces > r0:
        from trino_tpu.runtime.lifecycle import check_current

        bucket = (
            stacked.columns[0].data.shape[-1] if stacked.columns else None
        )
        OBSERVATORY.close_open(
            now() - t0, bucket=bucket, fragment=fid, mesh=mesh_key(wm)
        )
        # deadline watchdog: same contract as the runner's _call — a
        # compile-event close re-checks the cancellation token so a long
        # counts-pass compile can't overshoot query_max_run_time silently
        check_current()
    if profile is not None:
        profile.add_collective(
            fid, int(counts.nbytes), "gather", "capacity_sizing"
        )
    return next_pow2(max(1, int(counts.max())), floor=64)


def fused_repartition(
    stacked: Batch,
    key_channels: Sequence[int],
    wm: WorkerMesh,
    consumer=None,
    key: tuple = (),
    slot_cap: Optional[int] = None,
) -> Batch:
    """Hash-repartition a stacked [W, cap] batch so equal keys land on the
    same worker, running bucketize + all_to_all (+ the consumer's first
    step, when given) as ONE compiled program.  Returns a stacked
    [W, W*slot_cap] batch — or the consumer's output shape.

    `consumer` is a per-worker Batch -> Batch step applied to the received
    batch INSIDE the same jit (the reference's exchange-then-operator pair
    collapsed into one task); `key` must fingerprint it for the trace
    cache (empty key + consumer=None is the plain repartition)."""
    from trino_tpu.parallel.spmd import cached_spmd_step

    assert consumer is None or key, "a fused consumer needs a cache key"
    if slot_cap is None:
        slot_cap = exchange_slot_cap(stacked, key_channels, wm)

    def build():
        ex_k = _exchange_kernel(key_channels, wm.n, slot_cap)
        if consumer is None:
            return ex_k

        def kernel(st: Batch):
            out = ex_k(st)
            b = jax.tree.map(lambda x: x[0], out)
            ob = consumer(b)
            return jax.tree.map(lambda x: x[None], ob)

        return kernel

    fn = cached_spmd_step(
        wm,
        ("fused_exchange", tuple(key_channels), slot_cap) + tuple(key),
        build,
        collective=True,
        # a fused consumer's kind (`agg_final`, ...) is part of the name
        name="_".join(("fused_exchange", *key[:1], "x")),
    )
    return fn(stacked)


def repartition(stacked: Batch, key_channels: Sequence[int], wm: WorkerMesh) -> Batch:
    """Hash-repartition a stacked [W, cap] batch so equal keys land on the
    same worker.  Returns a stacked [W, W*slot_cap] batch."""
    return fused_repartition(stacked, key_channels, wm)


def _broadcast_kernel(st: Batch):
    b = jax.tree.map(lambda x: x[0], st)

    def bcast(x):
        g = jax.lax.all_gather(x, "workers")  # [W, cap, ...]
        return g.reshape((-1,) + g.shape[2:])

    cols = [
        Column(
            bcast(c.data),
            c.type,
            None if c.valid is None else bcast(c.valid),
            c.dictionary,
        )
        for c in b.columns
    ]
    out = Batch(cols, bcast(b.mask()))
    return jax.tree.map(lambda x: x[None], out)


def broadcast(stacked: Batch, wm: WorkerMesh) -> Batch:
    """Replicate every worker's rows to all workers (FIXED_BROADCAST /
    BroadcastOutputBuffer role): stacked [W, cap] -> stacked [W, W*cap]."""
    from trino_tpu.parallel.spmd import cached_spmd_step

    fn = cached_spmd_step(
        wm, ("broadcast",), lambda: _broadcast_kernel, collective=True
    )
    return fn(stacked)
