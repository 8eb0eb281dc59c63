"""Scan buffer pool: two-tier (host RAM / device HBM) cache of scan batches.

Reference roles: the OS page cache + connector-level caching that keeps a warm
Java Trino from re-reading ORC bytes per query, and `MemoryPagesStore`'s role
of serving hot tables from RAM.  On a TPU the analogous scarce path is
host→device transfer (PCIe: on a v5e, first generating and loading Q1's SF1
scan columns took 4–9 s and re-loading them after a pool clear 0.5 s, against
0.6 s for the warm query over the resident copy — PR 21 chip runs), so the
pool keeps *device-resident* batches for repeated scans of immutable splits — a buffer pool over HBM — with a host tier of
already-padded numpy batches below it.

Entries are keyed by (table, split slice, projected columns, page size,
connector scan version); a connector that cannot guarantee immutability
returns version None and is never cached.  Both tiers are byte-budgeted LRU,
accounted through runtime/memory.py MemoryContext so budgets are visible in
the same reservation tree the operators use.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Optional

from trino_tpu.runtime.memory import MemoryContext, batch_bytes


def _env_bytes(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class _Tier:
    """One byte-budgeted LRU tier."""

    def __init__(self, name: str, limit_bytes: int):
        self.name = name
        self.limit_bytes = limit_bytes
        self.entries: OrderedDict = OrderedDict()  # key -> (batches, nbytes)
        self.ctx = MemoryContext(None, f"buffer_pool:{name}")
        self.hits = 0
        self.misses = 0

    def get(self, key):
        e = self.entries.get(key)
        if e is None:
            self.misses += 1
            return None
        self.entries.move_to_end(key)
        self.hits += 1
        return e[0]

    def put(self, key, batches, nbytes: int) -> None:
        if nbytes > self.limit_bytes:
            return  # larger than the whole tier: don't thrash
        old = self.entries.pop(key, None)
        if old is not None:
            self.ctx.add_bytes(-old[1])
        while self.entries and self.ctx.reserved + nbytes > self.limit_bytes:
            _, (_, old_bytes) = self.entries.popitem(last=False)
            self.ctx.add_bytes(-old_bytes)
        self.entries[key] = (batches, nbytes)
        self.ctx.add_bytes(nbytes)

    def clear(self) -> None:
        self.entries.clear()
        self.ctx.set_bytes(0)


class BufferPool:
    def __init__(
        self,
        host_limit_bytes: Optional[int] = None,
        device_limit_bytes: Optional[int] = None,
    ):
        if host_limit_bytes is None:
            host_limit_bytes = _env_bytes(
                "TRINO_TPU_HOST_CACHE_BYTES", 6 << 30
            )
        if device_limit_bytes is None:
            device_limit_bytes = _env_bytes(
                "TRINO_TPU_DEVICE_CACHE_BYTES", 8 << 30
            )
        self.host = _Tier("host", host_limit_bytes)
        self.device = _Tier("device", device_limit_bytes)
        self.lock = threading.Lock()

    @staticmethod
    def split_key(split, columns, page_rows: int, version) -> tuple:
        t = split.table
        return (
            t.catalog,
            t.schema,
            t.table,
            split.seq,
            split.row_start,
            split.row_count,
            tuple(columns),
            page_rows,
            version,
        )

    def get_device(self, key):
        with self.lock:
            return self.device.get(key)

    def put_device(self, key, batches) -> None:
        nbytes = sum(batch_bytes(b) for b in batches)
        with self.lock:
            self.device.put(key, list(batches), nbytes)

    def get_host(self, key):
        with self.lock:
            return self.host.get(key)

    def put_host(self, key, batches) -> None:
        nbytes = sum(batch_bytes(b) for b in batches)
        with self.lock:
            self.host.put(key, list(batches), nbytes)

    def invalidate_device(self, stale) -> int:
        """Drop device-tier entries whose key satisfies `stale(key)`;
        returns how many were dropped.  Used by membership's mesh-shrink
        re-planning to evict stacked-scan batches keyed by a mesh signature
        that no longer exists (runtime/membership.invalidate_mesh_scans)."""
        dropped = 0
        with self.lock:
            for key in [k for k in self.device.entries if stale(k)]:
                _, nbytes = self.device.entries.pop(key)
                self.device.ctx.add_bytes(-nbytes)
                dropped += 1
        return dropped

    def clear(self) -> None:
        with self.lock:
            self.host.clear()
            self.device.clear()

    def stats(self) -> dict:
        with self.lock:
            return {
                "host_bytes": self.host.ctx.reserved,
                "host_hits": self.host.hits,
                "host_misses": self.host.misses,
                "device_bytes": self.device.ctx.reserved,
                "device_hits": self.device.hits,
                "device_misses": self.device.misses,
            }


#: process-wide pool (the engine is one process per host, like a worker JVM)
POOL = BufferPool()
