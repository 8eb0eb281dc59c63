"""Graceful degradation under memory pressure: HBM-budgeted partition
waves, filesystem-SPI spill, and memory revocation.

Reference: the Trino revoke+spill machinery SURVEY.md §5.7 maps onto an
HBM-budgeted k-pass partition loop —

  * ``HashBuilderOperator.startMemoryRevoke:372`` — a blocking operator
    asked to give memory back spills its state and releases its
    reservation (here: :class:`RevocableOperator` + :class:`MemoryEscalation`);
  * ``GenericPartitioningSpiller`` — state hash-partitions by the exchange
    row hash and persists through the spill SPI (here: :class:`SpillManager`
    over the FTE ``SpoolManager`` npz format and the filesystem SPI);
  * ``SpillingJoinProcessor`` — spilled join partitions process in
    sequential waves (here: :func:`partition_wave_join` and the mesh wave
    hooks in ``parallel/runner``).

The escalation ladder a reservation climbs (enforced by
tests/test_spill.py):

  1. **budget** — blocking operators (join build, hash aggregation,
     order-by sort, window) reserve their device footprint on the
     lifecycle memory pool BEFORE materializing;
  2. **revoke** — when the shared pool blocks, the largest *registered
     revocable* operator is asked to spill a partition and release its
     reservation (``trino_tpu_memory_revocations_total``);
  3. **wave** — an operator whose own reservation cannot fit degrades to
     ``k = next_pow2(need / budget)`` hash-partition waves, spilling
     non-resident partitions host-side (``trino_tpu_memory_waves_total``,
     ``trino_tpu_spill_bytes_total``);
  4. **kill** — the LowMemoryKiller remains the last resort, its
     largest-victim choice unchanged (``trino_tpu_memory_kills_total``).

Zero-cost-when-idle: none of this engages without a budget —
tests/test_spill.py::test_mesh_wave_join_matches_local asserts that an
unconstrained run records zero waves, zero spill, zero revocations.
"""

from __future__ import annotations

import math
import threading
import uuid
from typing import Callable, Optional

import numpy as np

#: partition-wave fan-out ceiling (a 64-pass query is already degraded far
#: past useful; beyond this the killer is the kinder answer)
MAX_WAVES = 64


# -- budget arithmetic ---------------------------------------------------------


def session_budget(properties) -> int:
    """Per-query session budget in bytes: the smallest nonzero of
    ``query_max_memory`` and the legacy ``query_max_memory_bytes``."""
    vals = []
    if properties is not None:
        for knob in ("query_max_memory", "query_max_memory_bytes"):
            try:
                v = int(properties.get(knob))
            except KeyError:  # pragma: no cover - older property sets
                v = 0
            if v > 0:
                vals.append(v)
    return min(vals) if vals else 0


def effective_budget(properties=None, memory_ctx=None) -> int:
    """The per-query device budget in bytes (0 = unconstrained): the
    smallest nonzero of the ``query_max_memory`` session property (or the
    legacy ``query_max_memory_bytes``), the query context's own limit, and
    any ancestor pool limit (``memory.pool-limit-bytes``)."""
    candidates = []
    sb = session_budget(properties)
    if sb > 0:
        candidates.append(sb)
    node = memory_ctx
    while node is not None:
        if node.limit_bytes:
            candidates.append(int(node.limit_bytes))
        node = node.parent
    return min(candidates) if candidates else 0


def wave_count(need: int, budget: int, properties=None) -> int:
    """``k = next_pow2(need / budget)`` partition-wave fan-out, clamped to
    [2, MAX_WAVES]; the ``memory_wave_partitions`` session property
    overrides (bisection knob)."""
    if properties is not None:
        try:
            k = int(properties.get("memory_wave_partitions"))
        except KeyError:  # pragma: no cover - older property sets
            k = 0
        if k > 0:
            return max(2, min(MAX_WAVES, k))
    if budget <= 0:
        return 2
    from trino_tpu.ops.common import next_pow2

    return max(
        2,
        min(MAX_WAVES, next_pow2(max(1, math.ceil(need / budget)), floor=2)),
    )


def spill_to_disk(properties) -> bool:
    """The ``spill_enabled`` session knob: False stages non-resident wave
    partitions in host RAM instead of the filesystem SPI (bisection)."""
    if properties is None:
        return True
    try:
        return bool(properties.get("spill_enabled"))
    except KeyError:  # pragma: no cover - older property sets
        return True


# -- observability -------------------------------------------------------------


class PressureObserver:
    """Routes wave/spill events to the metrics registry plus an optional
    per-query sink (a StatsCollector locally, a MeshProfile on the mesh —
    anything with ``bump(name, n)``), so EXPLAIN ANALYZE and Prometheus
    tell the same story."""

    def __init__(self, sink=None):
        self.sink = sink

    def waves(self, operator: str, k: int) -> None:
        from trino_tpu.telemetry.metrics import memory_waves_counter

        memory_waves_counter().labels(operator).inc(k)
        if self.sink is not None:
            self.sink.bump("memory_wave", k)

    def spilled(self, nbytes: int) -> None:
        from trino_tpu.telemetry.metrics import spill_bytes_counter

        spill_bytes_counter().inc(nbytes)
        if self.sink is not None:
            self.sink.bump("spill_bytes", nbytes)


# -- the partitioning spiller --------------------------------------------------


class SpillManager:
    """Partitioned host-side spill store (GenericPartitioningSpiller role):
    persists lists of host batches per (tag, partition) through the FTE
    ``SpoolManager`` npz format, which itself rides the filesystem SPI —
    pointing ``memory.spill-dir`` at an object store becomes a
    configuration change the day a remote filesystem lands."""

    def __init__(self, directory: Optional[str] = None, observer=None):
        from trino_tpu.runtime.fte import SpoolManager

        if directory is None:
            from trino_tpu.config import get_config

            directory = get_config().memory.spill_dir or None
        self.spool = SpoolManager(directory)
        #: unique per manager so shared spill dirs never collide
        self._prefix = f"spill_{uuid.uuid4().hex[:12]}"
        #: (tag, part) -> (symbols, dictionaries): the schema needed to
        #: rehydrate (npz stores arrays, not types)
        self._meta: dict = {}
        self._seq: dict = {}
        self.bytes_spilled = 0
        self._closed = False
        self.observer = observer if observer is not None else PressureObserver()
        # abort hygiene: a query killed or canceled mid-wave abandons its
        # wave generator, whose finally-close only runs at GC — register
        # with the owning query's lifecycle so the statement-end path
        # (runner.execute / worker task finally) deletes our partitions
        # through the filesystem SPI immediately
        from trino_tpu.runtime.lifecycle import register_spill

        register_spill(self)

    def _fid(self, tag: str, part: int) -> int:
        key = (tag, part)
        fid = self._seq.get(key)
        if fid is None:
            fid = len(self._seq)
            self._seq[key] = fid
        return fid

    def save(self, tag: str, part: int, batches: list) -> int:
        """Spill host batches as one partition; returns bytes written.
        Dictionaries are unified across the partition's batches first so
        ONE dictionary list rehydrates every batch exactly."""
        from trino_tpu.ops.sort import _unify_host_dictionaries
        from trino_tpu.planner import plan as P
        from trino_tpu.runtime.memory import batches_bytes

        if not batches:
            return 0
        batches = _unify_host_dictionaries(list(batches))
        first = batches[0]
        symbols = [
            P.Symbol(f"c{i}", c.type) for i, c in enumerate(first.columns)
        ]
        self.spool.save(self._prefix + "_" + tag, self._fid(tag, part),
                        batches, symbols)
        self._meta[(tag, part)] = (
            symbols, [c.dictionary for c in first.columns]
        )
        nbytes = batches_bytes(batches)
        self.bytes_spilled += nbytes
        self.observer.spilled(nbytes)
        return nbytes

    def load(self, tag: str, part: int) -> list:
        """Rehydrate one partition's host batches ([] when the partition
        was empty and never written)."""
        meta = self._meta.get((tag, part))
        if meta is None:
            return []
        symbols, dicts = meta
        out = self.spool.load(
            self._prefix + "_" + tag, self._fid(tag, part), symbols, dicts
        )
        return out if out is not None else []

    def close(self) -> None:
        # idempotent: the abort path (lifecycle.release_spills) and the
        # wave loop's own finally may both close — a double delete of a
        # tempdir-owned spool would raise on the second fs.list
        if self._closed:
            return
        self._closed = True
        from trino_tpu.runtime.lifecycle import current_query

        ctx = current_query()
        if ctx is not None:
            ctx.unregister_spill(self)
        # a CONFIGURED spill dir is shared: the spool only removes
        # directories it created, and the orphan sweep is an hours-scale
        # backstop — delete our own partition files (we know every
        # (tag, part) we wrote) so sustained pressure cannot fill the disk
        for (tag, part), fid in list(self._seq.items()):
            if (tag, part) in self._meta:
                try:
                    self.spool.fs.delete(
                        self.spool._path(self._prefix + "_" + tag, fid)
                    )
                except OSError:  # pragma: no cover - already swept
                    pass
        self._meta.clear()
        self.spool.close()


class _DiskSide:
    """One operator input, hash-partitioned into k on-disk partitions."""

    def __init__(self, spiller: SpillManager, tag: str, n_parts: int):
        self.spiller = spiller
        self.tag = tag
        self.n_parts = n_parts

    def load_part(self, part: int) -> list:
        return self.spiller.load(self.tag, part)


class _RamSide:
    """spill_enabled=false fallback: partitions stay in host RAM."""

    def __init__(self, buckets: list):
        self.buckets = buckets
        self.n_parts = len(buckets)

    def load_part(self, part: int) -> list:
        return self.buckets[part]


def partition_side(host_batches: list, key_channels, k: int,
                   spiller: Optional[SpillManager], tag: str):
    """Hash-partition host batches by the exchange row hash (the
    value-stable host mirror, ``serde.stable_row_hash``) into k partitions;
    spilled to disk when a spiller is given, staged in RAM otherwise."""
    from trino_tpu.parallel.serde import partition_batches

    buckets = partition_batches(host_batches, list(key_channels), k)
    if spiller is None:
        return _RamSide(buckets)
    for part, bucket in enumerate(buckets):
        if bucket:
            spiller.save(tag, part, bucket)
        buckets[part] = None  # free RAM as partitions land on disk
    return _DiskSide(spiller, tag, k)


# -- partition-wave join (SpillingJoinProcessor role) --------------------------


def partition_wave_join(make_op, build_side, probe_side, n_waves: int,
                        ctx, observer: PressureObserver):
    """k-pass partition-wave join: each wave materializes only its slice of
    the build side on device while both sides re-feed from the spill tier.
    Partitioning both sides by the same key-value hash preserves exact
    results for inner/left/full joins — every potential match pair lands in
    the same wave, and each row is emitted by exactly one wave."""
    import jax

    from trino_tpu.runtime.memory import batches_bytes

    observer.waves("join", n_waves)
    for wave in range(n_waves):
        wave_build = [jax.device_put(b) for b in build_side.load_part(wave)]
        wave_bytes = batches_bytes(wave_build)
        if ctx is not None:
            # raw slice + compacted copy
            reserve_wave_working_set(ctx, 2 * wave_bytes)
        op = make_op()
        op.set_build(wave_build)
        del wave_build

        def probe_feed(w=wave):
            for hb in probe_side.load_part(w):
                yield jax.device_put(hb)

        yield from op.process(probe_feed())
        del op
    if ctx is not None:
        ctx.close()


def pull_host(*trees):
    """The spill tier's DECLARED host boundary: device values cross to
    host exactly here, immediately before being partitioned and spilled.
    Lives in runtime/ (not the linted device paths) because moving data
    off-device is this module's whole purpose."""
    from trino_tpu.columnar.batch import host_pull

    out = host_pull(tuple(trees), "spill")
    return out if len(out) > 1 else out[0]


def reserve_wave_working_set(ctx, nbytes: int) -> None:
    """Account one wave's working set on the reservation tree, BEST
    EFFORT: the wave path is already the degradation tier, so its own
    bookkeeping must never kill the query it is saving — when even a
    single wave cannot fit the (possibly further-shrunk) budget, the wave
    proceeds with the reservation pinned at whatever was admitted
    (reference analog: revocable memory is accounted outside the query
    limit in MemoryPool.getReservedRevocableBytes)."""
    from trino_tpu.runtime.memory import ExceededMemoryLimitException

    try:
        ctx.set_bytes(nbytes)
    except ExceededMemoryLimitException:
        pass


# -- memory revocation (startMemoryRevoke role) --------------------------------


class RevocableOperator:
    """A registered wave-capable blocking operator: when the shared pool
    blocks, the escalation hook asks the largest one to spill its state
    and release its reservation instead of shooting a query.

    The handle's lock serializes the revoker (another query's thread)
    against the owner: ``revoke()`` runs the spill callback under it, and
    the owner's ``revoked`` reads take it too — an owner that observes
    ``revoked == True`` is guaranteed the spill completed."""

    def __init__(self, operator: str, ctx, spill_fn: Callable[[], int]):
        self.operator = operator
        self.ctx = ctx
        self._spill_fn = spill_fn
        #: REENTRANT on purpose: owners guard their own state mutations
        #: with it too, and an owner-thread reservation that triggers the
        #: escalation hook may revoke its OWN handle (self-revocation —
        #: spill yourself before the killer shoots someone)
        self.lock = threading.RLock()
        self._revoked = False
        self._done = False

    @property
    def revoked(self) -> bool:
        with self.lock:
            return self._revoked

    def reserved_bytes(self) -> int:
        """Ranking key for victim choice (a point-in-time read)."""
        return int(self.ctx.reserved) if self.ctx is not None else 0

    def revoke(self) -> int:
        """Spill + release; returns bytes freed (0 when already revoked or
        finished — the registry then tries the next candidate)."""
        with self.lock:
            if self._revoked or self._done:
                return 0
            freed = int(self._spill_fn() or 0)
            self._revoked = True
        REVOCABLES.unregister(self)
        return freed

    def finish(self) -> None:
        """Owner completed (normally or not): no longer revocable."""
        with self.lock:
            self._done = True
        REVOCABLES.unregister(self)


class RevocableRegistry:
    """Process-wide registry the escalation hook consults (reference role:
    the ClusterMemoryManager's taskMemoryRevoking candidates)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: list = []

    def register(self, handle: RevocableOperator) -> RevocableOperator:
        with self._lock:
            self._entries.append(handle)
        return handle

    def unregister(self, handle) -> None:
        with self._lock:
            if handle in self._entries:
                self._entries.remove(handle)

    def live(self) -> list:
        with self._lock:
            return list(self._entries)

    def revoke_largest(self) -> int:
        """Ask the largest-reservation revocable to spill; falls through to
        smaller ones if the largest races to completion first.  Returns
        bytes freed (0 = nothing revocable)."""
        for h in sorted(
            self.live(), key=lambda e: e.reserved_bytes(), reverse=True
        ):
            freed = h.revoke()
            if freed > 0:
                return freed
        return 0


#: the process registry (cleared by tests via REVOCABLES._entries checks)
REVOCABLES = RevocableRegistry()


class MemoryEscalation:
    """Pool-root ``on_exceeded`` hook: the revoke tier runs BEFORE the
    low-memory killer — spilling a cooperative operator is strictly kinder
    than shooting a query, and the killer's largest-victim semantics are
    unchanged when revocation cannot free the shortfall."""

    def __init__(self, killer=None):
        if killer is None:
            from trino_tpu.runtime.lifecycle import LowMemoryKiller

            killer = LowMemoryKiller()
        self.killer = killer

    def __call__(self, pool_root, requesting, delta: int) -> bool:
        freed = REVOCABLES.revoke_largest()
        if freed > 0:
            from trino_tpu.telemetry.metrics import (
                memory_revocations_counter,
            )

            memory_revocations_counter().inc()
            return True  # something released: retry the reservation
        return self.killer(pool_root, requesting, delta)


# -- host-side wave slicing (shared by agg/window waves) -----------------------


def host_wave_slice(hb, key_channels: list, n_waves: int, wave: int):
    """Rows of a HOST batch whose key VALUE hash lands in `wave`, compacted
    to a dense host batch (None when empty).  Value hashing (not code
    hashing) keeps groups whole across batches with batch-local
    dictionaries."""
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.parallel.serde import stable_row_hash

    h = stable_row_hash(hb, key_channels)
    keep = np.asarray(hb.mask()) & ((h % np.uint64(n_waves)) == np.uint64(wave))
    n = int(keep.sum())
    if n == 0:
        return None
    idx = np.nonzero(keep)[0]
    cols = []
    for c in hb.columns:
        cols.append(
            Column(
                np.asarray(c.data)[idx],
                c.type,
                None if c.valid is None else np.asarray(c.valid)[idx],
                c.dictionary,
                None if c.lengths is None else np.asarray(c.lengths)[idx],
            )
        )
    return Batch(cols, np.ones(n, dtype=bool))


class SpillingAccumulator:
    """Bounded accumulation of host batches with an optional disk tier:
    chunks pushed over the course of a stream land in RAM or (spiller
    given) the filesystem SPI, and are re-read chunk-at-a-time per wave.
    The shared shape under the agg-state / window / raw-input wave
    streams."""

    def __init__(self, spiller: Optional[SpillManager], tag: str):
        self.spiller = spiller
        self.tag = tag
        self._chunks: list = []  # part index (disk) or [host batches] (ram)
        self.total_bytes = 0

    def push_chunk(self, host_batches: list) -> None:
        from trino_tpu.runtime.memory import batches_bytes

        if not host_batches:
            return
        self.total_bytes += batches_bytes(host_batches)
        if self.spiller is not None:
            part = len(self._chunks)
            self.spiller.save(self.tag, part, list(host_batches))
            self._chunks.append(part)
        else:
            self._chunks.append(list(host_batches))

    def __len__(self) -> int:
        return len(self._chunks)

    def chunks(self):
        """Iterate chunk-at-a-time (one chunk resident in RAM when disk-
        backed): yields lists of host batches."""
        for c in self._chunks:
            if isinstance(c, int):
                yield self.spiller.load(self.tag, c)
            else:
                yield c

    def wave_parts(self, key_channels: list, n_waves: int, wave: int) -> list:
        """Every chunk's slice for one wave (host batches).

        Disk-backed chunks are re-read once PER WAVE (k x total read
        amplification).  Deliberate for the state-wave consumers: k is
        only known after the last chunk lands, and agg/window states are
        compacted partials, typically orders of magnitude smaller than
        the raw input.  The join paths — where the spilled data IS the
        raw input — partition at write time instead (partition_side) and
        read each wave exactly once."""
        parts = []
        for chunk in self.chunks():
            for hb in chunk:
                p = host_wave_slice(hb, key_channels, n_waves, wave)
                if p is not None:
                    parts.append(p)
        return parts
