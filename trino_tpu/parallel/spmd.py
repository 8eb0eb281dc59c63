"""Stacked-batch SPMD utilities.

A distributed batch is an ordinary Batch whose leaves carry a leading worker
axis [W, cap], sharded over the mesh's `workers` axis.  Every per-worker
operator step runs under shard_map with the same pure step function the local
engine jits — the reference's "same operator code on every worker task"
property (SqlTaskExecution), realized as SPMD.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from trino_tpu.columnar import Batch, Column
from trino_tpu.ops.common import next_pow2
from trino_tpu.telemetry.compile_events import OBSERVATORY
from trino_tpu.telemetry.programs import jit_program


class TraceCache:
    """Process-wide cache of jitted SPMD programs, keyed on the step's
    semantic fingerprint + shape bucket (reference role: the task-level
    operator-factory reuse a long-lived worker gets for free; here the jit
    wrapper IS the compiled task, so a fresh closure per execution would
    retrace and recompile every fragment every query).

    Keys must capture everything the step closure bakes in that is not a
    traced argument or pytree aux data: expression fingerprints, static
    capacities, dynamic-filter ranges, mesh signature.  Dictionaries and
    dtypes ride as pytree aux, so jax's own jit cache retraces on their
    change — `retraces` counts those trace-time executions (zero after
    warmup for repeated same-bucket batches)."""

    def __init__(self, limit: int = 512):
        self.limit = limit
        self._fns: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.retraces = 0
        #: entries dropped by the LRU bound — manifest coverage vs cache
        #: pressure: a prewarm manifest larger than the cache limit churns
        self.evictions = 0
        #: wall seconds spent inside calls that traced (trace + XLA compile)
        self.trace_s = 0.0
        #: audit hook (verify.cache_key_audit): called as audit(key, build)
        #: on EVERY get — hits included — so cache-key completeness (same key
        #: => same step-closure semantics) is checked against live traffic
        self.audit: Optional[Callable] = None

    def get(self, key, build: Callable):
        audit = self.audit  # snapshot: a concurrent audit-exit may null it
        if audit is not None:
            audit(key, build)
        with self._lock:
            fn = self._fns.get(key)
            if fn is not None:
                self._fns.move_to_end(key)
                self.hits += 1
                return fn
        # miss: a trace+compile is coming — open the structured compile
        # event (the launch site attributes wall/bucket/fragment at close)
        ev = OBSERVATORY.open_miss(key)
        try:
            fn = build()
        except BaseException:
            # a failed build never compiles: withdraw the open event so the
            # NEXT traced launch doesn't inherit it (and its wall share)
            OBSERVATORY.abort(ev)
            raise
        with self._lock:
            self.misses += 1
            self._fns[key] = fn
            while len(self._fns) > self.limit:
                self._fns.popitem(last=False)
                self.evictions += 1
        return fn

    def stats(self) -> dict:
        with self._lock:  # counters + len(dict) move under the lock
            return {
                "entries": len(self._fns),
                "hits": self.hits,
                "misses": self.misses,
                "retraces": self.retraces,
                "evictions": self.evictions,
                "trace_s": round(self.trace_s, 4),
            }

    def clear(self) -> None:
        with self._lock:
            self._fns.clear()


#: the process-wide cache (cleared only by tests / explicit calls)
TRACE_CACHE = TraceCache()


#: local dir the persistent XLA cache currently points at (None = off);
#: TRACE_CACHE dies with the process, this survives it — a restarted worker
#: re-traces every key but reloads the XLA executable from disk
PERSISTENT_CACHE_DIR: Optional[str] = None

#: where compiled programs persist when nothing else says: ONE fixed path
#: inside the checkout (.gitignore'd).  The path is part of JAX's cache key
#: lookup, so a directory named by a temp name, pid or time never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_persistent_cache(
    cache_dir: str = "",
    enabled: bool = True,
    min_compile_time_s: float = 0.0,
    min_entry_size_bytes: int = -1,
) -> Optional[str]:
    """THE one rule for where compiled programs persist; every entry point
    (chip_smoke.py, benchmark/, the CLI/server start-up, tests/conftest.py,
    `compile-cache.*` config installs) goes through here and nothing else
    touches `jax_compilation_cache_dir`:

      1. `JAX_COMPILATION_CACHE_DIR` set in the environment -> JAX reads it
         itself; no directory is set in code, whatever the config says.
      2. otherwise `cache_dir` (a deployment's explicit `compile-cache.dir`)
         when given, else DEFAULT_CACHE_DIR.  `enabled=False` detaches.

    Returns the directory in effect (None when detached).  Policy,
    filesystem-SPI resolution and warnings for configured dirs live in
    runtime/prewarm.enable_persistent_compile_cache."""
    global PERSISTENT_CACHE_DIR
    from jax.experimental.compilation_cache import compilation_cache

    # persist everything: jax's defaults skip sub-second compiles, which is
    # most of a query's programs
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_time_s)
    )
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", int(min_entry_size_bytes)
    )
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        PERSISTENT_CACHE_DIR = from_env
        return from_env
    target = (cache_dir or DEFAULT_CACHE_DIR) if enabled else None
    if target != jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", target)
        # jax initializes its cache AT MOST ONCE, at the first compile — a
        # dir configured after that (a server installing config
        # post-import, or a dir change) is ignored without a reset
        compilation_cache.reset_cache()
    PERSISTENT_CACHE_DIR = target
    return target


def mesh_key(wm: "WorkerMesh") -> tuple:
    """Stable fingerprint of the mesh for trace-cache keys."""
    return (wm.n, tuple(str(d) for d in wm.devices))


def bucket_cap(n: int, floor: int = 64) -> int:
    """Pow2 shape bucket for batch capacities: a small set of distinct
    shapes so (fragment, bucket)-keyed traces are reused across batches."""
    return next_pow2(max(1, n), floor=floor)


class WorkerMesh:
    """The engine's view of the device mesh (reference role: the worker set
    managed by DiscoveryNodeManager / NodeScheduler)."""

    def __init__(self, devices: Optional[Sequence] = None, n_workers: Optional[int] = None):
        devs = list(devices if devices is not None else jax.devices())
        if n_workers is not None:
            devs = devs[:n_workers]
        self.devices = devs
        self.mesh = Mesh(np.array(devs), ("workers",))
        self.n = len(devs)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P("workers"))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def _pad_host(col_data: np.ndarray, cap: int, fill=0) -> np.ndarray:
    if col_data.shape[0] == cap:
        return col_data
    pad = np.full((cap - col_data.shape[0],) + col_data.shape[1:], fill, dtype=col_data.dtype)
    return np.concatenate([col_data, pad])


def stack_batches(batches: Sequence[Optional[Batch]], wm: WorkerMesh, cap: Optional[int] = None) -> Batch:
    """Stack one host Batch per worker (None = empty) into a sharded [W, cap]
    stacked batch.  Dictionaries are unioned so codes are comparable across
    workers (exchange serde role)."""
    from trino_tpu.columnar.batch import concat_batches
    from trino_tpu.columnar.dictionary import union_many

    real = [b for b in batches if b is not None and b.width]
    assert real, "stack_batches needs at least one non-empty batch"
    width = real[0].width
    types = [c.type for c in real[0].columns]
    cap = cap or next_pow2(max(b.capacity for b in real), floor=1)

    # union dictionaries per channel
    dicts_per_ch = []
    tables_per_ch = []
    for ch in range(width):
        dicts = [
            (b.columns[ch].dictionary if b is not None and b.width else None)
            for b in batches
        ]
        if any(d is not None for d in dicts):
            # empty workers have no dictionary; give them the first real one
            # (their slots are dead rows, codes never read)
            fallback = next(d for d in dicts if d is not None)
            d, tables = union_many([d if d is not None else fallback for d in dicts])
        else:
            d, tables = None, [None] * len(batches)
        dicts_per_ch.append(d)
        tables_per_ch.append(tables)

    cols = []
    for ch in range(width):
        datas, valids, lens = [], [], []
        any_valid = any(
            b is not None and b.width and b.columns[ch].valid is not None for b in batches
        )
        any_lengths = any(
            b is not None and b.width and b.columns[ch].lengths is not None
            for b in batches
        )
        # array columns: pad every worker's K to the widest
        k = 0
        if any_lengths:
            k = max(
                b.columns[ch].data.shape[1]
                for b in batches
                if b is not None and b.width
            )
        from trino_tpu.types import DecimalType as _Dec

        is_long_dec = isinstance(types[ch], _Dec) and types[ch].is_long
        for wi, b in enumerate(batches):
            if b is None or not b.width:
                if any_lengths:
                    shape = (cap, k)
                elif is_long_dec:
                    shape = (cap, 2)  # limb planes
                else:
                    shape = (cap,)
                datas.append(np.zeros(shape, dtype=types[ch].np_dtype))
                valids.append(np.zeros(cap, dtype=bool))
                if any_lengths:
                    lens.append(np.zeros(cap, dtype=np.int32))
                continue
            c = b.columns[ch]
            data = np.asarray(c.data)
            if is_long_dec and data.ndim == 1:
                # short-valued rows under a long type: widen to planes
                data = np.stack([data >> 63, data], axis=-1)
            if any_lengths and data.shape[1] < k:
                data = np.pad(data, ((0, 0), (0, k - data.shape[1])))
            table = tables_per_ch[ch][wi]
            if table is not None:
                data = np.asarray(table)[data.astype(np.int64)]
            datas.append(_pad_host(data, cap))
            v = (
                np.asarray(c.valid)
                if c.valid is not None
                else np.ones(data.shape[0], dtype=bool)
            )
            valids.append(_pad_host(v, cap))
            if any_lengths:
                lens.append(
                    _pad_host(np.asarray(c.lengths), cap)
                    if c.lengths is not None
                    else np.zeros(cap, dtype=np.int32)
                )
        stacked = np.stack(datas)
        valid = np.stack(valids) if any_valid else None
        lengths = np.stack(lens) if any_lengths else None
        cols.append(
            Column(stacked, types[ch], valid, dicts_per_ch[ch], lengths)
        )
    masks = []
    for b in batches:
        if b is None or not b.width:
            masks.append(np.zeros(cap, dtype=bool))
        else:
            masks.append(_pad_host(np.asarray(b.mask()), cap, fill=False))
    mask = np.stack(masks)
    out = Batch(cols, mask)
    return jax.device_put(out, wm.sharding())


def unstack_batch(stacked: Batch) -> Batch:
    """[W, cap] stacked batch -> one flat host Batch [W*cap] (the gather-to-
    coordinator exchange; reference: final stage output buffer read)."""
    cols = []
    for c in stacked.columns:
        d = np.asarray(c.data)
        data = d.reshape((-1,) + d.shape[2:])  # keep array-element trailing dims
        valid = None if c.valid is None else np.asarray(c.valid).reshape(-1)
        lengths = None if c.lengths is None else np.asarray(c.lengths).reshape(-1)
        cols.append(Column(data, c.type, valid, c.dictionary, lengths))
    mask = np.asarray(stacked.mask()).reshape(-1)
    return Batch(cols, mask)


def spmd_step(wm: WorkerMesh, step: Callable, name: str,
              out_replicated: bool = False):
    """Lift a per-worker pure Batch step into a jitted SPMD program named
    `name` (the launch door, telemetry/programs.py).

    `step` sees a worker-local Batch (no leading axis) and returns one; the
    wrapper maps it over the mesh with shard_map, squeezing the local [1, cap]
    shard view to [cap].  The python body only runs while jax traces — each
    run bumps TRACE_CACHE.retraces, so "zero retraces after warmup" is a
    measured fact, not an assumption."""

    def local(*args):
        TRACE_CACHE.retraces += 1
        squeezed = jax.tree.map(lambda x: x[0], list(args))
        out = step(*squeezed)
        return jax.tree.map(lambda x: x[None], out)

    inner = jax.shard_map(
        local,
        mesh=wm.mesh,
        in_specs=P("workers"),
        out_specs=P() if out_replicated else P("workers"),
        check_vma=False,
    )
    return jit_program(inner, name)


def spmd_collective_step(wm: WorkerMesh, step: Callable, name: str,
                         out_replicated: bool = False):
    """Like spmd_step but `step` may use collectives over axis name
    'workers' (all_to_all / all_gather / psum); the local shard view keeps
    its leading axis of 1 so collective outputs shape naturally."""

    def traced(*args):
        TRACE_CACHE.retraces += 1
        return step(*args)

    inner = jax.shard_map(
        traced,
        mesh=wm.mesh,
        in_specs=P("workers"),
        out_specs=P() if out_replicated else P("workers"),
        check_vma=False,
    )
    return jit_program(inner, name)


def step_name(key: tuple, collective: bool = False) -> str:
    """The program name of a `cached_spmd_step` key: its kind (`key[0]`);
    a deferred `chain` appends the kinds of the steps it fused, in order;
    a program with collectives ends in `_x`.  No fingerprints, literals or
    fragment ids: those are in the `launch` span's attributes."""
    def kind(k):
        return kind(k[0]) if isinstance(k, tuple) else str(k)

    name = kind(key)
    if name == "chain":
        name = "_".join(dict.fromkeys(["chain", *map(kind, key[1:])]))
    return name + "_x" if collective else name


def cached_spmd_step(
    wm: WorkerMesh,
    key: tuple,
    build_step: Callable,
    out_replicated: bool = False,
    collective: bool = False,
    name: Optional[str] = None,
):
    """TRACE_CACHE-backed spmd_step: `build_step()` constructs the per-worker
    step closure only on a cache miss.  `key` must fingerprint the step's
    semantics (expression text, static caps, mesh) — see TraceCache.  The
    program is named `name`, by default `step_name(key, collective)`."""
    lift = spmd_collective_step if collective else spmd_step
    return TRACE_CACHE.get(
        ("spmd", collective, out_replicated, mesh_key(wm)) + tuple(key),
        lambda: lift(
            wm, build_step(), name or step_name(key, collective),
            out_replicated=out_replicated,
        ),
    )
