"""Ordering operators (reference: OrderByOperator.java, TopNOperator.java:35,
LimitOperator.java, DistinctLimitOperator.java).

TopN keeps a bounded device state: each pushed batch is merged with the
current top-N candidates and re-truncated — the TPU analog of the reference's
TopNProcessor heap, with `lax.sort` doing the heap's job (SURVEY.md §7 maps
TopNOperator to top_k/sort).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from trino_tpu.columnar import Batch
from trino_tpu.columnar.batch import COMPACT, concat_batches, host_pull
from trino_tpu.ops.common import SortKey, multi_key_sort_perm, next_pow2
from trino_tpu.ops.aggregation import _pad_device
from trino_tpu.telemetry.programs import jit_program


#: shared jitted steps across per-query instances (see filter_project)
_STEP_CACHE: dict = {}


class OrderByOperator:
    """Full materialized sort; emits one sorted, compacted batch."""

    def __init__(self, keys: Sequence[SortKey], memory_ctx=None,
                 spill_factory=None, observer=None):
        self.keys = list(keys)
        self.memory_ctx = memory_ctx
        #: lazy filesystem-SPI spill store (runtime/spill.SpillManager)
        #: for over-budget runs; None / factory-returns-None = host RAM
        self._spill_factory = spill_factory
        self._spiller = None
        self._spiller_made = False
        self._spill_runs = 0
        self.observer = observer
        self._acc: list[Batch] = []
        key = ("orderby", tuple(keys))
        if key not in _STEP_CACHE:
            _STEP_CACHE[key] = jit_program(self._sort_step, "sort")
        self._step = _STEP_CACHE[key]

    def _sort_step(self, batch: Batch) -> Batch:
        perm = multi_key_sort_perm(batch, self.keys)
        live = jnp.take(batch.mask(), perm, mode="clip")
        return batch.gather(perm, valid=live)

    def _get_spiller(self):
        if not self._spiller_made:
            self._spiller_made = True
            if self._spill_factory is not None:
                self._spiller = self._spill_factory()
        return self._spiller

    def _spill_chunk(self) -> object:
        """Compact the accumulated batches to live rows and move them OFF
        device as one spill run — to the filesystem SPI when a spiller is
        attached (reference: GenericSpiller in OrderByOperator.java's
        revoke path), host RAM otherwise.  Runs are NOT per-run sorted:
        the finish-time merge is a full host lexsort, so a per-run device
        sort would be thrown-away work; the single-run case re-sorts on
        device at finish.  Returns the host run, or an int disk-run id."""
        big = self._acc[0] if len(self._acc) == 1 else concat_batches(self._acc)
        self._acc.clear()
        n = big.num_rows_host()
        cap = next_pow2(max(n, 1), floor=1)
        host = host_pull(COMPACT(big, out_capacity=cap), "sort_compact")
        spiller = self._get_spiller()
        if spiller is None:
            return host
        run = self._spill_runs
        self._spill_runs += 1
        spiller.save("run", run, [host])
        return run

    def _load_runs(self, runs: list) -> list:
        """Rehydrate disk-run ids back to host batches (in-RAM runs pass
        through).  The merge is ONE vectorized host lexsort over all runs,
        so host-RAM peak at finish equals the in-RAM staging path — the
        SPI spill buys DEVICE residency (runs leave HBM as they form) and
        the object-store-ready storage seam, not a host peak reduction;
        an incremental k-way merge is the follow-up that would."""
        spiller = self._spiller
        return [
            spiller.load("run", r)[0] if isinstance(r, int) else r
            for r in runs
        ]

    def process(self, stream):
        """In-memory device sort; over budget, fall back to an EXTERNAL sort
        (reference: OrderingCompiler + spiller/ GenericSpiller usage in
        OrderByOperator.java — revoke memory by spilling runs, sort at
        finish).  Spill runs live UNSORTED in host RAM; the finish step is
        one vectorized host lexsort over all runs (the merge exchange's
        kernel), so device memory stays bounded by one chunk."""
        from trino_tpu.runtime.memory import (
            ExceededMemoryLimitException,
            batches_bytes,
        )

        runs: list = []
        try:
            for b in stream:
                self._acc.append(b)
                if self.memory_ctx is not None:
                    # recomputed over the accumulation so a dictionary
                    # shared by every batch is counted once, not per batch
                    try:
                        self.memory_ctx.set_bytes(batches_bytes(self._acc))
                    except ExceededMemoryLimitException:
                        runs.append(self._spill_chunk())
                        self.memory_ctx.set_bytes(0)
            if not self._acc and not runs:
                return
            if not runs:
                big = self._acc[0] if len(self._acc) == 1 else concat_batches(self._acc)
                big = _pad_device(big, next_pow2(big.capacity, floor=1))
                out = self._step(big)
                if self.memory_ctx is not None:
                    self.memory_ctx.close()
                yield out
                return
            if self._acc:
                runs.append(self._spill_chunk())
            if self.observer is not None:
                # external-sort waves: one run merged per pass slice
                self.observer.waves("sort", len(runs))
            runs = self._load_runs(runs)
            if len(runs) == 1:
                # one run = the budget tripped at the very end; a device sort
                # of the whole set is what the in-memory path would have done
                big = jax.device_put(runs[0])
                out = self._step(_pad_device(big, next_pow2(big.capacity, floor=1)))
                if self.memory_ctx is not None:
                    self.memory_ctx.close()
                yield out
                return
            from trino_tpu.ops.merge import merge_sorted_shards

            runs = _unify_host_dictionaries(runs)
            out = merge_sorted_shards(runs, self.keys)
            if self.memory_ctx is not None:
                self.memory_ctx.close()
            yield out
        finally:
            if self._spiller is not None:
                self._spiller.close()


class TopNOperator:
    def __init__(self, keys: Sequence[SortKey], n: int):
        self.keys = list(keys)
        self.n = n
        self._state: Optional[Batch] = None
        key = ("topn", tuple(keys), n)
        if key not in _STEP_CACHE:
            _STEP_CACHE[key] = jit_program(
                self._merge_step, "sort_merge", static_argnames=("out_cap",)
            )
        self._step = _STEP_CACHE[key]

    def _merge_step(self, batch: Batch, out_cap: int) -> Batch:
        perm = multi_key_sort_perm(batch, self.keys)
        live = jnp.take(batch.mask(), perm, mode="clip")
        # keep only first n live rows
        rank = jnp.cumsum(live) - 1
        keep = jnp.logical_and(live, rank < self.n)
        out = batch.gather(perm, valid=keep)
        return _truncate(out, out_cap)

    def process(self, stream):
        out_cap = next_pow2(self.n, floor=1)
        for b in stream:
            if self._state is not None:
                b = concat_batches([self._state, b])
            b = _pad_device(b, next_pow2(b.capacity, floor=1))
            self._state = self._step(b, out_cap=out_cap)
        if self._state is not None:
            yield self._state


class LimitOperator:
    """LIMIT/OFFSET without ordering; truncates the stream host-side
    (reference: LimitOperator.java + OffsetOperator.java).  count=None
    means OFFSET-only (skip, keep the rest)."""

    def __init__(self, n, offset: int = 0):
        self.n = n
        self.offset = offset

    def process(self, stream):
        skip = self.offset
        remaining = self.n  # None = unlimited
        for b in stream:
            if remaining is not None and remaining <= 0:
                break
            cnt = b.num_rows_host()
            if skip >= cnt:
                skip -= cnt
                continue
            if skip > 0 or (remaining is not None and cnt - skip > remaining):
                live = b.mask()
                rank = jnp.cumsum(live) - 1
                keep = jnp.logical_and(live, rank >= skip)
                if remaining is not None:
                    keep = jnp.logical_and(keep, rank < skip + remaining)
                    remaining -= min(cnt - skip, remaining)
                yield b.filter(keep)
                skip = 0
            else:
                remaining = None if remaining is None else remaining - (cnt - skip)
                skip = 0
                yield b


def _unify_host_dictionaries(runs: list) -> list:
    """Spill runs from different scan batches may carry per-run
    dictionaries; recode every string channel into one union dictionary so
    the merge's code comparisons are rank comparisons again."""
    import numpy as np

    from trino_tpu.columnar import Column
    from trino_tpu.columnar.dictionary import union_many

    if not runs:
        return runs
    width = runs[0].width
    out = [list(r.columns) for r in runs]
    for ch in range(width):
        dicts = [r.columns[ch].dictionary for r in runs]
        if not any(d is not None for d in dicts):
            continue
        merged, tables = union_many(dicts)
        for i, table in enumerate(tables):
            c = out[i][ch]
            data = np.asarray(c.data)
            if table is not None:
                data = np.asarray(table)[np.clip(data.astype(np.int64), 0, len(table) - 1)]
            out[i][ch] = Column(data, c.type, c.valid, merged, c.lengths)
    return [Batch(cols, r.row_mask) for cols, r in zip(out, runs)]


def _truncate(batch: Batch, cap: int) -> Batch:
    """Slice the leading `cap` rows (used after sorts put keepers first)."""
    from trino_tpu.columnar import Column

    cols = [
        Column(
            c.data[:cap],
            c.type,
            None if c.valid is None else c.valid[:cap],
            c.dictionary,
            None if c.lengths is None else c.lengths[:cap],
        )
        for c in batch.columns
    ]
    return Batch(cols, batch.mask()[:cap])
