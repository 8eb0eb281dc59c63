"""Batch: the engine's Page (reference: spi/Page.java:31).

A Batch is a tuple of equal-capacity Columns plus an optional boolean row mask.
Filtering ANDs the mask (never reallocates on device); operators that need
dense input (exchange partitioning, result rendering) compact explicitly.
Positional channels, not names — the planner tracks symbols->channels exactly
like the reference's LocalExecutionPlanner layout mapping.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from trino_tpu.columnar.column import Column
from trino_tpu.runtime.lifecycle import current_query
from trino_tpu.telemetry.programs import jit_program, note_path, recording_tracer
from trino_tpu.telemetry.spans import now


class Batch:
    __slots__ = ("columns", "row_mask")

    def __init__(self, columns: Sequence[Column], row_mask=None):
        self.columns = tuple(columns)
        self.row_mask = row_mask  # None => all rows live

    # -- shape ---------------------------------------------------------------

    @property
    def capacity(self) -> int:
        if self.columns:
            return self.columns[0].capacity
        if self.row_mask is not None:
            return self.row_mask.shape[0]
        return 0

    @property
    def width(self) -> int:
        return len(self.columns)

    def mask(self):
        """Materialized live-row mask, shape [capacity]."""
        if self.row_mask is None:
            return jnp.ones(self.capacity, dtype=bool)
        return self.row_mask

    def count(self):
        """Device scalar: number of live rows."""
        if self.row_mask is None:
            return jnp.asarray(self.capacity, dtype=jnp.int64)
        return jnp.sum(self.row_mask, dtype=jnp.int64)

    # -- transforms ----------------------------------------------------------

    def column(self, i: int) -> Column:
        return self.columns[i]

    def with_columns(self, columns: Sequence[Column]) -> "Batch":
        return Batch(columns, self.row_mask)

    def append_column(self, col: Column) -> "Batch":
        return Batch(self.columns + (col,), self.row_mask)

    def project(self, channels: Sequence[int]) -> "Batch":
        return Batch([self.columns[i] for i in channels], self.row_mask)

    def filter(self, keep_mask) -> "Batch":
        """AND a boolean mask into the live-row mask."""
        if self.row_mask is None:
            return Batch(self.columns, keep_mask)
        return Batch(self.columns, jnp.logical_and(self.row_mask, keep_mask))

    def gather(self, indices, valid=None) -> "Batch":
        """Row gather; `valid` marks which gathered slots are live."""
        cols = [c.gather(indices) for c in self.columns]
        if valid is None and self.row_mask is not None:
            valid = jnp.take(self.row_mask, indices, axis=0, mode="clip")
        return Batch(cols, valid)

    def compact_device(self, out_capacity: Optional[int] = None) -> "Batch":
        """Pack live rows to the front (stable): each output slot finds its
        source row (`slot_sources`), then every column gathers.

        Shape-stable: output capacity is static (`out_capacity` or input
        capacity); trailing slots are dead and read row 0.  When more rows
        are live than fit, the first `out_capacity` come out.  This is the
        selection-vector -> dense step the reference does in PageProcessor
        output.
        """
        outc = out_capacity or self.capacity
        inv, live = slot_sources(self.mask(), outc)
        cols = [c.gather(inv) for c in self.columns]
        return Batch(cols, live)

    # -- host-side -----------------------------------------------------------

    def device_put(self, device=None) -> "Batch":
        return jax.device_put(self, device)

    def block_until_ready(self) -> "Batch":
        for c in self.columns:
            if hasattr(c.data, "block_until_ready"):
                c.data.block_until_ready()
        return self

    def num_rows_host(self) -> int:
        """Live rows, read to the host: it sizes the next program's static
        capacity (one `row_count` launch, one `capacity` pull)."""
        if self.row_mask is None:
            return self.capacity
        if isinstance(self.row_mask, np.ndarray):  # a host batch
            return int(self.row_mask.sum())
        return int(host_pull(_ROW_COUNT(self.row_mask), "capacity"))

    def to_pylist(self) -> list[list]:
        """Rows of python values (live rows only, in order)."""
        host = host_pull(self, "result")
        rm = None if host.row_mask is None else np.asarray(host.row_mask)
        cols = [c.to_pylist(rm) for c in host.columns]
        return [list(r) for r in zip(*cols)] if cols else []

    def __repr__(self) -> str:  # pragma: no cover
        return f"Batch(cap={self.capacity}, width={self.width})"


def host_pull(tree, why: str):
    """The engine's one device->host door: `jax.device_get(tree)` with every
    leaf's transfer STARTED before any is awaited (one round trip for the
    whole pytree, not one per leaf), accounted for.

    Books on the executing statement's `QueryContext` one pull, the
    seconds this thread was blocked and the bytes of the device leaves;
    with `query_trace` on it also opens a `host_pull` span for the wait,
    carrying `why` (what the host needs the value for — the closed
    vocabulary is in `trino_tpu.telemetry`'s docstring), `bytes` and
    `after` (the `step` of the statement's newest launch: what the device
    was most likely still running).  The tracer is not thread-safe: a pull
    made off the statement's own thread is counted but records no span."""
    nbytes = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "copy_to_host_async"):
            nbytes += leaf.nbytes
            try:
                leaf.copy_to_host_async()
            except Exception:
                pass  # backend without async copies: plain get below
    ctx = current_query()
    if ctx is None:
        return jax.device_get(tree)
    tracer = recording_tracer(ctx)
    t0 = now()
    if tracer is None:
        out = jax.device_get(tree)
    else:
        with tracer.span(
            "host_pull", why=why, bytes=nbytes, after=ctx.last_step
        ):
            out = jax.device_get(tree)
    ctx.host_pulls += 1
    ctx.host_pull_s += now() - t0
    ctx.d2h_bytes += nbytes
    return out


#: rows of one block of `slot_sources`' sort: a batched sort of rows this
#: long compiles in 2-3 s whatever the capacity and sorts in on-chip memory;
#: one `lax.sort` of a whole 2^19..2^20-row plane compiled for 11-17 s a
#: variant (tools/compact_sweep.py, PERF.md section 6, PR 31)
_SORT_BLOCK = 4096


def slot_sources(m, outc: int):
    """(inv, live) of a stable compaction of the rows `m` marks into `outc`
    slots: `inv[j]` is the source row of slot j and `live[j] = j < n`, n the
    live rows; a dead slot reads row 0, and rows past the first `outc` live
    ones are left out.  Positions and counts are int32: the chip emulates
    int64 as two u32 planes.

    Never a scatter: the TPU serialises one at ~75 ns a SOURCE row whatever
    comes out (38.8 ms a 2^19-row split, 63-89 ms a 2^20-row one; this form
    1.1-4.2 ms and 1.2-13.7 ms: tools/compact_sweep.py, PERF.md section 6,
    PR 31).  A one-key sort in blocks instead: the key is the row number
    with bit 31 set on dead rows (unique, so no payload and no stability
    needed); sorted, each block of `_SORT_BLOCK` rows holds its live rows
    first, in order.  Slot j then lies in the block after those whose
    running total it has passed (a dense compare against at most
    capacity / `_SORT_BLOCK` totals), at its offset from that block's first
    slot: one gather of `outc` keys.
    """
    cap = m.shape[0]
    assert cap < (1 << 31) and outc < (1 << 31), (cap, outc)
    if cap == 0:
        return jnp.zeros(outc, jnp.int32), jnp.zeros(outc, bool)
    note_path("compact_sort")
    width = min(_SORT_BLOCK, cap)
    m = jnp.pad(m, (0, -cap % width)).reshape(-1, width)
    rows = jnp.arange(m.size, dtype=jnp.uint32).reshape(m.shape)
    blocks = jax.lax.sort(
        jnp.where(m, rows, rows | jnp.uint32(1 << 31)), dimension=1
    )
    count = jnp.sum(m, axis=1, dtype=jnp.int32)
    end = jnp.cumsum(count)
    slot = jnp.arange(outc, dtype=jnp.int32)
    live = slot < end[-1]
    passed = end[None, :] <= slot[:, None]
    block = jnp.sum(passed, axis=1, dtype=jnp.int32)
    start = jnp.sum(jnp.where(passed, count[None, :], 0), axis=1, dtype=jnp.int32)
    key = jnp.take(blocks.reshape(-1), block * width + (slot - start), mode="clip")
    inv = jnp.where(live, key & jnp.uint32(0x7FFF_FFFF), 0).astype(jnp.int32)
    return inv, live


#: jitted stable compaction (Batch.compact_device), ONE program object for
#: every operator that packs live rows to a static capacity bucket
COMPACT = jit_program(
    Batch.compact_device, "compact", static_argnames=("out_capacity",)
)

_ROW_COUNT = jit_program(
    lambda mask: jnp.sum(mask, dtype=jnp.int64), "row_count"
)


def _batch_flatten(b: Batch):
    return (b.columns, b.row_mask), None


def _batch_unflatten(aux, children):
    columns, row_mask = children
    return Batch(columns, row_mask)


jax.tree_util.register_pytree_node(Batch, _batch_flatten, _batch_unflatten)


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Host-side concat (used by accumulating operators between jit steps).

    Dictionary-encoded columns whose batches carry different dictionaries are
    recoded into a union dictionary (reference analog: DictionaryBlock
    compaction when appending across pages)."""
    assert batches
    width = batches[0].width
    cols = []
    for ch in range(width):
        parts = [b.columns[ch] for b in batches]
        dictionary = None
        dicts = [p.dictionary for p in parts]
        if any(d is not None for d in dicts):
            from trino_tpu.columnar.dictionary import union_many

            dictionary, tables = union_many(dicts)
            parts = [
                p
                if table is None
                else Column(
                    jnp.take(
                        jnp.asarray(table), jnp.asarray(p.data, jnp.int32), mode="clip"
                    ),
                    p.type,
                    p.valid,
                    dictionary,
                )
                for p, table in zip(parts, tables)
            ]
        lengths = None
        if any(p.lengths is not None for p in parts):
            # array columns: right-pad every part to the widest K.  Parts
            # with lengths=None carry 1-D data (no elements) and are lifted
            # to an all-empty [capacity, k] layout first.  Map columns pack
            # keys+values halves, so each half pads separately.
            from trino_tpu.types import MapType

            is_map = isinstance(parts[0].type, MapType)
            k = max(
                (p.data.shape[1] for p in parts if p.lengths is not None),
                default=1,
            )
            k = max(k, 2 if is_map else 1)

            def _lift(p):
                if p.lengths is None:
                    return Column(
                        jnp.zeros((p.capacity, k), dtype=p.data.dtype),
                        p.type,
                        p.valid,
                        p.dictionary,
                        jnp.zeros(p.capacity, jnp.int32),
                    )
                if p.data.shape[1] == k:
                    return p
                if is_map:
                    half = p.data.shape[1] // 2
                    pad = (k - p.data.shape[1]) // 2
                    data = jnp.concatenate(
                        [
                            jnp.pad(p.data[:, :half], ((0, 0), (0, pad))),
                            jnp.pad(p.data[:, half:], ((0, 0), (0, pad))),
                        ],
                        axis=1,
                    )
                else:
                    data = jnp.pad(
                        p.data, ((0, 0), (0, k - p.data.shape[1]))
                    )
                return Column(
                    data, p.type, p.valid, p.dictionary, p.lengths
                )

            parts = [_lift(p) for p in parts]
            lengths = jnp.concatenate(
                [
                    (
                        p.lengths
                        if p.lengths is not None
                        else jnp.zeros(p.capacity, jnp.int32)
                    )
                    for p in parts
                ]
            )
        data = jnp.concatenate([p.data for p in parts])
        if any(p.valid is not None for p in parts):
            valid = jnp.concatenate([p.valid_mask() for p in parts])
        else:
            valid = None
        c0 = parts[0]
        cols.append(Column(data, c0.type, valid, dictionary, lengths))
    if any(b.row_mask is not None for b in batches):
        mask = jnp.concatenate([b.mask() for b in batches])
    else:
        mask = None
    return Batch(cols, mask)
