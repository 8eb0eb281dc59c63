"""UNNEST operator: expand array columns into rows.

Reference: core/trino-main/.../operator/unnest/UnnestOperator.java (+
UnnestBlockBuilder): each input row is replicated once per element of its
unnested array(s); multiple arrays zip, padding the shorter with NULLs;
WITH ORDINALITY appends the 1-based element index.

TPU design: arrays are rectangular [cap, K] blocks (columnar/column.py), so
unnest is a static-shape reshape — replicate row r to K output slots, mask
slot (r, k) live iff k < max(lengths_i[r]).  Output capacity is cap*K; the
driver compacts at the next boundary.  No per-row host loop, no dynamic
shapes: one jitted gather per batch.
"""

from __future__ import annotations

import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.columnar import Batch, Column
from trino_tpu.expr import ExprCompiler
from trino_tpu.expr.ir import Expr
from trino_tpu.telemetry.programs import jit_program

_STEP_CACHE: dict = {}


class UnnestOperator:
    """`exprs` evaluate to array values over the input batch; `replicate` is
    every pass-through input channel."""

    def __init__(self, exprs, with_ordinality: bool = False):
        self.exprs = list(exprs)
        self.with_ordinality = with_ordinality
        key = (
            tuple(e.key() for e in self.exprs),
            with_ordinality,
        )
        #: un-jitted step for callers that wrap it in their own program
        #: (the SPMD executor jits it inside shard_map)
        self.raw_step = self._make_step()
        cached = _STEP_CACHE.get(key)
        if cached is None:
            cached = jit_program(self.raw_step, "unnest")
            _STEP_CACHE[key] = cached
        self._step = cached

    def _make_step(self):
        exprs, with_ord = self.exprs, self.with_ordinality

        def step(batch: Batch):
            c = ExprCompiler(batch)
            arrays = []
            for e in exprs:
                v = c.value(e)
                if v.lengths is None:
                    raise NotImplementedError("UNNEST of non-array value")
                k_e = v.data.shape[-1]
                data = jnp.broadcast_to(
                    jnp.asarray(v.data), (batch.capacity, k_e)
                )
                lens = jnp.broadcast_to(
                    jnp.asarray(v.lengths, jnp.int32), (batch.capacity,)
                )
                if v.valid is not None and v.valid is not False:
                    lens = jnp.where(v.valid, lens, 0)
                elif v.valid is False:
                    lens = jnp.zeros_like(lens)
                arrays.append((data, lens, v))
            k = max(1, max(a[0].shape[1] for a in arrays))
            cap = batch.capacity
            pos = jnp.arange(k, dtype=jnp.int32)[None, :]  # [1, K]
            max_lens = arrays[0][1]
            for _, lens, _v in arrays[1:]:
                max_lens = jnp.maximum(max_lens, lens)
            live2 = jnp.logical_and(
                batch.mask()[:, None], pos < max_lens[:, None]
            )  # [cap, K]
            out_mask = live2.reshape(cap * k)
            # replicated source columns: row index repeats K times
            rep = jnp.repeat(jnp.arange(cap, dtype=jnp.int64), k)
            cols = [col.gather(rep) for col in batch.columns]
            # element columns
            for data, lens, v in arrays:
                k_e = data.shape[1]
                if k_e < k:
                    data = jnp.pad(data, ((0, 0), (0, k - k_e)))
                flat = data.reshape(cap * k)
                evalid = (pos < lens[:, None]).reshape(cap * k)
                cols.append(
                    Column(flat, v.type.element, evalid, v.dictionary)
                )
            if with_ord:
                ordv = (pos + 1).astype(jnp.int64)
                cols.append(
                    Column(
                        jnp.broadcast_to(ordv, (cap, k)).reshape(cap * k),
                        T.BIGINT,
                    )
                )
            return cols, out_mask

        return step

    def process(self, stream):
        for batch in stream:
            cols, mask = self._step(batch)
            yield Batch(cols, mask)
