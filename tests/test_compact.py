"""`Batch.compact_device`'s contract, against a NumPy reference: stable
order, static output capacity, `live[j] = j < n`, truncation to the first
`out_capacity` live rows, dead slots reading row 0, every column layout
gathered as a whole row — eagerly, under `jit`, and inside the mesh runner's
`shard_map` wrapper; at capacities on both sides of `slot_sources`' sort
block and at output capacities from one slot to more than the input."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.columnar.batch import _SORT_BLOCK, COMPACT, Batch, slot_sources
from trino_tpu.columnar.column import Column
from trino_tpu.columnar.dictionary import StringDictionary

# (capacity, out_capacity): one block and several, a capacity that is a whole
# number of blocks and ones that are not, one slot, an output wider than
# the input, and ISSUE 31's threshold shapes
SHAPES = [
    (300, 64), (3000, 2048), (3000, 2049), (9000, 4096), (5000, 8192), (3, 4),
    (2 * _SORT_BLOCK, 1), (_SORT_BLOCK + 1, 600),
]
MASKS = ["sparse", "over", "all_dead", "all_live", "none"]
MODES = ["eager", "jit", "shard_map"]


def _mask(kind: str, cap: int, outc: int, rng):
    if kind == "none":
        return None
    if kind == "all_dead":
        return np.zeros(cap, dtype=bool)
    if kind == "all_live":
        return np.ones(cap, dtype=bool)
    # fewer live rows than slots / more than fit (where the capacity allows)
    want = max(1, outc // 3) if kind == "sparse" else outc + outc // 2 + 1
    m = np.zeros(cap, dtype=bool)
    m[rng.choice(cap, min(cap, want), replace=False)] = True
    return m


def _leaves(batch: Batch):
    """Every array a row gather moves, in column order."""
    return [
        np.asarray(x)
        for c in batch.columns
        for x in (c.data, c.valid, c.lengths)
        if x is not None
    ]


def _batch(cap: int, mask, rng) -> Batch:
    """One column of each layout: plain int64, long-decimal limb planes,
    an array with `lengths`, a column with validity, a dictionary column."""
    words = StringDictionary(["a", "b", "c", "d"])
    return Batch(
        [
            Column(rng.integers(-(1 << 40), 1 << 40, cap), T.BIGINT),
            Column(rng.integers(0, 1 << 62, (cap, 2)), T.DecimalType(38, 2)),
            Column(
                rng.integers(0, 99, (cap, 3)), T.ArrayType(T.BIGINT),
                lengths=rng.integers(0, 4, cap).astype(np.int32),
            ),
            Column(rng.random(cap), T.DOUBLE, valid=rng.random(cap) < 0.7),
            Column(
                rng.integers(0, 4, cap).astype(np.int32), T.VARCHAR,
                dictionary=words,
            ),
        ],
        mask,
    )


def _reference(batch: Batch, outc: int):
    """(leaves, live) the contract asks for, in NumPy."""
    cap = batch.capacity
    mask = np.ones(cap, bool) if batch.row_mask is None else batch.row_mask
    src = np.nonzero(mask)[0][:outc]
    inv = np.zeros(outc, dtype=np.int64)  # a dead slot reads row 0
    inv[: len(src)] = src
    live = np.arange(outc) < len(src)
    return [x[inv] for x in _leaves(batch)], live


def _run(mode: str, batch: Batch, outc: int) -> Batch:
    if mode == "eager":
        return batch.device_put().compact_device(out_capacity=outc)
    if mode == "jit":
        return COMPACT(batch.device_put(), out_capacity=outc)
    from trino_tpu.parallel.spmd import WorkerMesh, spmd_step

    wm = WorkerMesh(n_workers=2)
    stacked = jax.device_put(
        jax.tree.map(lambda x: np.stack([x, x]), batch), wm.sharding()
    )
    step = spmd_step(
        wm, lambda b: b.compact_device(out_capacity=outc), "state_compact"
    )
    out = step(stacked)
    for leaf in jax.tree_util.tree_leaves(out):
        assert np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[1]))
    return jax.tree.map(lambda x: x[0], out)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("mask", MASKS)
@pytest.mark.parametrize("cap,outc", SHAPES)
def test_compaction_contract(cap, outc, mask, mode):
    rng = np.random.default_rng(cap * 31 + outc)
    batch = _batch(cap, _mask(mask, cap, outc, rng), rng)
    out = _run(mode, batch, outc)
    want, live = _reference(batch, outc)
    assert out.capacity == outc
    assert np.array_equal(np.asarray(out.row_mask), live)
    got = _leaves(out)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    assert [c.type for c in out.columns] == [c.type for c in batch.columns]
    assert out.columns[4].dictionary.values == batch.columns[4].dictionary.values


@pytest.mark.parametrize("outc", [1, 8, 2048, 1 << 17, 1 << 19])
def test_positions_are_int32_at_a_scan_split(outc):
    """At `cap` = 2^20 no 64-bit plane is computed (the chip emulates int64
    as two u32 planes), nothing is scattered, one sort is traced."""
    cap = 1 << 20
    jaxpr = str(
        jax.make_jaxpr(lambda m: slot_sources(m, outc))(
            jax.ShapeDtypeStruct((cap,), jnp.bool_)
        )
    )
    # weak-typed Python scalars trace as `i64[]`; no int64 *plane* may
    assert not re.search(r"[iu]64\[\d", jaxpr) and "scatter" not in jaxpr
    assert jaxpr.count(" sort[") == 1
    rng = np.random.default_rng(outc)
    m = np.zeros(cap, dtype=bool)
    m[rng.choice(cap, min(cap, outc + 5), replace=False)] = True
    inv, live = jax.jit(lambda m: slot_sources(m, outc))(jnp.asarray(m))
    assert inv.dtype == jnp.int32
    src = np.nonzero(m)[0][:outc]
    assert np.array_equal(np.asarray(inv)[: len(src)], src)
    assert not np.asarray(inv)[len(src):].any()
    assert np.array_equal(np.asarray(live), np.arange(outc) < len(src))


def test_empty_batch_compacts_to_itself():
    b = Batch([Column(jnp.zeros(0, jnp.int64), T.BIGINT)], jnp.zeros(0, bool))
    assert b.compact_device().capacity == 0
