"""The repartition's send buffer (`parallel/exchange.bucketize`) and its
counts pass against a plain NumPy reference: destination `d`'s piece is the
live rows whose key hashes to `d`, in row order, the first `slot_cap` of
them; every other slot is zero, not valid and not live.  Then the whole
exchange over a CPU mesh: every row delivered exactly once, to the worker
its key hashes to, each sender's rows in the sender's order, senders in
worker order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.columnar.batch import _SORT_BLOCK, Batch
from trino_tpu.columnar.column import Column
from trino_tpu.columnar.dictionary import StringDictionary
from trino_tpu.parallel import exchange as ex
from trino_tpu.partitioning.layout import bucket_rows

#: scenario -> (capacity, key channels); the capacities lie on both sides
#: of `slot_sources`' sort block
SCENARIOS = {
    "bigint_key": (2 * _SORT_BLOCK + 77, [0]),
    "unmasked": (300, [0]),
    "one_destination": (700, [0]),
    "no_live_row": (300, [0]),
    "overflow": (_SORT_BLOCK + 500, [0]),
    "nullable_key": (900, [3]),
    "long_decimal_key": (900, [1]),
    "dictionary_key": (900, [4]),
    "two_keys": (900, [0, 5]),
}


_WORDS = StringDictionary(["a", "b", "c", "d", "e"])


def _batch(scenario: str, cap: int, rng) -> Batch:
    """One column of each layout the exchange moves: bigint, long-decimal
    limb planes, a double with a validity plane, a nullable bigint, a
    dictionary column, a date."""
    key = rng.integers(-(1 << 40), 1 << 40, cap)
    if scenario == "one_destination":
        key[:] = 42
    mask = rng.random(cap) < 0.6
    if scenario == "no_live_row":
        mask[:] = False
    if scenario == "unmasked":
        mask = None
    return Batch(
        [
            Column(key, T.BIGINT),
            Column(rng.integers(0, 1 << 62, (cap, 2)), T.DecimalType(38, 2)),
            Column(rng.random(cap), T.DOUBLE, valid=rng.random(cap) < 0.7),
            Column(
                rng.integers(0, 50, cap), T.BIGINT, valid=rng.random(cap) < 0.8
            ),
            Column(
                rng.integers(0, 5, cap).astype(np.int32), T.VARCHAR,
                dictionary=_WORDS,
            ),
            Column(rng.integers(8000, 9000, cap).astype(np.int32), T.DATE),
        ],
        mask,
    )


def _planes(batch: Batch):
    """(data, valid or None) of every column, as NumPy."""
    return [
        (np.asarray(c.data), None if c.valid is None else np.asarray(c.valid))
        for c in batch.columns
    ]


def _reference(batch: Batch, dest: np.ndarray, n_workers: int, slot_cap: int):
    """(planes, mask) of the send buffer, in NumPy: [n_workers, slot_cap]."""
    mask = np.zeros((n_workers, slot_cap), dtype=bool)
    rows = _planes(batch)
    planes = [
        (
            np.zeros((n_workers, slot_cap) + d.shape[1:], d.dtype),
            None if v is None else np.zeros((n_workers, slot_cap), bool),
        )
        for d, v in rows
    ]
    for w in range(n_workers):
        src = np.nonzero(dest == w)[0][:slot_cap]
        mask[w, : len(src)] = True
        for (d, v), (od, ov) in zip(rows, planes):
            od[w, : len(src)] = d[src]
            if v is not None:
                ov[w, : len(src)] = v[src]
    return planes, mask


@pytest.mark.parametrize("n_workers", [1, 2, 4, 8])
@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_send_buffer_is_the_reference(scenario, n_workers):
    cap, keys = SCENARIOS[scenario]
    rng = np.random.default_rng(cap * 17 + n_workers)
    host = _batch(scenario, cap, rng)
    dev = host.device_put()
    dest = np.asarray(ex._destinations(dev, keys, n_workers))
    live = np.ones(cap, bool) if host.row_mask is None else host.row_mask
    assert dest.dtype == np.int32
    assert (dest[~live] == n_workers).all() and (dest[live] < n_workers).all()
    if all(host.columns[k].data.ndim == 1 for k in keys):
        # bit for bit the layout's host mirror: bucketed scans co-locate
        assert np.array_equal(dest, bucket_rows(host, keys, n_workers))

    counts = np.bincount(dest[live], minlength=n_workers)
    stacked = jax.tree.map(lambda x: x[None], dev)
    got_counts = np.asarray(ex._counts_kernel(keys, n_workers)(stacked))
    assert got_counts.shape == (1, n_workers)
    assert np.array_equal(got_counts[0], counts)
    if scenario == "one_destination":
        assert np.count_nonzero(counts) == 1
    if scenario == "no_live_row":
        assert not counts.any()

    # the engine's pow2 bucket of the fullest piece; `overflow` has fewer
    # slots than rows and keeps the first of them
    slot_cap = ex.next_pow2(max(1, int(counts.max())), floor=64)
    if scenario == "overflow":
        slot_cap = max(1, int(counts.max()) // 3)
    out = ex.bucketize(dev, jnp.asarray(dest), n_workers, slot_cap)
    want_planes, want_mask = _reference(host, dest, n_workers, slot_cap)
    assert np.array_equal(np.asarray(out.row_mask), want_mask)
    assert want_mask.sum() == np.minimum(counts, slot_cap).sum()
    for c, src, (d, v), (wd, wv) in zip(
        out.columns, host.columns, _planes(out), want_planes
    ):
        assert c.type == src.type and c.dictionary is src.dictionary
        assert d.dtype == wd.dtype and np.array_equal(d, wd)
        assert (v is None) == (wv is None)
        if v is not None:
            assert np.array_equal(v, wv)


@pytest.mark.parametrize("n_workers", [2, 4, 8])
def test_repartition_delivers_each_row_once_in_order(n_workers):
    """The whole exchange on a CPU mesh: worker `w` receives, sender by
    sender in worker order, the sender's live rows that hash to `w`, in the
    sender's row order."""
    from trino_tpu.parallel.spmd import WorkerMesh

    wm = WorkerMesh(n_workers=n_workers)
    cap, keys = 1200, [0]
    rng = np.random.default_rng(n_workers)
    senders = [_batch("bigint_key", cap, rng) for _ in range(n_workers)]
    stacked = jax.device_put(
        jax.tree.map(lambda *xs: np.stack(xs), *senders), wm.sharding()
    )
    out = ex.repartition(stacked, keys, wm)
    dests = [bucket_rows(b, keys, n_workers) for b in senders]
    got_mask = np.asarray(out.row_mask)  # [W, W * slot_cap]
    slot_cap = got_mask.shape[1] // n_workers
    for w in range(n_workers):
        for s, (b, dest) in enumerate(zip(senders, dests)):
            src = np.nonzero(dest == w)[0]
            piece = slice(s * slot_cap, s * slot_cap + len(src))
            rest = slice(s * slot_cap + len(src), (s + 1) * slot_cap)
            assert got_mask[w, piece].all() and not got_mask[w, rest].any()
            for c, (d, v) in zip(out.columns, _planes(b)):
                assert np.array_equal(np.asarray(c.data)[w, piece], d[src])
                assert not np.asarray(c.data)[w, rest].any()
                if v is not None:
                    assert np.array_equal(np.asarray(c.valid)[w, piece], v[src])
                    assert not np.asarray(c.valid)[w, rest].any()
    delivered = int(got_mask.sum())
    assert delivered == sum(int(b.row_mask.sum()) for b in senders)
