"""The join's direct-table locate (ops/join.py): one table builder and one
eligibility rule, shared by the local build and the mesh join.

`_direct_table` is the start/count table math the local build ran inline
before the mesh shared it; `_old_tables` below is that inline math as it
stood, so the local path's tables are pinned bit for bit.  `_locate_by_table`
(the mesh's per-shard locate) must agree with the sorted locate it replaces
wherever the rule admits the domain.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu import types as T
from trino_tpu.columnar import RowBatchBuilder
from trino_tpu.ops.join import (
    TABLE_DOMAIN_LIMIT,
    TABLE_SPARSE_LIMIT,
    _direct_table,
    _locate_by_table,
    _locate_sorted,
    _prepare_sorted_build,
    _sort_build_device,
    direct_table_cap,
)


def _old_tables(c_sorted, total: int, tcap: int):
    """The local build's table math before it was factored out."""
    import jax

    cap = c_sorted.shape[0]
    pos = jnp.arange(cap, dtype=jnp.int64)
    cs = jnp.minimum(c_sorted, tcap)
    start_t = jax.ops.segment_min(
        jnp.where(c_sorted < total, pos, cap), cs, tcap + 1
    )[:tcap].astype(jnp.int32)
    count_t = jax.ops.segment_sum(
        (c_sorted < total).astype(jnp.int32), cs, tcap + 1
    )[:tcap]
    return start_t, count_t


def _seeded_codes(seed: int, cap: int, total: int):
    """Sorted composite codes with gaps (codes no row holds), duplicates
    (a code on many rows) and nomatch rows (`total`) at the end."""
    rng = np.random.default_rng(seed)
    held = rng.choice(total, size=max(1, total // 5), replace=False)
    n_match = int(rng.integers(0, cap))
    codes = np.sort(rng.choice(held, size=n_match))
    return np.concatenate([codes, np.full(cap - n_match, total)])


@pytest.mark.parametrize("seed", [3900000001, 3900000017, 3900000029])
@pytest.mark.parametrize("cap, total", [(64, 40), (1024, 700), (4096, 5000)])
def test_direct_table_is_the_old_math(seed, cap, total):
    c_sorted = jnp.asarray(_seeded_codes(seed, cap, total), dtype=jnp.int64)
    tcap = direct_table_cap(total, cap)
    got = _direct_table(c_sorted, total, tcap)
    want = _old_tables(c_sorted, total, tcap)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape == (tcap,)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_local_build_tables_are_the_old_math():
    """`_prepare_sorted_build` over two keys with NULLs, duplicates and gaps
    gives the tables the inline math gave."""
    rng = np.random.default_rng(3900000041)
    b = RowBatchBuilder([T.BIGINT, T.INTEGER, T.BIGINT])
    for i in range(300):
        k1 = None if i % 17 == 0 else int(rng.integers(-40, 60))
        b = b.row(k1, int(rng.integers(5, 12)), i)
    build = b.build().device_put()
    sb, canon, n_match, _, table = _prepare_sorted_build(build, [0, 1])
    assert table is not None
    mins, widths, start_t, count_t = table
    mins, widths = np.asarray(mins), np.asarray(widths)
    total = int(np.prod(widths))
    live = np.arange(sb.capacity) < n_match
    c = np.asarray(canon[0] - mins[0]) * widths[1] + np.asarray(
        canon[1] - mins[1]
    )
    c_sorted = jnp.asarray(np.where(live, c, total), dtype=jnp.int64)
    want = _old_tables(c_sorted, total, start_t.shape[0])
    np.testing.assert_array_equal(np.asarray(start_t), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(count_t), np.asarray(want[1]))


@pytest.mark.parametrize("total, n_match, tcap", [
    (1, 1, 16),
    (1_500_000, 700_000, 1 << 21),          # o_orderkey at SF1
    (TABLE_SPARSE_LIMIT, 1, TABLE_SPARSE_LIMIT),
    (TABLE_SPARSE_LIMIT + 1, 1, None),       # sparse: few rows, wide domain
    (TABLE_DOMAIN_LIMIT, TABLE_DOMAIN_LIMIT // 64, TABLE_DOMAIN_LIMIT),
    (TABLE_DOMAIN_LIMIT + 1, TABLE_DOMAIN_LIMIT, None),
    (0, 10, None),
])
def test_direct_table_cap(total, n_match, tcap):
    assert direct_table_cap(total, n_match) == tcap


@pytest.mark.parametrize("seed", [3900000053, 3900000067])
def test_locate_by_table_is_the_sorted_locate(seed):
    """Over a shard's sorted build, the direct table finds each probe key's
    run where the binary search does: keys below and above the domain,
    NULL probe keys and build keys, keys held many times."""
    rng = np.random.default_rng(seed)
    cap, probe = 256, 512
    b = RowBatchBuilder([T.BIGINT, T.BIGINT])
    for i in range(cap - 30):
        b = b.row(None if i % 23 == 0 else int(rng.integers(100, 160)), i)
    build = b.build().device_put()
    p = RowBatchBuilder([T.BIGINT])
    for i in range(probe):
        p = p.row(None if i % 11 == 0 else int(rng.integers(50, 220)))
    pb = p.build().device_put()
    sb, canon, n_match = _sort_build_device(build, [0])
    keys = np.asarray(canon[0])[: int(n_match)]
    lo, hi = int(keys.min()), int(keys.max())
    pc = [pb.columns[0].data.astype(jnp.int64)]
    pn = ~(pb.mask() & pb.columns[0].valid)
    want = _locate_sorted(canon, n_match, pc, pn, cap_b=sb.capacity)
    mins = jnp.asarray([lo], dtype=jnp.int64)
    widths = jnp.asarray([hi - lo + 1], dtype=jnp.int64)
    tcap = direct_table_cap(hi - lo + 1, int(n_match))
    got = _locate_by_table(canon, n_match, pc, pn, mins, widths, tcap=tcap)
    count = np.asarray(want[1])
    assert count.max() > 1 and (count == 0).sum() > probe // 4
    np.testing.assert_array_equal(np.asarray(got[1]), count)
    hit = count > 0
    np.testing.assert_array_equal(
        np.asarray(got[0])[hit], np.asarray(want[0])[hit]
    )
