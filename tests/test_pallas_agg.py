"""Pallas MXU aggregation kernel (ops/pallas_agg.py): correctness vs the
XLA formulation and end-to-end behind the `pallas_agg` session property.
On CPU the kernel runs in interpreter mode; the TPU path compiles the same
program."""

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops.pallas_agg import grouped_sums_pallas, grouped_sums_xla
from trino_tpu.runtime.runner import LocalQueryRunner


def test_kernel_matches_xla():
    rng = np.random.default_rng(7)
    n, k, g = 4096, 5, 9
    gids = jnp.asarray(rng.integers(0, g, n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < 0.7)
    vals = jnp.asarray(rng.random((n, k)), jnp.float32)
    a = grouped_sums_pallas(gids, mask, vals, n_groups=g, interpret=True)
    b = grouped_sums_xla(gids, mask, vals, g)
    assert jnp.allclose(a, b, atol=1e-2)


def test_kernel_multi_block():
    rng = np.random.default_rng(8)
    n, g = 8192, 3  # 4 grid steps at block 2048
    gids = jnp.asarray(rng.integers(0, g, n), jnp.int32)
    mask = jnp.ones(n, bool)
    vals = jnp.ones((n, 1), jnp.float32)
    a = grouped_sums_pallas(gids, mask, vals, n_groups=g, interpret=True)
    counts = np.bincount(np.asarray(gids), minlength=g)
    assert np.allclose(np.asarray(a)[:, 0], counts)


def test_query_with_pallas_agg_matches_default():
    """`pallas_agg` REACHES the kernel from SQL (streaming partial
    aggregation; until PR 21 the per-batch reducer dropped the flag and
    this test passed without it) and agrees with the default path to f32
    accumulation precision."""
    import math

    from trino_tpu.telemetry.metrics import aggregation_path_counter

    sql = (
        "select o_orderstatus, o_orderpriority, count(*), "
        "sum(cast(o_totalprice as double)), avg(cast(o_totalprice as double)) "
        "from orders group by o_orderstatus, o_orderpriority"
    )
    base = LocalQueryRunner(catalog="tpch", schema="tiny")
    expected = sorted(base.execute(sql).rows)

    fast = LocalQueryRunner(catalog="tpch", schema="tiny")
    fast.execute("set session pallas_agg = true")
    actual = sorted(fast.execute(sql).rows)
    assert aggregation_path_counter().value(("pallas",)) > 0
    assert len(actual) == len(expected)
    for ra, re in zip(actual, expected):
        assert ra[:3] == re[:3]  # keys and counts exact
        assert all(
            math.isclose(a, e, rel_tol=1e-5) for a, e in zip(ra[3:], re[3:])
        ), (ra, re)


def test_onehot_direct_sums_exact():
    """The one-hot masked-reduction aggregation path (TPU default) is exact for int,
    short-decimal, long-decimal, and double sums — forced on here since
    tests run on CPU where the segmented path is the default."""
    from decimal import Decimal

    import trino_tpu.ops.aggregation as agg
    from trino_tpu.runtime.runner import LocalQueryRunner

    q = (
        "select l_returnflag, sum(l_quantity), sum(l_extendedprice), "
        "sum(l_extendedprice * (1 - l_discount)), avg(l_quantity), "
        "count(*), count(l_comment) from lineitem group by l_returnflag "
        "order by l_returnflag"
    )
    # oracle FIRST, through whatever (segmented) steps are already cached
    expected = LocalQueryRunner(
        catalog="tpch", schema="tiny", target_splits=4
    ).execute(q).rows

    orig = agg.AggregationOperator._onehot_direct_sums
    orig_cache = agg._STEP_CACHE
    called = {"n": 0}

    def forced(self, batch, live, gid, prod):
        self.force_onehot = True
        out = orig(self, batch, live, gid, prod)
        if out is not None:
            called["n"] += 1
        return out

    # fresh step cache: the jitted steps bake the (forced) matmul path into
    # their traces, so they must neither reuse earlier unforced traces nor
    # leak forced ones back into the shared process-level cache
    agg.AggregationOperator._onehot_direct_sums = forced
    agg._STEP_CACHE = {}
    try:
        r = LocalQueryRunner(catalog="tpch", schema="tiny", target_splits=4)
        rows = r.execute(q).rows
        assert called["n"] > 0, "one-hot path did not engage"
        assert rows == expected
    finally:
        agg.AggregationOperator._onehot_direct_sums = orig
        agg._STEP_CACHE = orig_cache
