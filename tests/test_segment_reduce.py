"""`ops/common.segment_reduce` lowers a few-segment reduction densely and a
many-segment one as a scatter; both must return the same integers.  The
reference here is `jax.ops.segment_*` called directly — what every caller
ran before the helper chose — and, for `_sum128`, a Python big-int sum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import trino_tpu  # noqa: F401  (x64)
from trino_tpu.ops import common
from trino_tpu.ops.common import DENSE_SEGMENT_LIMIT, segment_reduce

ROWS = 300
EDGE = (1 << 62) - 1


def _scatter_reference(values, gid, nseg, kind, valid):
    """The pre-helper formulation, straight on jax.ops.segment_*."""
    if kind == "count":
        return jax.ops.segment_sum(valid.astype(jnp.int64), gid, nseg)
    if kind == "any":
        idx = jnp.where(valid, jnp.arange(ROWS, dtype=jnp.int64), ROWS)
        first = jax.ops.segment_min(idx, gid, nseg)
        return jnp.take(values, jnp.clip(first, 0, ROWS - 1), mode="clip")
    if kind == "sum":
        return jax.ops.segment_sum(jnp.where(valid, values, 0), gid, nseg)
    if kind == "min":
        masked = jnp.where(valid, values, common._max_sentinel(values.dtype))
        return jax.ops.segment_min(masked, gid, nseg)
    masked = jnp.where(valid, values, common._min_sentinel(values.dtype))
    return jax.ops.segment_max(masked, gid, nseg)


def _case(case: str, nseg: int):
    """(values, gid, valid) for one named input shape."""
    rng = np.random.default_rng(len(case) * 1000 + nseg)
    gid = rng.integers(0, nseg, ROWS)
    valid = rng.random(ROWS) < 0.7
    values = rng.integers(-(10**9), 10**9, ROWS, dtype=np.int64)
    if case == "all_dead":
        valid[:] = False
    elif case == "stray_gids":
        # out-of-range and negative ids drop on both lowerings
        gid[::3] = nseg + rng.integers(0, 5, len(gid[::3]))
        gid[1::7] = -1 - rng.integers(0, 5, len(gid[1::7]))
    elif case == "edge_values":
        # int64 sums wrap the same way on both lowerings
        values = rng.choice(np.array([EDGE, -EDGE, 1, -1], np.int64), ROWS)
    elif case == "bool_plane":
        values = rng.random(ROWS) < 0.5
    return jnp.asarray(values), jnp.asarray(gid, jnp.int64), jnp.asarray(valid)


KINDS = ("sum", "min", "max", "count", "any")
NSEGS = (1, 2, 13, 33, DENSE_SEGMENT_LIMIT, DENSE_SEGMENT_LIMIT + 1)
CASES = ("all_dead", "stray_gids", "edge_values", "bool_plane")


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("nseg", NSEGS)
@pytest.mark.parametrize("kind", KINDS)
def test_segment_reduce_matches_scatter(kind, nseg, case):
    if case == "bool_plane" and kind == "sum":
        # bool_and / bool_or reduce bool planes as min / max; scatter-add
        # of bool does not exist, so there is a count and nothing to sum
        kind = "count"
    values, gid, valid = _case(case, nseg)
    got = segment_reduce(values, gid, nseg, kind, valid=valid)
    want = _scatter_reference(values, gid, nseg, kind, valid)
    assert got.shape == want.shape == (nseg,)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("nseg", NSEGS)
def test_lowering_follows_the_limit(kind, nseg):
    """Dense up to the limit — no scatter in the lowered program — and a
    scatter above it; the choice rides `note_path`."""
    from trino_tpu.telemetry.programs import jit_program

    values, gid, valid = _case("stray_gids", nseg)
    program = jit_program(
        lambda v, g, ok: segment_reduce(v, g, nseg, kind, valid=ok), "agg_reduce"
    )
    text = program.lower(values, gid, valid).as_text()
    dense = nseg <= DENSE_SEGMENT_LIMIT
    assert ("stablehlo.scatter" in text) != dense
    assert program.path == ("dense" if dense else "scatter")


# -- _sum128 against Python integers ----------------------------------------


def _limbs(vals):
    from trino_tpu.types.int128 import split_py

    h = np.array([split_py(v)[0] for v in vals], np.int64)
    l = np.array([split_py(v)[1] for v in vals], np.int64)
    return jnp.stack([jnp.asarray(h), jnp.asarray(l)], axis=-1)


def _sum128_inputs(shape: str, rows: int):
    """(device input, python values, kwargs) for one `_sum128` branch."""
    rng = np.random.default_rng(rows)
    small = [int(v) for v in rng.integers(-(10**11), 10**11, rows)]
    if shape == "short":  # 1-D, runtime probe, narrow branch
        return jnp.asarray(np.array(small, np.int64)), small, {}
    if shape == "short_wide":  # 1-D, a value above thr forces sum128_widened
        vals = small[:-2] + [(1 << 62) + 5, (1 << 62) + 7]
        return jnp.asarray(np.array(vals, np.int64)), vals, {}
    if shape == "limbs":  # 2-D, runtime probe, narrow branch
        return _limbs(small), small, {"in_precision": 38}
    if shape == "limbs_wide":  # 2-D, values beyond i64: segment_sum128
        vals = small[:-2] + [10**37, -(10**36)]
        return _limbs(vals), vals, {"in_precision": 38}
    if shape == "limbs_hi_direct":  # 2-D wide, |hi| * rows proven small
        vals = small[:-2] + [10**24, -(10**23)]
        return _limbs(vals), vals, {"in_precision": 25}
    if shape == "licensed":  # range certificate: one i64 sum, no probe
        return (
            jnp.asarray(np.array(small, np.int64)), small,
            {"sum_bound": 10**11 * rows},
        )
    assert shape == "licensed_limbs"
    return _limbs(small), small, {"sum_bound": 10**11 * rows}


@pytest.mark.parametrize("nseg", [1, 13])
@pytest.mark.parametrize(
    "shape",
    ["short", "short_wide", "limbs", "limbs_wide", "limbs_hi_direct",
     "licensed", "licensed_limbs"],
)
def test_sum128_matches_python_integers(shape, nseg):
    from trino_tpu.ops.aggregation import _sum128
    from trino_tpu.types.int128 import join_py

    rows = 64
    d, vals, kwargs = _sum128_inputs(shape, rows)
    rng = np.random.default_rng(nseg)
    gid = rng.integers(0, nseg, rows)
    valid = rng.random(rows) < 0.8
    valid[-2:] = True  # the wide values count
    out = np.asarray(
        jax.jit(lambda d, g, ok: _sum128(d, g, nseg, ok, **kwargs))(
            d, jnp.asarray(gid, jnp.int64), jnp.asarray(valid)
        )
    )
    want = [0] * nseg
    for v, g, ok in zip(vals, gid, valid):
        if ok:
            want[g] += v
    assert [join_py(int(h), int(l)) for h, l in out] == want
