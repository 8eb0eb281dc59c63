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


# -- reductions over the runs of a non-decreasing group id (`common.Runs`) ----
#
# What `_range_step` runs above DENSE_SEGMENT_LIMIT slots.  The reference
# is the scatter again, whose output is positional (slot = group id): its
# occupied slots, in id order, are the run form's packed output.

OUT_CAPS = (4096, 65536)
RUN_CASES = (
    "ordered", "unordered", "dead_tail", "dead_interleaved", "all_dead",
    "all_live", "one_run", "every_row_its_own_run", "wrapping_prefix",
)


def _run_case(case: str, out_cap: int):
    """(values, gid, live, valid): `gid` non-decreasing over the live rows
    (but for `unordered`), dead rows anywhere."""
    rng = np.random.default_rng(len(case) * 1000 + out_cap)
    gid = np.sort(rng.integers(0, out_cap, ROWS))
    live = rng.random(ROWS) < 0.7
    values = rng.integers(-(10**9), 10**9, ROWS, dtype=np.int64)
    if case == "unordered":
        gid = rng.permutation(gid)
    elif case == "dead_tail":
        live = np.arange(ROWS) < 200
    elif case == "dead_interleaved":
        live = np.arange(ROWS) % 3 != 1
        live[:5] = False
    elif case == "all_dead":
        live[:] = False
    elif case == "all_live":
        live[:] = True
    elif case == "one_run":
        gid[:] = out_cap - 1
    elif case == "every_row_its_own_run":
        gid = np.sort(rng.choice(out_cap, ROWS, replace=False))
    elif case == "wrapping_prefix":
        # the running sum passes 2**63 again and again; no run's own does
        values = rng.choice(np.array([EDGE, EDGE - 1], np.int64), ROWS)
        gid = np.arange(ROWS) // 2
    valid = np.logical_and(live, rng.random(ROWS) < 0.8)
    return (
        jnp.asarray(values), jnp.asarray(gid, jnp.int64), jnp.asarray(live),
        jnp.asarray(valid),
    )


def _reduce_over_runs(values, gid, live, valid, out_cap, kind, sort: bool):
    """(packed [out_cap] result, [out_cap] live slots) as `_range_step`
    computes them: a stable 32-bit sort first where the rows are not in id
    order, dead rows carrying `out_cap`."""
    gid = jnp.where(live, gid, out_cap)
    if sort:
        gid, perm = jax.lax.sort(
            (gid.astype(jnp.uint32), jnp.arange(ROWS, dtype=jnp.int32)),
            num_keys=1, is_stable=True,
        )
        live = gid < out_cap
        values, valid = values[perm], valid[perm]
    runs = common.run_ids(gid, live, out_cap)
    return segment_reduce(values, runs, out_cap, kind, valid=valid), runs.live


def _packed_scatter_reference(values, gid, live, valid, out_cap, kind):
    """(the scatter's occupied slots in id order, identity of an empty slot)"""
    g = jnp.where(live, gid, out_cap)
    want = _scatter_reference(values, g, out_cap + 1, kind, valid)
    occupied = np.asarray(jax.ops.segment_sum(live.astype(jnp.int32), g, out_cap + 1))
    empty = _scatter_reference(values, g, out_cap + 2, kind, valid)[-1]
    return np.asarray(want)[:out_cap][occupied[:out_cap] > 0], np.asarray(empty)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", RUN_CASES)
@pytest.mark.parametrize("out_cap", OUT_CAPS)
@pytest.mark.parametrize("kind", KINDS)
def test_run_reduce_matches_scatter(kind, out_cap, case, jitted):
    values, gid, live, valid = _run_case(case, out_cap)
    fn = lambda *a: _reduce_over_runs(  # noqa: E731
        *a, out_cap, kind, sort=case == "unordered"
    )
    got, out_live = (jax.jit(fn) if jitted else fn)(values, gid, live, valid)
    want, empty = _packed_scatter_reference(values, gid, live, valid, out_cap, kind)
    n = len(want)
    assert got.shape == (out_cap,) and got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(out_live), np.arange(out_cap) < n)
    got = np.asarray(got)
    if kind == "any":
        # a group with no valid row reads whatever row the clip lands on
        has = _packed_scatter_reference(values, gid, live, valid, out_cap, "count")[0] > 0
        np.testing.assert_array_equal(got[:n][has], want[has])
    else:
        np.testing.assert_array_equal(got[:n], want)
        np.testing.assert_array_equal(got[n:], np.full(out_cap - n, empty))


@pytest.mark.parametrize("out_cap", OUT_CAPS)
def test_run_reduce_lowers_without_scatter(out_cap):
    values, gid, live, valid = _run_case("dead_interleaved", out_cap)
    for kind in KINDS:
        text = jax.jit(
            lambda *a: _reduce_over_runs(*a, out_cap, kind, sort=True)
        ).lower(values, gid, live, valid).as_text()
        assert "stablehlo.scatter" not in text, kind


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("out_cap", OUT_CAPS)
def test_run_reduce_null_keys_last(out_cap, jitted):
    """`_range_gid` gives a nullable key's NULL the last code, so rows
    sorted NULLS LAST are in code order and the NULL group is the last run."""
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.ops.aggregation import _range_gid

    rng = np.random.default_rng(out_cap)
    key = np.sort(rng.integers(100, 100 + out_cap - 1, ROWS))
    key_valid = np.arange(ROWS) < 250  # the NULL keys last
    values = rng.integers(-1000, 1000, ROWS)
    batch = Batch(
        [Column(jnp.asarray(key), T.BIGINT, jnp.asarray(key_valid))],
        jnp.ones(ROWS, bool),
    )
    lo, hi = int(key[key_valid].min()), int(key[key_valid].max())
    mins, sizes = jnp.asarray([lo]), jnp.asarray([hi - lo + 2])

    def fn(batch, values):
        gid = _range_gid(batch, [0], mins, sizes)
        runs = common.run_ids(gid, batch.mask(), out_cap)
        total = segment_reduce(values, runs, out_cap, "sum")
        return gid, total, runs.live, jnp.take(runs.gid, runs.src)

    gid, total, out_live, codes = (jax.jit(fn) if jitted else fn)(
        batch, jnp.asarray(values)
    )
    assert (np.diff(np.asarray(gid)) >= 0).all()
    n = int(np.asarray(out_live).sum())
    assert n == len(set(key[key_valid])) + 1
    assert int(codes[n - 1]) == hi - lo + 1  # the NULL code
    assert int(total[n - 1]) == int(values[~key_valid].sum())
    assert int(total[0]) == int(values[key == lo].sum())


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("out_cap", OUT_CAPS)
def test_run_reduce_double_sum_keeps_the_small_group(out_cap, jitted):
    """A DOUBLE sum restarts at every run: a group of 1e-3s after a group of
    1e12s reads its own sum, which a difference of float prefix sums (1e15
    apart in magnitude) would round away."""
    big, small = np.full(100, 1e12), np.full(200, 1e-3)
    values = jnp.asarray(np.concatenate([big, small]))
    gid = jnp.asarray(np.concatenate([np.full(100, 7), np.full(200, out_cap - 2)]))
    live = jnp.ones(ROWS, bool)
    fn = lambda *a: _reduce_over_runs(*a, out_cap, "sum", sort=False)  # noqa: E731
    got, _ = (jax.jit(fn) if jitted else fn)(values, gid, live, live)
    assert float(got[0]) == 1e14
    np.testing.assert_allclose(float(got[1]), 0.2, rtol=1e-12)
    assert (np.asarray(got[2:]) == 0).all()
    # and it never depends on the rows before the run
    alone, _ = fn(values.at[:100].set(0.0), gid, live, live)
    assert float(alone[1]) == float(got[1])


@pytest.mark.parametrize("out_cap", OUT_CAPS)
@pytest.mark.parametrize(
    "shape",
    ["short", "short_wide", "limbs", "limbs_wide", "limbs_hi_direct",
     "licensed", "licensed_limbs"],
)
def test_sum128_over_runs_matches_python_integers(shape, out_cap):
    """Every `_sum128` branch (licensed, precision-proven, runtime probe
    narrow and wide) over `Runs`, dead rows interleaved."""
    from trino_tpu.ops.aggregation import _sum128
    from trino_tpu.types.int128 import join_py

    rows = 64
    d, vals, kwargs = _sum128_inputs(shape, rows)
    rng = np.random.default_rng(out_cap)
    gid = np.sort(rng.integers(0, out_cap, rows) // 512 * 512)
    valid = rng.random(rows) < 0.8
    valid[-2:] = True  # the wide values count

    def fn(d, g, ok):
        runs = common.run_ids(g, ok, out_cap)
        return _sum128(d, runs, out_cap, ok, **kwargs), runs.live

    out, out_live = jax.jit(fn)(d, jnp.asarray(gid), jnp.asarray(valid))
    want: dict = {}
    for v, g, ok in zip(vals, gid, valid):
        if ok:
            want[g] = want.get(g, 0) + v
    n = int(np.asarray(out_live).sum())
    got = [join_py(int(h), int(l)) for h, l in np.asarray(out)[:n]]
    assert got == [want[g] for g in sorted(want)]
    assert (np.asarray(out)[n:] == 0).all()


@pytest.mark.parametrize("kind", ["min", "max", "any"])
@pytest.mark.parametrize("out_cap", OUT_CAPS)
def test_reduce128_over_runs_matches_python_integers(out_cap, kind):
    """min/max/any of long-decimal limb planes over `Runs`: the winning high
    limb goes back to its run's rows (`segment_values_of_rows`) to pick the
    low limb among them."""
    from trino_tpu.ops.aggregation import _reduce128
    from trino_tpu.types.int128 import join_py

    rows = 64
    rng = np.random.default_rng(out_cap)
    vals = [int(v) * 10**20 + int(w) for v, w in zip(
        rng.integers(-3, 4, rows), rng.integers(-(10**9), 10**9, rows)
    )]
    gid = np.sort(rng.integers(0, out_cap, rows) // 512 * 512)
    valid = rng.random(rows) < 0.8

    def fn(d, g, ok):
        runs = common.run_ids(g, ok, out_cap)
        return _reduce128(d, runs, out_cap, kind, ok), runs.live

    out, out_live = jax.jit(fn)(_limbs(vals), jnp.asarray(gid), jnp.asarray(valid))
    want: dict = {}
    for v, g, ok in zip(vals, gid, valid):
        if ok:
            want.setdefault(g, []).append(v)
    pick = {"min": min, "max": max, "any": lambda vs: vs[0]}[kind]
    n = int(np.asarray(out_live).sum())
    got = [join_py(int(h), int(l)) for h, l in np.asarray(out)[:n]]
    assert got == [pick(want[g]) for g in sorted(want)]
