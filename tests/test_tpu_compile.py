"""AOT compiles for a DESCRIBED TPU v5e (no chip attached): the pieces of
the main path that branch on the backend, at the widths the engine really
uses.  The chip's compiler is installed here and refuses exactly what it
would refuse on the machine with the chip (a Mosaic layout it cannot infer,
a 64-bit block, a program that does not fit) — interpret mode shows none of
that.  A compile that passes is not a chip run; chip_smoke.py is.

All of these live in this ONE file: the worker that describes the topology
keeps libtpu (and its lock) until it exits, so a second file on another
xdist worker would skip every test.  The topology is described inside a
module-scoped fixture — never at import, never in conftest.py.
"""

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of these tests
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding
    )


# (rows, value planes K, groups G): a scan page / a learned-capacity batch
# at the property's group-domain ceiling
@pytest.mark.parametrize(
    "n,k,g", [(1 << 21, 6, 8), (1 << 20, 11, 512)], ids=["2M_k6_g8", "1M_k11_g512"]
)
def test_pallas_grouped_sums_compiles(one_chip, n, k, g):
    from trino_tpu.ops.pallas_agg import grouped_sums_pallas

    S = _shapes(one_chip)
    compiled = grouped_sums_pallas.lower(
        S((n,), jnp.int32), S((n,), jnp.bool_), S((n, k), jnp.float32),
        n_groups=g,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [1 << 20, 1 << 21], ids=["1M", "2M"])
def test_onehot_plane_sums_compiles(one_chip, n):
    """The default grouped-sum path off-CPU: K = 12 planes is what Q1
    produces (decimal sums as 32-bit chunk planes, plus counts), G = 8 its
    padded flag x status domain.  The f64 one-hot einsum this replaced
    materialized its product (3.19 GB of temp at ONEHOT_ROW_LIMIT, and
    inexact on the chip); the masked int64 reductions fuse — guard that."""
    from trino_tpu.ops.aggregation import AggregationOperator, _onehot_plane_sums

    assert n <= AggregationOperator.ONEHOT_ROW_LIMIT
    S = _shapes(one_chip)
    g = 8
    planes = [S((n,), jnp.int64)] * 10 + [S((n,), jnp.float64)] * 2
    compiled = jax.jit(
        lambda gid, live, *planes: _onehot_plane_sums(gid, live, list(planes), g)
    ).lower(S((n,), jnp.int64), S((n,), jnp.bool_), *planes).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (256 << 20), f"one-hot temp grew to {temp} bytes at {n} rows"


def test_q1_fragment_compiles(one_chip):
    """`__graft_entry__.entry()`: int64 argsort + segment_sum at a scan
    page's capacity."""
    import __graft_entry__ as g

    S = _shapes(one_chip)
    n = 1 << 20
    i64 = ("qty", "price", "disc", "tax")
    cols = {c: S((n,), jnp.int64) for c in i64}
    cols.update({c: S((n,), jnp.int32) for c in ("flag", "status", "shipdate")})
    fn, _ = g.entry()
    jax.jit(fn).lower(cols).compile()


# -- the partial aggregation steps of the scan cell: Q1 (direct path over a
# 3 x 2 dictionary domain, one-hot sums) and Q6 (global long-decimal sum)


def _agg_partial_step(query: str, S):
    """(`agg_reduce` step function, input batch of shapes) for one scan
    page of TPC-H Q1 / Q6, laid out as the planner's projection leaves it."""
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.columnar.dictionary import StringDictionary
    from trino_tpu.ops.aggregation import AggregationOperator, AggSpec

    n = 1 << 20
    dec = T.DecimalType

    def short(p, s):
        return Column(S((n,), jnp.int64), dec(p, s), None)

    def long(p, s):
        return Column(S((n, 2), jnp.int64), dec(p, s), None)

    if query == "q6":
        cols = [long(24, 4)]
        specs = [AggSpec("sum", 0, dec(38, 4), sum_bound=10**15)]
        groups = []
    else:
        def code(values):
            return Column(
                S((n,), jnp.int32), T.VarcharType(1), None,
                StringDictionary(values),
            )

        cols = [
            code(["A", "N", "R"]), code(["F", "O"]),
            short(12, 2), short(12, 2), long(25, 4), long(38, 6),
            short(12, 2),
        ]
        specs = [
            AggSpec("sum", 2, dec(38, 2), sum_bound=10**12),
            AggSpec("sum", 3, dec(38, 2)),
            AggSpec("sum", 4, dec(38, 4)),
            AggSpec("sum", 5, dec(38, 6)),
            AggSpec("avg", 6, dec(12, 2)),
            AggSpec("count_star", None, T.BIGINT),
        ]
        groups = [0, 1]
    op = AggregationOperator(
        groups, specs, [c.type for c in cols], mode="partial"
    )
    op.force_onehot = True  # what the chip runs; the CPU default is segmented
    return op._reduce_step, Batch(cols, S((n,), jnp.bool_))


@pytest.mark.parametrize("query", ["q1", "q6"])
def test_agg_partial_lowers_without_scatter(query):
    """CPU lowering, no chip described: a 13-segment occupancy count and a
    1-segment long-decimal sum are dense masked reductions.  As scatters
    they were 95 % of the scan cell's device time (74-127 ns a row)."""
    step, batch = _agg_partial_step(query, jax.ShapeDtypeStruct)
    text = jax.jit(step, static_argnames=("out_cap",)).lower(
        batch, out_cap=1 << 20
    ).as_text()
    assert "stablehlo.reduce" in text
    assert "stablehlo.scatter" not in text


@pytest.mark.parametrize("query", ["q1", "q6"])
def test_agg_partial_compiles(one_chip, query):
    step, batch = _agg_partial_step(query, _shapes(one_chip))
    compiled = jax.jit(step, static_argnames=("out_cap",)).lower(
        batch, out_cap=1 << 20
    ).compile()
    assert " scatter(" not in compiled.as_text()  # the op, not a frame name
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (256 << 20), f"{query}: temp grew to {temp} bytes"


@pytest.mark.parametrize(
    "cap_b,probe", [(1 << 16, 1 << 20), (1 << 20, 1 << 21)],
    ids=["build64K_probe1M", "build1M_probe2M"],
)
def test_locate_sorted_compiles(one_chip, cap_b, probe):
    """The join probe: two vectorized binary searches of int64 canon planes
    (XLA gathers; there is no Pallas probe kernel)."""
    from trino_tpu.ops.join import _locate_sorted

    S = _shapes(one_chip)
    jax.jit(
        lambda b, nm, p, pn: _locate_sorted([b], nm, [p], pn, cap_b=cap_b)
    ).lower(
        S((cap_b,), jnp.int64), S((), jnp.int64),
        S((probe,), jnp.int64), S((probe,), jnp.bool_),
    ).compile()


@pytest.mark.parametrize(
    "cap_b,probe,tcap", [(1 << 15, 1 << 18, 1 << 18), (1 << 18, 1 << 20, 1 << 21)],
    ids=["build32K_probe256K", "build256K_probe1M"],
)
def test_locate_by_table_compiles(one_chip, cap_b, probe, tcap):
    """The mesh join's per-shard locate at Q3's SF1 shapes on four chips
    (customer's 150 K keys, orders' 1.5 M): the start/count tables built
    in the program, then two gathers a probe row."""
    from trino_tpu.ops.join import _locate_by_table

    S = _shapes(one_chip)
    t0 = time.perf_counter()
    jax.jit(
        lambda b, nm, p, pn, mins, widths: _locate_by_table(
            [b], nm, [p], pn, mins, widths, tcap=tcap
        )
    ).lower(
        S((cap_b,), jnp.int64), S((), jnp.int64),
        S((probe,), jnp.int64), S((probe,), jnp.bool_),
        S((1,), jnp.int64), S((1,), jnp.int64),
    ).compile()
    assert time.perf_counter() - t0 < 60


@pytest.mark.parametrize(
    "cap,outc", [(1 << 19, 1 << 10), (1 << 20, 1 << 19)],
    ids=["512K_to_1K", "1M_to_512K"],
)
def test_compact_compiles_without_scatter(one_chip, cap, outc):
    """`COMPACT` at a dynamically filtered store_sales split and at a
    lineitem split: each slot's source row comes from a one-key sort in
    blocks.  As a scatter it cost 38.8 ms a 2^19-row split whatever came out
    (13 of `star_report`'s 21 busy seconds, PERF.md section 6, PR 31); a long
    cumsum or one sort of the whole plane compiles for 11-34 s a variant."""
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.columnar.batch import COMPACT

    S = _shapes(one_chip)
    batch = Batch(
        [
            Column(S((cap,), jnp.int64), T.BIGINT, None),
            Column(S((cap, 2), jnp.int64), T.DecimalType(38, 2), None),
        ],
        S((cap,), jnp.bool_),
    )
    t0 = time.perf_counter()
    compiled = COMPACT.lower(batch, out_capacity=outc).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    assert " scatter(" not in text and " sort(" in text  # ops, not frame names
    temp = compiled.memory_analysis().temp_size_in_bytes
    # one [rows, slots] int32 plane would be 2 GiB at the smaller shape
    assert temp < (64 << 20), f"temp grew to {temp} bytes"
    assert seconds < 20, f"compiled for {seconds:.1f} s"


@pytest.mark.parametrize(
    "cap,out_cap,mode",
    [(1 << 20, 1 << 18, "partial"), (1 << 21, 1 << 21, "merge")],
    ids=["split_1M_to_256K_partial", "fold_2M_to_2M_merge"],
)
def test_agg_range_compiles_without_scatter(one_chip, cap, out_cap, mode):
    """Q18's inner aggregation (`sum(l_quantity) group by l_orderkey`) at a
    lineitem split and at its fold, rows in key order: a group is a run of
    rows, a run's sum a difference of a two-level prefix sum at two run
    ends.  As `jax.ops.segment_*` into 262 145 / 2 097 153 slots the three
    reductions cost 72 / 145 ms each whatever came out (10.85 of
    `join_agg`'s 32 busy seconds, PERF.md section 6, PR 33); one plane-wide
    cumsum compiles for 18-34 s, an `associative_scan` for minutes."""
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column
    from trino_tpu.ops.aggregation import AggregationOperator, AggSpec

    S = _shapes(one_chip)
    state = T.DecimalType(38, 2)
    spec = AggSpec("sum", 1, state, sum_bound=10**12)
    if mode == "partial":
        cols = [
            Column(S((cap,), jnp.int64), T.BIGINT, None),
            Column(S((cap,), jnp.int64), T.DecimalType(15, 2), None),
        ]
    else:
        cols = [
            Column(S((cap,), jnp.int64), T.BIGINT, None),
            Column(S((cap, 2), jnp.int64), state, None),
            Column(S((cap,), jnp.int64), T.BIGINT, None),
        ]
    op = AggregationOperator([0], [spec], [c.type for c in cols], mode=mode)
    t0 = time.perf_counter()
    compiled = jax.jit(
        op._range_step, static_argnames=("out_cap", "form")
    ).lower(
        Batch(cols, S((cap,), jnp.bool_)), S((1,), jnp.int64), S((1,), jnp.int64),
        out_cap=out_cap, form="runs",
    ).compile()
    seconds = time.perf_counter() - t0
    assert " scatter(" not in compiled.as_text()  # the op, not a frame name
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (256 << 20), f"temp grew to {temp} bytes"
    assert seconds < 30, f"compiled for {seconds:.1f} s"


# -- the cross-chip path: one program over the four chips of a v5e:2x2 ---------


def _stacked_batch(wm, cap):
    """A [W, cap] stacked scan batch of shapes: bigint key, nullable short
    decimal, date — the column kinds Q3's repartition moves."""
    from trino_tpu import types as T
    from trino_tpu.columnar import Batch, Column

    S = _shapes(wm.sharding())
    w = wm.n
    return Batch(
        [
            Column(S((w, cap), jnp.int64), T.BIGINT, None),
            Column(
                S((w, cap), jnp.int64), T.DecimalType(12, 2),
                S((w, cap), jnp.bool_),
            ),
            Column(S((w, cap), jnp.int32), T.DATE, None),
        ],
        S((w, cap), jnp.bool_),
    )


@pytest.mark.parametrize("kernel", ["exchange", "counts"])
def test_four_chip_repartition_compiles(topo, kernel):
    """Hash repartition = counts pass, then bucketize + `all_to_all` under
    shard_map, on a WorkerMesh of the four described chips (what
    `chip_smoke.py --chips 4` runs for real), at the mesh cell's shape: 2^21
    rows a worker into 4 x 2^18 slots.  Neither program scatters: as
    `.at[].set` into 2^20 + 1 slots and `segment_*` into five they cost
    3 x 192 + 2 x 126 ms a statement (PERF.md section 6, PR 36), and the
    stable int64 argsort in front of them compiled for 76 s at 2^20 rows."""
    from trino_tpu.parallel.exchange import _counts_kernel, _exchange_kernel
    from trino_tpu.parallel.spmd import WorkerMesh, spmd_collective_step

    wm = WorkerMesh(devices=list(topo.devices))
    assert wm.n == 4
    step, name = {
        "exchange": (_exchange_kernel([0], wm.n, 1 << 18), "fused_exchange_x"),
        "counts": (_counts_kernel([0], wm.n), "exchange_counts_x"),
    }[kernel]
    t0 = time.perf_counter()
    compiled = spmd_collective_step(wm, step, name).lower(
        _stacked_batch(wm, 1 << 21)
    ).compile()
    seconds = time.perf_counter() - t0
    text = compiled.as_text()
    assert " scatter(" not in text  # the op, not a frame name
    assert ("all-to-all" in text) == (kernel == "exchange")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < (128 << 20), f"temp grew to {temp} bytes"
    assert seconds < 40, f"compiled for {seconds:.1f} s"


def test_four_chip_broadcast_compiles(topo):
    from trino_tpu.parallel.exchange import _broadcast_kernel
    from trino_tpu.parallel.spmd import WorkerMesh, spmd_collective_step

    wm = WorkerMesh(devices=list(topo.devices))
    fn = spmd_collective_step(wm, _broadcast_kernel, "broadcast_x")
    compiled = fn.lower(_stacked_batch(wm, 1 << 16)).compile()
    assert "all-gather" in compiled.as_text()


@pytest.mark.parametrize("k", [0, 4, 37])
def test_divround128_compiles_small(one_chip, k):
    """The exact long-decimal divide (`int128.divround128`) over 2^20 rows:
    one `fori_loop` over the dividend's bits, uint32 limbs, so its program
    stays small and compiles in seconds whatever the rescale `k`."""
    from trino_tpu.types.int128 import divround128

    S = _shapes(one_chip)
    plane = S((1 << 20,), jnp.int64)
    fn = jax.jit(lambda ah, al, bh, bl: divround128(ah, al, bh, bl, k, 38))
    t0 = time.perf_counter()
    compiled = fn.lower(plane, plane, plane, plane).compile()
    seconds = time.perf_counter() - t0
    assert "while" in compiled.as_text()
    assert seconds < 30, f"compiled for {seconds:.1f} s"
