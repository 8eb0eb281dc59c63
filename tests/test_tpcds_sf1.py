"""TPC-DS spot checks at SF1 (round-3 gap: nothing validated TPC-DS beyond
schema `tiny`).  A representative query slice runs at sf1 and must (a)
complete within the memory budget machinery, (b) agree exactly with the
8-worker distributed mesh run, and (c) return plausible non-degenerate
shapes.  NOT in the smoke tier — this is the slow-ring (ring 2/3) check.

Reference role: the reference validates connectors at scale via
product-tests/benchto at SF>=1; the oracle here is engine-vs-engine
(local == distributed), the same independence DistributedQueryRunner tests
rely on.
"""

import pytest


from trino_tpu.connectors.tpcds.queries import QUERIES

pytestmark = pytest.mark.heavy

#: structurally diverse slice: star joins (3, 7, 19), date-dim correlated
#: subquery (25), grouping breadth (42, 52), inventory semi-join shape (82),
#: ROLLUP (27) and a window over the grouped rows (89): with 3 and 7 the
#: four statements of the benchmark's `tpcds_sf1.star_report`
SPOT = [3, 7, 19, 25, 27, 42, 52, 82, 89]


@pytest.fixture(scope="module")
def local():
    from trino_tpu.runtime.runner import LocalQueryRunner

    return LocalQueryRunner(catalog="tpcds", schema="sf1", target_splits=4)


@pytest.fixture(scope="module")
def mesh():
    from trino_tpu.parallel.runner import DistributedQueryRunner

    return DistributedQueryRunner(catalog="tpcds", schema="sf1")


def _sql(qid: int) -> str:
    """The suite's text, but for 27 and 89 the benchmark's template with a
    seed's parameters (`tpcds_sf1.star_report`): as written they name a
    state and classes this generator has not, and answer from no rows."""
    if qid not in (27, 89):
        return QUERIES[qid]
    from benchmark.harness import spec, traffic

    mix = traffic.Mix(spec.Cell("tpcds_sf1.star_report").traffic, 1)
    return {st.query: st.sql for st in mix.warmup()}[f"q{qid}"]


@pytest.mark.parametrize("qid", SPOT)
def test_sf1_local_vs_mesh(local, mesh, qid):
    sql = _sql(qid)
    a = local.execute(sql)
    b = mesh.execute(sql)
    assert a.column_names == b.column_names
    # ROLLUP's absent keys are NULL (q27): order rows with None last
    key = lambda row: tuple((v is None, v) for v in row)
    assert sorted(map(tuple, a.rows), key=key) == sorted(
        map(tuple, b.rows), key=key
    )
    assert a.row_count > 0, f"q{qid} degenerate empty result at sf1"
