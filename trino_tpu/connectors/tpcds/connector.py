"""TPC-DS connector plumbing (reference: plugin/trino-tpcds —
TpcdsConnectorFactory.java / TpcdsMetadata.java / TpcdsSplitManager;
row-range splits mirror TpcdsSplitManager's per-node partitioning)."""

from __future__ import annotations

import math

from trino_tpu.connectors.api import (
    ColumnMeta,
    ColumnStatistics,
    Connector,
    ConnectorMetadata,
    PageSource,
    Split,
    TableHandle,
    TableMetadata,
    TableStatistics,
)
from trino_tpu.connectors.tpcds import schema as ds_schema
from trino_tpu.connectors.tpcds.generator import TpcdsGenerator, generator


#: (schema, table) -> TableStatistics: the data is a pure function of both
_STATISTICS: dict = {}


class TpcdsMetadata(ConnectorMetadata):
    def list_schemas(self):
        return sorted(ds_schema.SCHEMAS)

    def list_tables(self, schema: str):
        ds_schema.schema_scale(schema)
        return sorted(ds_schema.TABLES)

    def table_metadata(self, schema: str, table: str) -> TableMetadata:
        ds_schema.schema_scale(schema)
        if table not in ds_schema.TABLES:
            raise KeyError(f"tpcds table not found: {table}")
        cols = tuple(
            ColumnMeta(name, t) for name, t in ds_schema.column_types(table)
        )
        return TableMetadata(schema, table, cols)

    def table_statistics(self, schema: str, table: str) -> TableStatistics:
        # the optimizer's rules and the verifiers ask once per scan each:
        # Q27's plan asked 126 times, half a second of every statement
        stats = _STATISTICS.get((schema, table))
        if stats is None:
            stats = _STATISTICS[schema, table] = self._statistics(schema, table)
        return stats

    def _statistics(self, schema: str, table: str) -> TableStatistics:
        """Column stats derived from the generator's own rules (reference:
        plugin/trino-tpcds/.../statistics/ precomputed stats files): surrogate
        PKs are dense 1..n; FKs inherit the referenced dimension's key range;
        date FKs span the SALES window; fact-table FKs are ~4% NULL."""
        from trino_tpu.connectors.tpcds.generator import (
            _FACTS,
            _FK_SUFFIX,
            SALES_DAYS,
            SALES_START,
        )

        sf = ds_schema.schema_scale(schema)
        gen = generator(sf)
        rows = gen.row_count(table)
        cols = {}
        is_fact = table in _FACTS
        nullf = 0.04 if is_fact else 0.0
        pk = ds_schema.TABLES[table][0][0]
        for name, _t in ds_schema.TABLES[table]:
            if name == pk and name.endswith("_sk") and not is_fact:
                # dense surrogate key: the distinct count is a structural
                # fact, admissible as a uniqueness proof.  time_dim's PK
                # is 0-based (generator._t_time_dim returns the raw row
                # index) where every other dimension PK is 1-based
                # (idx + 1); claiming [1, rows] for it was unsound.
                # d_date_sk is julian-based, overridden below.
                lo = 0 if table == "time_dim" else 1
                cols[name] = ColumnStatistics(
                    distinct_count=rows, low=lo, high=lo + rows - 1,
                    exact_distinct=True,
                )
                continue
            if name.endswith("_date_sk"):
                # returns tables lag their parent sale by 1..90 days
                # (generator._return_column), so the returned-date range
                # extends past the sales window — the plain sales-window
                # claim was UNSOUND for *_returned_date_sk (caught by the
                # stats-vs-generator validation test)
                lag = 90 if name.endswith("_returned_date_sk") else 0
                cols[name] = ColumnStatistics(
                    distinct_count=min(rows, SALES_DAYS + lag),
                    low=SALES_START + (1 if lag else 0),
                    high=SALES_START + SALES_DAYS - 1 + lag,
                    null_fraction=nullf,
                )
                continue
            if name.endswith("_time_sk"):
                cols[name] = ColumnStatistics(
                    distinct_count=min(rows, 86_400), low=0, high=86_399,
                    null_fraction=nullf,
                )
                continue
            for suffix, ref in _FK_SUFFIX:
                if name.endswith(suffix):
                    ref_rows = gen.row_count(ref)
                    cols[name] = ColumnStatistics(
                        distinct_count=min(rows, ref_rows),
                        low=1,
                        high=ref_rows,
                        null_fraction=nullf,
                    )
                    break
            if name in cols:
                continue
            # generic-rule ranges: exact by construction (the generator's
            # own randint bounds), admissible for numeric/capacity proofs —
            # quantity/price/measure columns stop reading as full-dtype
            rng = gen.column_range(table, name)
            if rng is not None:
                cols[name] = ColumnStatistics(low=rng[0], high=rng[1])
        if table == "date_dim":
            import numpy as np

            base = np.datetime64("1900-01-01")
            from trino_tpu.connectors.tpcds.generator import JULIAN_1900

            # the calendar runs `rows` consecutive days from 1900-01-01;
            # every derived sequence below is an exact function of the row
            # index (see generator._t_date_dim), so these bounds are the
            # generator's own rules, not estimates
            months0_max = int(
                (base + np.timedelta64(max(0, rows - 1), "D"))
                .astype("datetime64[M]")
                .astype(np.int64)
            ) + 70 * 12
            cols["d_date_sk"] = ColumnStatistics(
                # FIX: the dense-PK rule above claimed [1, rows], but
                # d_date_sk is julian-day based (idx + JULIAN_1900) — the
                # old claim was unsound for any proof reading it
                distinct_count=rows, low=JULIAN_1900,
                high=JULIAN_1900 + rows - 1, exact_distinct=True,
            )
            cols["d_year"] = ColumnStatistics(
                distinct_count=201, low=1900, high=2100
            )
            cols["d_fy_year"] = cols["d_year"]
            cols["d_date"] = ColumnStatistics(
                distinct_count=rows, exact_distinct=True,
                low=int((base - np.datetime64("1970-01-01")).astype(int)),
                high=int((base - np.datetime64("1970-01-01")).astype(int)) + rows,
            )
            cols["d_moy"] = ColumnStatistics(distinct_count=12, low=1, high=12)
            cols["d_dom"] = ColumnStatistics(distinct_count=31, low=1, high=31)
            cols["d_dow"] = ColumnStatistics(distinct_count=7, low=0, high=6)
            cols["d_qoy"] = ColumnStatistics(distinct_count=4, low=1, high=4)
            week_hi = rows // 7 + 1
            cols["d_week_seq"] = ColumnStatistics(
                distinct_count=week_hi, low=1, high=week_hi
            )
            cols["d_fy_week_seq"] = cols["d_week_seq"]
            cols["d_month_seq"] = ColumnStatistics(
                distinct_count=months0_max + 1, low=0, high=months0_max
            )
            quarter_hi = months0_max // 3 + 1
            cols["d_quarter_seq"] = ColumnStatistics(
                distinct_count=quarter_hi, low=1, high=quarter_hi
            )
            cols["d_fy_quarter_seq"] = cols["d_quarter_seq"]
        return TableStatistics(row_count=rows, columns=cols)


class TpcdsPageSource(PageSource):
    def __init__(self, gen: TpcdsGenerator, split: Split, columns, page_rows: int):
        self.gen = gen
        self.split = split
        self.columns = list(columns)
        self.page_rows = page_rows

    def row_count(self) -> int:
        return self.split.row_count

    def pages(self):
        t = self.split.table.table
        start, remaining = self.split.row_start, self.split.row_count
        while remaining > 0:
            n = min(self.page_rows, remaining)
            yield [self.gen.column(t, c, start, n) for c in self.columns]
            start += n
            remaining -= n


class TpcdsConnector(Connector):
    name = "tpcds"

    def __init__(self):
        self._metadata = TpcdsMetadata()

    def metadata(self) -> TpcdsMetadata:
        return self._metadata

    def scan_version(self, handle):
        return 0  # generated data is immutable per (schema, table)

    def global_dictionary(self, handle: TableHandle, column: str):
        """tpcds string columns code against one trace-stable dictionary
        per (table, column, scale factor).  String ``*_id`` business keys
        on dimension tables are idx-coded null-free bijections (generic
        rule + d_date_id: code == row index, dictionary size == row
        count), so they carry the `unique` capacity claim."""
        from trino_tpu.connectors.tpcds.generator import _FACTS

        try:
            sf = ds_schema.schema_scale(handle.schema)
            gen = generator(sf)
            d = gen.dictionary(handle.table, column)
        except (KeyError, ValueError):
            return None
        if d is None:
            return None
        unique = (
            handle.table not in _FACTS
            and column.endswith("_id")
            and len(d.values) == gen.row_count(handle.table)
        )
        return d, unique

    def splits(self, handle: TableHandle, target_splits: int, predicate=None):
        sf = ds_schema.schema_scale(handle.schema)
        n = generator(sf).row_count(handle.table)
        nsplits = max(1, min(target_splits, math.ceil(n / 1024)))
        per = math.ceil(n / nsplits)
        out = []
        for i in range(nsplits):
            a = i * per
            b = min(n, a + per)
            if a >= b:
                break
            out.append(Split(handle, i, row_start=a, row_count=b - a))
        return out

    def page_source(self, split: Split, columns, max_rows_per_page: int = 1 << 20):
        sf = ds_schema.schema_scale(split.table.schema)
        return TpcdsPageSource(generator(sf), split, columns, max_rows_per_page)
