"""The benchmark's own copy of the TPC-DS data arithmetic.

The `tpcds` connector generates every value as a pure function of (table,
column, row index, scale) through a splitmix64 hash
(`trino_tpu/connectors/tpcds/generator.py`).  The plain reference must not
take its tables from the program, so the arithmetic of exactly the columns
the store-channel statements read (q3, q7, q27, q89) is copied here, in
plain numpy, and imports nothing of the program -- nor of the TPC-H copy
beside it.  A later change to the program's generator that alters the data
shows as `correct: false`.

Tables and columns: store_sales (the five foreign keys the statements join
on, each with its NULL mask, quantity and four money columns), date_dim
(d_date_sk, d_year, d_moy), item (i_item_sk, i_item_id, i_brand_id, i_brand,
i_class, i_category, i_manufact_id), customer_demographics (cd_demo_sk,
cd_gender, cd_marital_status, cd_education_status), promotion (p_promo_sk,
p_channel_email, p_channel_event), store (s_store_sk, s_store_name,
s_company_name, s_state).  Every dimension's surrogate key is dense
(row + 1; date_dim: row + the julian day of 1900-01-01), so a join is an
index look-up.  Money is an integer of cents; a string column is a code and
the tuple of its values.
"""

from __future__ import annotations

import datetime
import math

import numpy as np

SCHEMAS = {"tiny": 0.01, "sf1": 1.0, "sf10": 10.0, "sf100": 100.0}
SF1_ROWS = {
    "store_sales": 2_880_404, "item": 18_000, "store": 12, "promotion": 300,
    "customer_demographics": 1_920_800, "date_dim": 73_049,
}
FIXED = ("customer_demographics", "date_dim")
SLOW = ("item", "store", "promotion")  # grow with the root of the scale

#: julian day number of 1900-01-01: date_dim's first d_date_sk
JULIAN_1900 = 2_415_022
#: the five years fact sold-date keys are drawn from
SALES_START = JULIAN_1900 + (
    datetime.date(1998, 1, 2) - datetime.date(1900, 1, 1)
).days
SALES_DAYS = 365 * 5

CATEGORIES = ("Books", "Children", "Electronics", "Home", "Jewelry",
              "Men", "Music", "Shoes", "Sports", "Women")
CLASSES = tuple(f"class{i:02d}" for i in range(1, 17))
GENDER = ("F", "M")
MARITAL = ("D", "M", "S", "U", "W")
EDUCATION = ("2 yr Degree", "4 yr Degree", "Advanced Degree", "College",
             "Primary", "Secondary", "Unknown")
STORE_NAMES = ("able", "anti", "ation", "bar", "cally", "eing", "ese",
               "n st", "ought", "pri")
#: a generic string column's sixteen values, in the dictionary's (sorted) order
COMPANY_NAMES = tuple(sorted(f"name{i}" for i in range(16)))
STORE_STATES = ("AL", "AR", "AZ", "CA", "CO", "FL", "GA", "IA", "IL")
BRANDS = 5004

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_U = np.uint64


def _mix(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = x + _GOLDEN
        z = (z ^ (z >> _U(30))) * _U(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> _U(27))) * _U(0x94D049BB133111EB)
        return z ^ (z >> _U(31))


def _stream(name: str) -> np.uint64:
    h = _U(1469598103934665603)
    with np.errstate(over="ignore"):
        for ch in name.encode():
            h = (h ^ _U(ch)) * _U(1099511628211)
    return h


def randint(stream: str, idx: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Uniform integer in [lo, hi], a pure function of (stream, idx)."""
    with np.errstate(over="ignore"):
        r = _mix(
            np.asarray(idx, np.uint64) * _U(0x2545F4914F6CDD1D)
            + _stream(stream)
        )
    return (r % _U(hi - lo + 1)).astype(np.int64) + lo


def scaled_rows(table: str, sf: float) -> int:
    base = SF1_ROWS[table]
    if table in FIXED:
        return base
    if table in SLOW:
        return max(2, int(base * math.sqrt(sf)))
    return max(1, int(base * sf))


class Tpcds:
    """Columns of one schema, as host numpy arrays."""

    def __init__(self, schema: str):
        self.schema = schema
        self.sf = SCHEMAS[schema]
        self.rows = {t: scaled_rows(t, self.sf) for t in SF1_ROWS}

    def _idx(self, table: str) -> np.ndarray:
        return np.arange(self.rows[table], dtype=np.int64)

    # -- store_sales ---------------------------------------------------------

    #: foreign key -> (the dimension it points into, or None for the date)
    FACT_KEYS = {
        "ss_sold_date_sk": None, "ss_item_sk": "item",
        "ss_cdemo_sk": "customer_demographics", "ss_store_sk": "store",
        "ss_promo_sk": "promotion",
    }
    FACT_MONEY = ("ss_list_price", "ss_sales_price", "ss_ext_sales_price",
                  "ss_coupon_amt")

    def store_sales(self, columns, start: int = 0, count: int | None = None):
        """The named columns for a row range.  A foreign key comes as
        `name` (its value, drawn whether or not the row's key is NULL) and
        `name + ".valid"` (False where the key is NULL, about 1 in 26)."""
        if count is None:
            count = self.rows["store_sales"] - start
        idx = np.arange(start, start + count, dtype=np.int64)
        out = {}
        for col in columns:
            stream = f"store_sales.{col}"
            if col in self.FACT_KEYS:
                ref = self.FACT_KEYS[col]
                if ref is None:
                    vals = SALES_START + randint(stream, idx, 0, SALES_DAYS - 1)
                else:
                    vals = randint(stream, idx, 1, self.rows[ref])
                out[col] = vals
                out[col + ".valid"] = (
                    randint(stream + ".null", idx + vals, 0, 25) != 0
                )
            elif col == "ss_quantity":
                out[col] = randint(stream, idx, 1, 100)
            elif col in self.FACT_MONEY:
                out[col] = randint(stream, idx, 0, 100_00)
            else:
                raise KeyError(f"store_sales.{col} is not copied here")
        return out

    # -- date_dim ------------------------------------------------------------

    def date_dim(self) -> dict:
        idx = self._idx("date_dim")
        dates = np.datetime64("1900-01-01") + idx.astype("timedelta64[D]")
        months = dates.astype("datetime64[M]").astype(np.int64)
        return {
            "d_date_sk": idx + JULIAN_1900,
            "d_year": dates.astype("datetime64[Y]").astype(np.int64) + 1970,
            "d_moy": months % 12 + 1,
        }

    # -- item ----------------------------------------------------------------

    def item(self) -> dict:
        idx = self._idx("item")
        category = randint("item.category", idx, 0, len(CATEGORIES) - 1)
        brand_id = (category + 1) * 1_000_000 + randint(
            "item.brandm", idx, 1, 1000
        )
        return {
            "i_item_sk": idx + 1,
            "i_item_id": idx,  # code of item_id()
            "i_category": category,  # code of CATEGORIES
            "i_class": randint("item.i_class", idx, 0, len(CLASSES) - 1),
            "i_brand_id": brand_id,
            "i_brand": brand_id % BRANDS,  # code of brand()
            "i_manufact_id": randint("item.i_manufact_id", idx, 1, 1000),
        }

    @staticmethod
    def item_id(code: int) -> str:
        return f"I-{int(code) + 1:012d}"

    @staticmethod
    def brand(code: int) -> str:
        return f"Brand#{int(code) + 1:08d}"

    # -- customer_demographics -----------------------------------------------

    def customer_demographics(self) -> dict:
        """A mixed radix over (gender 2, marital status 5, education 7, ...)."""
        i = self._idx("customer_demographics")
        return {
            "cd_demo_sk": i + 1,
            "cd_gender": i % 2,  # code of GENDER
            "cd_marital_status": i // 2 % 5,  # code of MARITAL
            "cd_education_status": i // 10 % 7,  # code of EDUCATION
        }

    # -- promotion -----------------------------------------------------------

    def promotion(self) -> dict:
        """A channel flag is 'Y' on one draw in four (True = 'Y')."""
        idx = self._idx("promotion")
        return {
            "p_promo_sk": idx + 1,
            "p_channel_email":
                randint("promotion.p_channel_email", idx, 0, 3) == 1,
            "p_channel_event":
                randint("promotion.p_channel_event", idx, 0, 3) == 1,
        }

    # -- store ---------------------------------------------------------------

    def store(self) -> dict:
        idx = self._idx("store")
        return {
            "s_store_sk": idx + 1,
            "s_store_name": idx % len(STORE_NAMES),  # code of STORE_NAMES
            "s_company_name":  # code of COMPANY_NAMES
                randint("store.s_company_name", idx, 0, 15),
            "s_state": randint("store.state", idx, 0, 8),  # of STORE_STATES
        }
