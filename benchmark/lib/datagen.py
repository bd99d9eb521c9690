"""Seeded TPC-DS-shaped tables for the benchmark (a copy of the idea of
``spark_tpu.tpcds.datagen``, with the sizes and the set of tables as
arguments; imports nothing of the program).

A table is a file of its own, ``benchmark/generators/<table>.py``, found by
the name a statement's ``reads`` / ``dimensions`` give it: a statement over a
new table brings its generator with it and edits nothing here.  A generator
module states ``STREAM`` (its rng stream), ``FACT`` (written as multi-file
parquet, counted by ``fact_rows_per_s``, cached by ``fact_source=cached``),
``NEEDS`` (tables made first), optionally ``INT_COLUMNS``, and
``make(rng, rows, made)`` -> a pandas frame (a dimension) or a dict of
columns (a fact).  This file holds what the generators share.

Not dsdgen: uniform marginals from numpy, every row drawn alone, the
columns of the spec at the types ``spark_tpu/tpcds/schema.py`` declares,
referentially consistent.  Each table draws from a stream of its own,
``default_rng([seed, STREAM])``, so a table can be made alone.

Nullable key columns (4% NULL, as the original) are pandas ``Int64`` arrays:
they reach parquet as int64 with nulls.
"""

from __future__ import annotations

import datetime
import importlib
from typing import Dict, Iterable

import numpy as np
import pandas as pd

CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
              "Men", "Music", "Shoes", "Sports", "Women"]
CLASSES = ["accent", "bedding", "classical", "dresses", "estate",
           "fiction", "fitness", "pants", "portable", "romance"]
DAY_NAMES = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
             "Friday", "Saturday"]
STATES = ["TN", "CA", "TX", "NY", "OH"]
COUNTIES = ["Williamson County", "Walker County", "Ziebach County",
            "Bronx County", "Franklin Parish"]

DATE0_SK = 2450815            # 1998-01-01
DATE0 = datetime.date(1998, 1, 1)
N_DAYS = 5 * 365 + 1          # the five sales years, 1998-01-01 .. 2002-12-31

#: bytes of a declared SQL type (the roofline's widths)
TYPE_BYTES = {"bigint": 8, "int": 4, "double": 8}


def generator(table: str):
    """The module that makes ``table``: ``benchmark/generators/<table>.py``."""
    try:
        return importlib.import_module(f"benchmark.generators.{table}")
    except ModuleNotFoundError as e:
        raise ValueError(f"no generator benchmark/generators/{table}.py") \
            from e


def is_fact(table: str) -> bool:
    return bool(generator(table).FACT)


def column_type(table: str, column: str) -> str:
    """Declared SQL type of a FACT column (``spark_tpu/tpcds/schema.py``:
    surrogate keys and ticket / order numbers bigint, the generator's
    ``INT_COLUMNS`` int, money double)."""
    if column in getattr(generator(table), "INT_COLUMNS", ()):
        return "int"
    if column.endswith("_sk") or column.endswith("_number"):
        return "bigint"
    return "double"


def ids(sk) -> list:
    return [f"AAAAAAAA{x:08d}" for x in sk]


def nullable(rng, arr, frac=0.04):
    out = pd.array(np.asarray(arr, np.int64), dtype="Int64")
    out[rng.random(len(out)) < frac] = pd.NA
    return out


def sales_columns(rng, n, sizes) -> dict:
    """The columns every sales channel shares (prefix added by the caller)."""
    def key(size):
        return rng.integers(1, size + 1, n).astype(np.int64)

    qty = rng.integers(1, 101, n)
    wholesale = np.round(rng.uniform(1.0, 100.0, n), 2)
    list_price = np.round(wholesale * rng.uniform(1.0, 2.0, n), 2)
    sales_price = np.round(list_price * rng.uniform(0.2, 1.0, n), 2)
    ext_sales = np.round(sales_price * qty, 2)
    ext_wholesale = np.round(wholesale * qty, 2)
    ext_tax = np.round(ext_sales * 0.08, 2)
    coupon = np.round(ext_sales * rng.choice([0.0, 0.0, 0.0, 0.1], n), 2)
    net_paid = np.round(ext_sales - coupon, 2)
    return {
        "sold_date_sk": nullable(rng, DATE0_SK + rng.integers(0, N_DAYS, n)),
        "sold_time_sk": rng.integers(0, 86400, n).astype(np.int64),
        "item_sk": key(sizes["item"]),
        "customer_sk": nullable(rng, key(sizes["customer"])),
        "cdemo_sk": key(sizes["customer_demographics"]),
        "hdemo_sk": key(sizes["household_demographics"]),
        "addr_sk": key(sizes["customer_address"]),
        "store_sk": nullable(rng, key(sizes["store"])),
        "promo_sk": key(sizes["promotion"]),
        "quantity": qty.astype(np.int32),
        "wholesale_cost": wholesale, "list_price": list_price,
        "sales_price": sales_price,
        "ext_discount_amt": np.round((list_price - sales_price) * qty, 2),
        "ext_sales_price": ext_sales, "ext_wholesale_cost": ext_wholesale,
        "ext_list_price": np.round(list_price * qty, 2),
        "ext_tax": ext_tax, "coupon_amt": coupon, "net_paid": net_paid,
        "net_paid_inc_tax": np.round(net_paid + ext_tax, 2),
        "net_profit": np.round(net_paid - ext_wholesale, 2),
    }


def generate(seed: int, rows: Dict[str, int], tables: Iterable[str],
             dimension_seed: "int | None" = None) -> dict:
    """The ``tables`` asked for (and no other in the result), at the row
    counts in ``rows`` (every table made or a key column names: a missing
    one is an error, not a default).  A dimension is a pandas frame; a fact
    is a plain dict of columns (numpy arrays, nullable keys as pandas
    ``Int64`` arrays) — a 23-column frame of 2.9M rows costs pandas 4 s of
    copying that nothing needs.  ``dimension_seed``, where given, seeds the
    dimensions in place of ``seed`` (the facts reference a dimension by its
    key range alone, so they stay consistent)."""
    want = list(tables)
    made: dict = {}

    def make(t):
        if t not in made:
            gen = generator(t)
            for dep in gen.NEEDS:
                make(dep)
            s = seed if gen.FACT or dimension_seed is None else dimension_seed
            rng = np.random.default_rng([int(s), int(gen.STREAM)])
            made[t] = gen.make(rng, rows, made)
        return made[t]

    for t in want:
        make(t)
    return {t: made[t] for t in want}
