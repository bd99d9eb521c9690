"""``store``: the spec's 29 columns."""

import numpy as np
import pandas as pd

from benchmark.lib import datagen as D

STREAM = 2          # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``


def make(rng, rows, made) -> pd.DataFrame:
    n = rows["store"]
    sk = np.arange(1, n + 1)
    return pd.DataFrame({
        "s_store_sk": sk.astype(np.int64),
        "s_store_id": D.ids(sk),
        "s_rec_start_date": "1997-03-13", "s_rec_end_date": None,
        "s_closed_date_sk": None,
        "s_store_name": rng.choice(["ought", "able", "pri", "ese", "anti",
                                    "cally", "ation", "eing"], n),
        "s_number_employees": rng.integers(200, 300, n).astype(np.int32),
        "s_floor_space": rng.integers(5000000, 10000000, n).astype(np.int32),
        "s_hours": rng.choice(["8AM-8AM", "8AM-4PM", "8AM-12AM"], n),
        "s_manager": [f"Manager {x}" for x in rng.integers(1, 50, n)],
        "s_market_id": rng.integers(1, 11, n).astype(np.int32),
        "s_geography_class": "Unknown",
        "s_market_desc": [f"market {x}" for x in rng.integers(0, 50, n)],
        "s_market_manager": [f"Mkt Manager {x}"
                             for x in rng.integers(1, 50, n)],
        "s_division_id": np.ones(n, np.int32),
        "s_division_name": "Unknown",
        "s_company_id": np.ones(n, np.int32),
        "s_company_name": "Unknown",
        "s_street_number": [str(x) for x in rng.integers(1, 1000, n)],
        "s_street_name": rng.choice(["Main", "Oak", "First"], n),
        "s_street_type": rng.choice(["St", "Ave", "Blvd"], n),
        "s_suite_number": [f"Suite {x}" for x in rng.integers(0, 100, n)],
        "s_city": rng.choice(["Fairview", "Midway"], n),
        "s_county": rng.choice(D.COUNTIES, n),
        "s_state": rng.choice(D.STATES, n),
        "s_zip": [f"{x:05d}" for x in rng.integers(10000, 99999, n)],
        "s_country": "United States",
        "s_gmt_offset": rng.choice([-5.0, -6.0], n),
        "s_tax_precentage": np.round(rng.uniform(0.0, 0.11, n), 2),
    })
