"""``web_site``: the spec's 26 columns; ``web_company_name`` goes round the
spec's six names with the site's number, so every seed has each of them
('pri' among them, which q94 and q95 filter on)."""

import numpy as np
import pandas as pd

from benchmark.lib import datagen as D

STREAM = 9          # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``

COMPANY_NAMES = ["ought", "able", "pri", "ese", "anti", "cally"]


def make(rng, rows, made) -> pd.DataFrame:
    n = rows["web_site"]
    sk = np.arange(1, n + 1)
    company = sk % len(COMPANY_NAMES)
    return pd.DataFrame({
        "web_site_sk": sk.astype(np.int64),
        "web_site_id": D.ids(sk),
        "web_rec_start_date": "1997-08-16", "web_rec_end_date": None,
        "web_name": [f"site_{x // 6}" for x in sk],
        "web_open_date_sk": D.DATE0_SK - rng.integers(1, 1000, n),
        "web_close_date_sk": None,
        "web_class": "Unknown",
        "web_manager": [f"Manager {x}" for x in rng.integers(1, 50, n)],
        "web_mkt_id": rng.integers(1, 7, n).astype(np.int32),
        "web_mkt_class": [f"class {x}" for x in rng.integers(0, 50, n)],
        "web_mkt_desc": [f"market {x}" for x in rng.integers(0, 50, n)],
        "web_market_manager": [f"Mkt Manager {x}"
                               for x in rng.integers(1, 50, n)],
        "web_company_id": (company + 1).astype(np.int32),
        "web_company_name": [COMPANY_NAMES[c] for c in company],
        "web_street_number": [str(x) for x in rng.integers(1, 1000, n)],
        "web_street_name": rng.choice(["Main", "Oak", "First"], n),
        "web_street_type": rng.choice(["St", "Ave", "Blvd"], n),
        "web_suite_number": [f"Suite {x}" for x in rng.integers(0, 100, n)],
        "web_city": rng.choice(["Fairview", "Midway"], n),
        "web_county": rng.choice(D.COUNTIES, n),
        "web_state": rng.choice(D.STATES, n),
        "web_zip": [f"{x:05d}" for x in rng.integers(10000, 99999, n)],
        "web_country": "United States",
        "web_gmt_offset": rng.choice([-5.0, -6.0], n),
        "web_tax_percentage": np.round(rng.uniform(0.0, 0.12, n), 2),
    })
