"""``customer_address``: the spec's 13 columns."""

import numpy as np
import pandas as pd

from benchmark.lib import datagen as D

STREAM = 8          # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``


def make(rng, rows, made) -> pd.DataFrame:
    n = rows["customer_address"]
    sk = np.arange(1, n + 1)
    return pd.DataFrame({
        "ca_address_sk": sk.astype(np.int64),
        "ca_address_id": D.ids(sk),
        "ca_street_number": [str(x) for x in rng.integers(1, 1000, n)],
        "ca_street_name": rng.choice(["Main", "Oak", "First", "Park",
                                      "Cedar", "Elm"], n),
        "ca_street_type": rng.choice(["St", "Ave", "Blvd", "Way", "Dr"], n),
        "ca_suite_number": [f"Suite {x}" for x in rng.integers(0, 100, n)],
        "ca_city": rng.choice(["Fairview", "Midway", "Oak Grove",
                               "Centerville", "Riverside", "Salem"], n),
        "ca_county": rng.choice(D.COUNTIES, n),
        "ca_state": rng.choice(D.STATES, n),
        "ca_zip": [f"{x:05d}" for x in rng.integers(10000, 99999, n)],
        "ca_country": "United States",
        "ca_gmt_offset": rng.choice([-5.0, -6.0, -7.0, -8.0], n),
        "ca_location_type": rng.choice(["apartment", "condo",
                                        "single family"], n),
    })
