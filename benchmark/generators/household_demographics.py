"""``household_demographics``: the spec's 5 columns.  As in the spec the
table is the cross product of its four domains (20 income bands x 6 buying
potentials x 10 dependent counts x 6 vehicle counts = 7,200 rows at every
scale factor); nothing is drawn."""

import numpy as np
import pandas as pd

STREAM = 11         # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``

BUY_POTENTIAL = ["Unknown", "0-500", "501-1000", "1001-5000", "5001-10000",
                 ">10000"]


def make(rng, rows, made) -> pd.DataFrame:
    k = np.arange(rows["household_demographics"])
    return pd.DataFrame({
        "hd_demo_sk": (k + 1).astype(np.int64),
        "hd_income_band_sk": (k % 20 + 1).astype(np.int64),
        "hd_buy_potential": np.array(BUY_POTENTIAL, object)[k // 20 % 6],
        "hd_dep_count": (k // 120 % 10).astype(np.int32),
        "hd_vehicle_count": (k // 1200 % 6 - 1).astype(np.int32),
    })
