"""``date_dim``: one row a day of the five sales years (1998-01-01 ..
2002-12-31), the spec's 28 columns; nothing is drawn."""

import datetime

import numpy as np
import pandas as pd

from benchmark.lib import datagen as D

STREAM = 0          # default_rng([seed, STREAM])
FACT = False
NEEDS = ()          # made first, handed over in ``made``


def make(rng, rows, made) -> pd.DataFrame:
    if rows["date_dim"] != D.N_DAYS:
        raise ValueError(f"date_dim has {D.N_DAYS} days, not "
                         f"{rows['date_dim']}")
    days = np.arange(D.N_DAYS)
    dates = [D.DATE0 + datetime.timedelta(days=int(i)) for i in days]
    yy = np.array([d.year for d in dates], np.int32)
    mm = np.array([d.month for d in dates], np.int32)
    dd = np.array([d.day for d in dates], np.int32)
    dow = np.array([(d.weekday() + 1) % 7 for d in dates], np.int32)
    qoy = (mm - 1) // 3 + 1
    week_seq = ((days + (D.DATE0.weekday() + 1) % 7) // 7 + 5112) \
        .astype(np.int32)
    return pd.DataFrame({
        "d_date_sk": D.DATE0_SK + days,
        "d_date_id": D.ids(D.DATE0_SK + days),
        "d_date": [d.isoformat() for d in dates],
        "d_month_seq": (yy - 1900) * 12 + (mm - 1),
        "d_week_seq": week_seq,
        "d_quarter_seq": (yy - 1900) * 4 + qoy - 1,
        "d_year": yy, "d_dow": dow, "d_moy": mm, "d_dom": dd, "d_qoy": qoy,
        "d_fy_year": yy, "d_fy_quarter_seq": (yy - 1900) * 4 + qoy - 1,
        "d_fy_week_seq": week_seq,
        "d_day_name": [D.DAY_NAMES[x] for x in dow],
        "d_quarter_name": [f"{y}Q{q}" for y, q in zip(yy, qoy)],
        "d_holiday": np.where((mm == 12) & (dd == 25), "Y", "N"),
        "d_weekend": np.where((dow == 0) | (dow == 6), "Y", "N"),
        "d_following_holiday": "N",
        "d_first_dom": (D.DATE0_SK + days - dd + 1).astype(np.int64),
        "d_last_dom": (D.DATE0_SK + days - dd + 28).astype(np.int64),
        "d_same_day_ly": D.DATE0_SK + days - 365,
        "d_same_day_lq": D.DATE0_SK + days - 91,
        "d_current_day": "N", "d_current_week": "N", "d_current_month": "N",
        "d_current_quarter": "N", "d_current_year": "N",
    })
