"""What q74 and q79 share in pandas.  Independent of the program."""

import numpy as np
import pandas as pd

from .web_common import _num as num    # noqa: F401  (a fact column as float64, NaN for NULL)


def words(frame, columns):
    """String columns as Python objects with None for NULL, so that a NULL
    compares as SQL's in a merge key never used here and prints as None."""
    out = {}
    for c in columns:
        v = frame[c].astype(object)
        out[c] = v.where(v.notna(), None)
    return pd.DataFrame(out)


def top(frame, order, columns, n=100):
    """ORDER BY ``order`` ascending, NULLs first (as this engine, Spark and
    sqlite place them for an ascending key), stable; the first ``n`` rows
    as tuples with None for NULL."""
    g = frame.sort_values(order, kind="mergesort", na_position="first") \
        .head(n)
    return [tuple(None if v is None or v is pd.NA
                  or (isinstance(v, float) and np.isnan(v)) else v
                  for v in r)
            for r in g[columns].astype(object).itertuples(index=False)]
