"""TPC-DS q67 in pandas: three merges and the month filter, the float64
product with COALESCE, each of the ROLLUP's nine grouping sets grouped
straight from the joined rows (absent keys NULL), RANK within i_category
(a NULL category a partition of its own), ``rk <= 100``, the ten keys
ascending with NULLs first, the first 100.  ``float_dtype`` below float64
is the control: the same answer one precision under the one the
configuration states."""

import numpy as np
import pandas as pd

from .yoy_common import num, top, words

KEYS = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
        "d_qoy", "d_moy", "s_store_id"]
INTS = ["d_year", "d_qoy", "d_moy"]
OUT = KEYS + ["sumsales", "rk"]


def joined(frames, dms, float_dtype):
    """The store_sales lines of the twelve months, with the eight keys and
    COALESCE(ss_sales_price * ss_quantity, 0)."""
    ss, dd = frames["store_sales"], frames["date_dim"]
    price = np.asarray(ss["ss_sales_price"], dtype=float).astype(float_dtype)
    qty = num(ss["ss_quantity"]).astype(float_dtype)
    value = price * qty
    lines = pd.DataFrame({
        "d_date_sk": num(ss["ss_sold_date_sk"]),
        "i_item_sk": num(ss["ss_item_sk"]),
        "s_store_sk": num(ss["ss_store_sk"]),
        "v": np.where(np.isnan(value), float_dtype(0), value)
        .astype(float_dtype)}).dropna()
    days = dd[(dd.d_month_seq >= dms) & (dd.d_month_seq <= dms + 11)]
    days = days[["d_date_sk"] + INTS].astype({"d_date_sk": float})
    st = frames["store"]
    stores = pd.concat([st[["s_store_sk"]].astype(float),
                        words(st, ["s_store_id"])], axis=1)
    it = frames["item"]
    items = pd.concat([it[["i_item_sk"]].astype(float),
                       words(it, KEYS[:4])], axis=1)
    return lines.merge(days, on="d_date_sk") \
        .merge(stores, on="s_store_sk").merge(items, on="i_item_sk")


def grouping_set(lines, n_keys, float_dtype):
    """One set of the ROLLUP: GROUP BY the first ``n_keys`` keys (NULLs of
    the data a group of their own), the others NULL."""
    present = KEYS[:n_keys]
    if present:
        g = lines.groupby(present, dropna=False, sort=False,
                          as_index=False).agg(sumsales=("v", "sum"))
    else:
        g = pd.DataFrame({"sumsales": [lines["v"].sum()]})
    for k in KEYS[n_keys:]:
        g[k] = None
    g["sumsales"] = g["sumsales"].astype(float_dtype).astype("float64")
    return g[KEYS + ["sumsales"]]


def reference(frames, literals, float_dtype="float64"):
    float_dtype = np.dtype(float_dtype).type
    lines = joined(frames, int(literals["dms"]), float_dtype)
    sets = pd.concat([grouping_set(lines, n, float_dtype)
                      for n in range(len(KEYS), -1, -1)], ignore_index=True)
    for k in INTS:
        sets[k] = pd.array(sets[k].astype(object).where(sets[k].notna(),
                                                        None), dtype="Int64")
    for k in KEYS[:4] + ["s_store_id"]:
        sets[k] = sets[k].astype(object).where(sets[k].notna(), None)
    # RANK() OVER (PARTITION BY i_category ORDER BY sumsales DESC): the
    # lowest place among equal sums; NULL is a category of its own
    part = sets["i_category"].map(lambda c: ("null",) if c is None
                                  else ("value", c))
    sets["rk"] = sets.groupby(part, sort=False)["sumsales"] \
        .rank(method="min", ascending=False).astype("int64")
    return top(sets[sets["rk"] <= 100], OUT, OUT)
