"""q3 / q42 / q55 in pandas: filter the dimensions, semi-filter the fact to
the surviving keys, join, group, order, limit.  Independent of the program.
``float_dtype`` below float64 is the control: the same answer computed one
precision under the one the configuration states."""

import numpy as np
import pandas as pd


def star(frames, lit, item_filter, keys, order, ascending, float_dtype):
    dd, it, ss = frames["date_dim"], frames["item"], frames["store_sales"]
    dd = dd[dd.d_moy == lit["moy"]]
    if "year" in lit:
        dd = dd[dd.d_year == lit["year"]]
    col, name = item_filter
    it = it[it[col] == lit[name]]
    date = ss["ss_sold_date_sk"].to_numpy(dtype=float, na_value=np.nan)
    item = np.asarray(ss["ss_item_sk"])
    keep = np.isin(date, dd.d_date_sk.to_numpy().astype(float)) \
        & np.isin(item, it.i_item_sk.to_numpy())
    f = pd.DataFrame({
        "d_date_sk": date[keep].astype("int64"),
        "i_item_sk": item[keep],
        "s": np.asarray(ss["ss_ext_sales_price"])[keep].astype(float_dtype)})
    f = f.merge(dd[["d_date_sk", "d_year"]], on="d_date_sk") \
         .merge(it, on="i_item_sk")
    g = f.groupby(keys, as_index=False).agg(s=("s", "sum"))
    g["s"] = g["s"].astype(float_dtype).astype("float64")
    g = g.sort_values(order, ascending=ascending, kind="mergesort").head(100)
    return [tuple(r) for r in g[keys + ["s"]].itertuples(index=False)]
