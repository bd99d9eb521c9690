"""TPC-DS q17 in pandas (NULL keys never join: dropped up front)."""

import numpy as np
import pandas as pd


def reference(frames, literals, float_dtype="float64"):
    dd = frames["date_dim"]
    q1 = dd[dd.d_quarter_name == "2000Q1"].d_date_sk.to_numpy()
    q123 = dd[dd.d_quarter_name.isin(
        ["2000Q1", "2000Q2", "2000Q3"])].d_date_sk.to_numpy()

    def num(table, cols):
        t = frames[table]
        out = pd.DataFrame({
            c: (t[c].to_numpy(dtype=float, na_value=np.nan)
                if hasattr(t[c], "isna") else np.asarray(t[c]))
            for c in cols})
        return out.dropna()

    ss = num("store_sales",
             ["ss_sold_date_sk", "ss_item_sk", "ss_store_sk",
              "ss_customer_sk", "ss_ticket_number", "ss_quantity"])
    ss = ss[ss.ss_sold_date_sk.isin(q1)]
    sr = num("store_returns",
             ["sr_returned_date_sk", "sr_item_sk", "sr_customer_sk",
              "sr_ticket_number", "sr_return_quantity"])
    sr = sr[sr.sr_returned_date_sk.isin(q123)]
    cs = num("catalog_sales",
             ["cs_sold_date_sk", "cs_bill_customer_sk", "cs_item_sk",
              "cs_quantity"])
    cs = cs[cs.cs_sold_date_sk.isin(q123)]
    j = ss.merge(sr, left_on=["ss_customer_sk", "ss_item_sk",
                              "ss_ticket_number"],
                 right_on=["sr_customer_sk", "sr_item_sk",
                           "sr_ticket_number"])
    j = j.merge(cs, left_on=["sr_customer_sk", "sr_item_sk"],
                right_on=["cs_bill_customer_sk", "cs_item_sk"])
    j = j.merge(frames["store"][["s_store_sk", "s_state"]],
                left_on="ss_store_sk", right_on="s_store_sk")
    j = j.merge(frames["item"][["i_item_sk", "i_item_id", "i_item_desc"]],
                left_on="ss_item_sk", right_on="i_item_sk")
    keys = ["i_item_id", "i_item_desc", "s_state"]
    spec = {}
    for tag, col in (("ss", "ss_quantity"), ("sr", "sr_return_quantity"),
                     ("cs", "cs_quantity")):
        j[col] = j[col].astype(float_dtype)
        spec[f"{tag}_n"] = (col, "count")
        spec[f"{tag}_avg"] = (col, lambda v: float(
            v.to_numpy().mean(dtype=float_dtype)))
        spec[f"{tag}_sd"] = (col, lambda v: float(
            v.to_numpy().std(ddof=1, dtype=float_dtype))
            if len(v) > 1 else float("nan"))
    g = j.groupby(keys, as_index=False).agg(**spec)
    g = g.sort_values(keys, kind="mergesort").head(100)
    return [tuple(r) for r in g.itertuples(index=False)]
