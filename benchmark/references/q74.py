"""TPC-DS q74 in pandas: a year's total a customer and channel (two merges
and a ``groupby`` a channel), the four filtered totals merged on the
customer's id, the ratio test, the order, the first 100.  ``float_dtype``
below float64 is the control: the same answer one precision under the one
the configuration states."""

import numpy as np
import pandas as pd

from .yoy_common import num, top, words

KEYS = ["c_customer_id", "c_first_name", "c_last_name", "d_year"]


def year_total(frames, fact, customer_col, date_col, paid_col, years,
               float_dtype):
    """SELECT c_customer_id, c_first_name, c_last_name, d_year, SUM(paid)
    FROM customer, <fact>, date_dim WHERE ... GROUP BY the four."""
    f = frames[fact]
    dd, cu = frames["date_dim"], frames["customer"]
    lines = pd.DataFrame({
        "c_customer_sk": num(f[customer_col]), "d_date_sk": num(f[date_col]),
        "paid": np.asarray(f[paid_col]).astype(float_dtype)}).dropna()
    days = dd[dd.d_year.isin(years)][["d_date_sk", "d_year"]]
    names = pd.concat([cu[["c_customer_sk"]], words(cu, KEYS[:3])], axis=1)
    lines = lines.merge(days.astype({"d_date_sk": float}), on="d_date_sk") \
        .merge(names.astype({"c_customer_sk": float}), on="c_customer_sk")
    g = lines.groupby(KEYS, dropna=False, as_index=False) \
        .agg(year_total=("paid", "sum"))
    g["year_total"] = g["year_total"].astype(float_dtype).astype("float64")
    return g


def reference(frames, literals, float_dtype="float64"):
    year, year1 = int(literals["year"]), int(literals["year1"])
    totals = {
        "s": year_total(frames, "store_sales", "ss_customer_sk",
                        "ss_sold_date_sk", "ss_net_paid", [year, year1],
                        float_dtype),
        "w": year_total(frames, "web_sales", "ws_bill_customer_sk",
                        "ws_sold_date_sk", "ws_net_paid", [year, year1],
                        float_dtype)}

    def of(channel, y, name):
        t = totals[channel]
        return t[t.d_year == y].rename(columns={"year_total": name})

    # customer_id is never NULL, so a merge on it is the SQL join
    j = of("s", year1, "s2") \
        .merge(of("s", year, "s1")[["c_customer_id", "s1"]],
               on="c_customer_id") \
        .merge(of("w", year1, "w2")[["c_customer_id", "w2"]],
               on="c_customer_id") \
        .merge(of("w", year, "w1")[["c_customer_id", "w1"]],
               on="c_customer_id")
    # year_total > 0 on both first years; under it neither CASE takes its
    # NULL arm, and a NULL comparison would drop the row
    j = j[(j.s1 > 0) & (j.w1 > 0)]
    j = j[j.w2 * 1.0 / j.w1 > j.s2 * 1.0 / j.s1]
    return top(j, KEYS[:3], KEYS[:3])
