"""TPC-DS q79 in pandas: ``store_sales`` lines of the chosen days, stores
and households, summed a ticket, customer, address and city, joined to
``customer``, ordered by the names.  ``float_dtype`` below float64 is the
control: the same answer one precision under the one the configuration
states."""

import numpy as np
import pandas as pd

from .yoy_common import num, top, words


def reference(frames, literals, float_dtype="float64"):
    ss, dd, st, hd, cu = (frames[t] for t in (
        "store_sales", "date_dim", "store", "household_demographics",
        "customer"))
    days = dd[(dd.d_dow == 1) & dd.d_year.isin([1999, 2000, 2001])] \
        .d_date_sk.to_numpy().astype(float)
    stores = st[(st.s_number_employees >= 200)
                & (st.s_number_employees <= 295)][["s_store_sk", "s_city"]]
    homes = hd[(hd.hd_dep_count == 6) | (hd.hd_vehicle_count > 2)] \
        .hd_demo_sk.to_numpy()
    date, store = num(ss["ss_sold_date_sk"]), num(ss["ss_store_sk"])
    keep = np.isin(date, days) \
        & np.isin(store, stores.s_store_sk.to_numpy().astype(float)) \
        & np.isin(np.asarray(ss["ss_hdemo_sk"]), homes)
    f = pd.DataFrame({
        "ss_ticket_number": np.asarray(ss["ss_ticket_number"])[keep],
        "ss_customer_sk": num(ss["ss_customer_sk"])[keep],
        "ss_addr_sk": np.asarray(ss["ss_addr_sk"])[keep],
        "s_store_sk": store[keep].astype("int64"),
        "amt": np.asarray(ss["ss_coupon_amt"])[keep].astype(float_dtype),
        "profit": np.asarray(ss["ss_net_profit"])[keep].astype(float_dtype),
    }).merge(stores, on="s_store_sk")
    g = f.groupby(["ss_ticket_number", "ss_customer_sk", "ss_addr_sk",
                   "s_city"], dropna=False, as_index=False) \
        .agg(amt=("amt", "sum"), profit=("profit", "sum"))
    for c in ("amt", "profit"):
        g[c] = g[c].astype(float_dtype).astype("float64")
    names = pd.concat([cu[["c_customer_sk"]],
                       words(cu, ["c_last_name", "c_first_name"])], axis=1)
    # a NULL ss_customer_sk joins no customer
    g = g.dropna(subset=["ss_customer_sk"]).merge(
        names.astype({"c_customer_sk": float}),
        left_on="ss_customer_sk", right_on="c_customer_sk")
    g["city"] = g.s_city.str[:30]
    return top(g, ["c_last_name", "c_first_name", "city", "profit",
                   "ss_ticket_number"],
               ["c_last_name", "c_first_name", "city", "ss_ticket_number",
                "amt", "profit"])
