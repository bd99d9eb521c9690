"""GROUP BY ss_store_sk in pandas (a NULL key is a group).  All integer:
the control's lower precision has nothing to round here."""

import pandas as pd


def reference(frames, literals, float_dtype="float64"):
    ss = frames["store_sales"]
    f = pd.DataFrame({"k": ss["ss_store_sk"],
                      "q": ss["ss_quantity"].astype("int64"),
                      "t": ss["ss_ticket_number"]})
    g = f.groupby("k", dropna=False).agg(
        cnt=("q", "size"), qty=("q", "sum"), tickets=("t", "sum"))
    return [(None if k is pd.NA else int(k), int(r.cnt), int(r.qty),
             int(r.tickets)) for k, r in g.iterrows()]
