"""GROUP BY ss_customer_sk, ss_store_sk ORDER BY paid DESC, keys LIMIT 100
in pandas (NULL keys are groups; NULLs order first ascending, as Spark)."""

import numpy as np
import pandas as pd


def reference(frames, literals, float_dtype="float64"):
    ss = frames["store_sales"]
    # NULL -> 0: below every real key (keys start at 1), so NULLS FIRST
    f = pd.DataFrame({
        "c": ss["ss_customer_sk"].to_numpy(dtype="int64", na_value=0),
        "s": ss["ss_store_sk"].to_numpy(dtype="int64", na_value=0),
        "q": ss["ss_quantity"].astype("int64"),
        "p": np.asarray(ss["ss_net_paid"]).astype(float_dtype)})
    g = f.groupby(["c", "s"], as_index=False).agg(
        cnt=("q", "size"), qty=("q", "sum"), paid=("p", "sum"))
    g["paid"] = g["paid"].astype(float_dtype).astype("float64")
    g = g.sort_values(["paid", "c", "s"], ascending=[False, True, True],
                      kind="mergesort").head(100)
    return [(int(r.c) or None, int(r.s) or None, int(r.cnt), int(r.qty),
             float(r.paid)) for r in g.itertuples(index=False)]
