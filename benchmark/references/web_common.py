"""q94 / q95 in pandas: which orders qualify, which ``web_sales`` lines pass
the three dimension filters, then one row of a distinct count and two sums.
Independent of the program.  ``float_dtype`` below float64 is the control:
the same answer computed one precision under the one the configuration
states.

The subqueries are stated as what they mean of an order (a CPU test holds
this reading to the statement's text: it equals sqlite on it):

* q95's ``ws_wh`` holds an order number once for every pair of the order's
  lines whose warehouses are both non-NULL and differ, so ``IN (SELECT
  ws_order_number FROM ws_wh)`` says: the order has two non-NULL warehouses
  that differ; ``IN (SELECT wr_order_number FROM web_returns, ws_wh WHERE
  ...)`` says: such an order, with a return.
* q94's ``EXISTS (... ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)`` says the
  same of the order AND that the line's own warehouse is not NULL;
  ``NOT EXISTS`` says: the order has no return.
"""

import numpy as np
import pandas as pd


def _num(col):
    """A fact column as float64 with NaN for NULL (keys are far below 2^53)."""
    return col.to_numpy(dtype=float, na_value=np.nan) \
        if hasattr(col, "isna") else np.asarray(col, dtype=float)


def web_orders(frames, lit, own_warehouse, returned, float_dtype):
    ws, wr = frames["web_sales"], frames["web_returns"]
    dd, ca, web = (frames[t] for t in
                   ("date_dim", "customer_address", "web_site"))
    days = dd[(dd.d_date >= lit["date_lo"])
              & (dd.d_date <= lit["date_hi"])].d_date_sk.to_numpy()
    addrs = ca[ca.ca_state == lit["state"]].ca_address_sk.to_numpy()
    sites = web[web.web_company_name == lit["company"]] \
        .web_site_sk.to_numpy()
    order = np.asarray(ws["ws_order_number"])
    warehouse = _num(ws["ws_warehouse_sk"])
    # orders with two non-NULL warehouses that differ
    w = pd.DataFrame({"o": order, "w": warehouse}).dropna()
    n_wh = w.groupby("o").w.nunique()
    split = np.isin(order, n_wh[n_wh >= 2].index.to_numpy())
    has_return = np.isin(order, np.asarray(wr["wr_order_number"]))
    keep = split & (has_return if returned else ~has_return) \
        & np.isin(_num(ws["ws_ship_date_sk"]), days.astype(float)) \
        & np.isin(_num(ws["ws_ship_addr_sk"]), addrs.astype(float)) \
        & np.isin(_num(ws["ws_web_site_sk"]), sites.astype(float))
    if own_warehouse:
        keep &= ~np.isnan(warehouse)
    sums = []
    for c in ("ws_ext_ship_cost", "ws_net_profit"):
        v = np.asarray(ws[c])[keep].astype(float_dtype)
        # SUM over no row is NULL
        sums.append(float(v.sum(dtype=float_dtype)) if len(v) else None)
    return [(int(len(np.unique(order[keep]))), sums[0], sums[1])]
