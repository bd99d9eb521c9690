"""``catalog_sales``: the spec's 34 columns; a third of the rows carry a
store return's (customer, item) and a date within 60 days of it."""

import numpy as np

from benchmark.lib import datagen as D

STREAM = 5          # default_rng([seed, STREAM])
FACT = True
NEEDS = ("store_sales", "store_returns")      # made first, in ``made``
INT_COLUMNS = ("cs_quantity",)


def make(rng, rows, made) -> dict:
    n, sizes, sr = rows["catalog_sales"], rows, made["store_returns"]
    s = D.sales_columns(rng, n, sizes)
    # a third of catalog sales carry a store-return's (customer, item) and a
    # date within 60 days of the return: the cross-channel identity of q17
    n_sr = len(sr["sr_item_sk"])
    n_link = min(n // 3, 10 * n_sr)
    pick = rng.integers(0, n_sr, n_link)
    cust, item, date = s["customer_sk"], s["item_sk"], s["sold_date_sk"]
    cust[:n_link] = sr["sr_customer_sk"][pick]
    item[:n_link] = sr["sr_item_sk"][pick]
    date[:n_link] = sr["sr_returned_date_sk"][pick] \
        + rng.integers(0, 60, n_link)
    sold = date.to_numpy(dtype=np.int64, na_value=D.DATE0_SK)
    ship_cost = np.round(s["ext_sales_price"] * 0.05, 2)

    def key(size):
        return rng.integers(1, size + 1, n).astype(np.int64)

    cols = {
        "cs_sold_date_sk": date, "cs_sold_time_sk": s["sold_time_sk"],
        "cs_ship_date_sk": sold + rng.integers(1, 120, n),
        "cs_bill_customer_sk": cust, "cs_bill_cdemo_sk": s["cdemo_sk"],
        "cs_bill_hdemo_sk": s["hdemo_sk"], "cs_bill_addr_sk": s["addr_sk"],
        "cs_ship_customer_sk": cust, "cs_ship_cdemo_sk": s["cdemo_sk"],
        "cs_ship_hdemo_sk": s["hdemo_sk"], "cs_ship_addr_sk": s["addr_sk"],
        "cs_call_center_sk": key(sizes["call_center"]),
        "cs_catalog_page_sk": key(sizes["catalog_page"]),
        "cs_ship_mode_sk": key(20), "cs_warehouse_sk": key(sizes["warehouse"]),
        "cs_item_sk": item, "cs_promo_sk": s["promo_sk"],
        "cs_order_number": np.arange(1, n + 1, dtype=np.int64),
    }
    for c in ("quantity", "wholesale_cost", "list_price", "sales_price",
              "ext_discount_amt", "ext_sales_price", "ext_wholesale_cost",
              "ext_list_price", "ext_tax", "coupon_amt"):
        cols[f"cs_{c}"] = s[c]
    cols["cs_ext_ship_cost"] = ship_cost
    cols["cs_net_paid"] = s["net_paid"]
    cols["cs_net_paid_inc_tax"] = s["net_paid_inc_tax"]
    cols["cs_net_paid_inc_ship"] = np.round(s["net_paid"] + ship_cost, 2)
    cols["cs_net_paid_inc_ship_tax"] = np.round(
        s["net_paid_inc_tax"] + ship_cost, 2)
    cols["cs_net_profit"] = s["net_profit"]
    return cols
