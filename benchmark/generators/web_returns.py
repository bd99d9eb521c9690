"""``web_returns``: the spec's 24 columns; a sample of ``web_sales`` lines
without replacement, each carrying its line's order number and item (so
(``wr_order_number``, ``wr_item_sk``) is a key, as in dsdgen)."""

import numpy as np

from benchmark.lib import datagen as D

STREAM = 7          # default_rng([seed, STREAM])
FACT = True
NEEDS = ("web_sales",)            # made first, handed over in ``made``
INT_COLUMNS = ("wr_return_quantity",)


def make(rng, rows, made) -> dict:
    n, ws = rows["web_returns"], made["web_sales"]
    idx = np.sort(rng.choice(len(ws["ws_item_sk"]), n, replace=False))
    line = {c: ws[c][idx] for c in (
        "ws_quantity", "ws_sales_price", "ws_ship_date_sk", "ws_item_sk",
        "ws_bill_customer_sk", "ws_bill_cdemo_sk", "ws_bill_hdemo_sk",
        "ws_bill_addr_sk", "ws_web_page_sk", "ws_order_number")}
    qty = np.minimum(rng.integers(1, 101, n), line["ws_quantity"])
    amt = np.round(line["ws_sales_price"] * qty, 2)
    return {
        "wr_returned_date_sk": line["ws_ship_date_sk"]
        + rng.integers(1, 90, n),
        "wr_returned_time_sk": rng.integers(0, 86400, n).astype(np.int64),
        "wr_item_sk": line["ws_item_sk"],
        "wr_refunded_customer_sk": line["ws_bill_customer_sk"],
        "wr_refunded_cdemo_sk": line["ws_bill_cdemo_sk"],
        "wr_refunded_hdemo_sk": line["ws_bill_hdemo_sk"],
        "wr_refunded_addr_sk": line["ws_bill_addr_sk"],
        "wr_returning_customer_sk": line["ws_bill_customer_sk"],
        "wr_returning_cdemo_sk": line["ws_bill_cdemo_sk"],
        "wr_returning_hdemo_sk": line["ws_bill_hdemo_sk"],
        "wr_returning_addr_sk": line["ws_bill_addr_sk"],
        "wr_web_page_sk": line["ws_web_page_sk"],
        "wr_reason_sk": rng.integers(1, 36, n).astype(np.int64),
        "wr_order_number": line["ws_order_number"],
        "wr_return_quantity": qty.astype(np.int32),
        "wr_return_amt": amt,
        "wr_return_tax": np.round(amt * 0.08, 2),
        "wr_return_amt_inc_tax": np.round(amt * 1.08, 2),
        "wr_fee": np.round(rng.uniform(0.5, 100.0, n), 2),
        "wr_return_ship_cost": np.round(rng.uniform(0, 10, n), 2),
        "wr_refunded_cash": np.round(amt * 0.5, 2),
        "wr_reversed_charge": np.round(amt * 0.3, 2),
        "wr_account_credit": np.round(amt * 0.2, 2),
        "wr_net_loss": np.round(rng.uniform(0.5, 500.0, n), 2),
    }
