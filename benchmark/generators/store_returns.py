"""``store_returns``: the spec's 20 columns; a sample of ``store_sales``."""

import numpy as np

from benchmark.lib import datagen as D

STREAM = 4          # default_rng([seed, STREAM])
FACT = True
NEEDS = ("store_sales",)          # made first, handed over in ``made``
INT_COLUMNS = ("sr_return_quantity",)


def make(rng, rows, made) -> dict:
    """Returns reference ``n`` store_sales rows by (item, ticket, customer)."""
    n, ss = rows["store_returns"], made["store_sales"]
    idx = rng.choice(len(ss["ss_item_sk"]), n, replace=False)
    ssr = {c: ss[c][idx] for c in (
        "ss_quantity", "ss_sales_price", "ss_sold_date_sk", "ss_item_sk",
        "ss_customer_sk", "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk",
        "ss_store_sk", "ss_ticket_number")}
    qty = np.minimum(rng.integers(1, 101, n), ssr["ss_quantity"])
    amt = np.round(ssr["ss_sales_price"] * qty, 2)
    sold = ssr["ss_sold_date_sk"].to_numpy(dtype=np.int64, na_value=D.DATE0_SK)
    return {
        "sr_returned_date_sk": sold + rng.integers(1, 90, n),
        "sr_return_time_sk": rng.integers(0, 86400, n).astype(np.int64),
        "sr_item_sk": ssr["ss_item_sk"],
        "sr_customer_sk": ssr["ss_customer_sk"],
        "sr_cdemo_sk": ssr["ss_cdemo_sk"],
        "sr_hdemo_sk": ssr["ss_hdemo_sk"],
        "sr_addr_sk": ssr["ss_addr_sk"],
        "sr_store_sk": ssr["ss_store_sk"],
        "sr_reason_sk": rng.integers(1, 36, n).astype(np.int64),
        "sr_ticket_number": ssr["ss_ticket_number"],
        "sr_return_quantity": qty.astype(np.int32),
        "sr_return_amt": amt,
        "sr_return_tax": np.round(amt * 0.08, 2),
        "sr_return_amt_inc_tax": np.round(amt * 1.08, 2),
        "sr_fee": np.round(rng.uniform(0.5, 100.0, n), 2),
        "sr_return_ship_cost": np.round(rng.uniform(0, 10, n), 2),
        "sr_refunded_cash": np.round(amt * 0.5, 2),
        "sr_reversed_charge": np.round(amt * 0.3, 2),
        "sr_store_credit": np.round(amt * 0.2, 2),
        "sr_net_loss": np.round(rng.uniform(0.5, 500.0, n), 2),
    }
