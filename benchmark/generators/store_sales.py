"""``store_sales``: the spec's 23 columns; every row is drawn alone, so a
ticket is one line (``ss_ticket_number`` is the row's number)."""

import numpy as np

from benchmark.lib import datagen as D

STREAM = 3          # default_rng([seed, STREAM])
FACT = True
NEEDS = ()          # made first, handed over in ``made``
#: columns declared ``int``; the rest follow ``datagen.column_type``'s rule
INT_COLUMNS = ("ss_quantity",)


def make(rng, rows, made) -> dict:
    n = rows["store_sales"]
    s = D.sales_columns(rng, n, rows)
    order = ["sold_date_sk", "sold_time_sk", "item_sk", "customer_sk",
             "cdemo_sk", "hdemo_sk", "addr_sk", "store_sk", "promo_sk"]
    cols = {f"ss_{c}": s[c] for c in order}
    cols["ss_ticket_number"] = np.arange(1, n + 1, dtype=np.int64)
    cols.update({f"ss_{c}": v for c, v in s.items() if c not in order})
    return cols
