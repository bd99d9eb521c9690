"""``web_sales``: the spec's 34 columns with dsdgen's order structure: an
order is 8-16 lines (uniform, mean 12) that share what ``w_web_sales.c``
draws once an order (order number, sold date and time, bill and ship
customer with their demographics and addresses), and each line draws its
own item (distinct within its order, so (``ws_order_number``,
``ws_item_sk``) is a key), ship date, web page, web site, ship mode,
warehouse, promotion and pricing."""

import numpy as np

from benchmark.lib import datagen as D

STREAM = 6          # default_rng([seed, STREAM])
FACT = True
NEEDS = ()          # made first, handed over in ``made``
INT_COLUMNS = ("ws_quantity",)

LINES_MIN, LINES_MAX = 8, 16


def order_sizes(rng, n) -> np.ndarray:
    """Lines an order, each in 8..16, that sum to ``n`` exactly: sizes are
    drawn until they pass ``n``, and the excess (under 16) comes off the
    first orders that can spare a line."""
    sizes = rng.integers(LINES_MIN, LINES_MAX + 1, n // LINES_MIN + 1)
    sizes = sizes[:int(np.searchsorted(np.cumsum(sizes), n)) + 1]
    while sizes.sum() > n:
        spare = np.flatnonzero(sizes > LINES_MIN)[:sizes.sum() - n]
        if not len(spare):
            raise ValueError(f"web_sales: {n} rows do not make whole orders")
        sizes[spare] -= 1
    return sizes


def distinct_items(rng, order_of_line, n_items) -> np.ndarray:
    """A uniform item a line; a line that repeats an item of its own order
    draws again until none does."""
    n = len(order_of_line)
    item = rng.integers(1, n_items + 1, n).astype(np.int64)
    while True:
        by = np.lexsort((item, order_of_line))
        dup = np.zeros(n, bool)
        dup[by[1:]] = (order_of_line[by[1:]] == order_of_line[by[:-1]]) \
            & (item[by[1:]] == item[by[:-1]])
        if not dup.any():
            return item
        item[dup] = rng.integers(1, n_items + 1, int(dup.sum()))


def make(rng, rows, made) -> dict:
    n = rows["web_sales"]
    if rows["item"] < LINES_MAX:
        raise ValueError("an order's items are distinct: item needs 16 rows")
    sizes = order_sizes(rng, n)
    n_orders = len(sizes)
    line_order = np.repeat(np.arange(n_orders), sizes)    # 0-based order

    def key(size, m=n):
        return rng.integers(1, size + 1, m).astype(np.int64)

    # -- once an order ----------------------------------------------------
    sold = D.DATE0_SK + rng.integers(0, D.N_DAYS, n_orders)
    o = {
        "sold_date_sk": D.nullable(rng, sold),
        "sold_time_sk": rng.integers(0, 86400, n_orders).astype(np.int64),
        "customer_sk": D.nullable(rng, key(rows["customer"], n_orders)),
        "cdemo_sk": key(rows["customer_demographics"], n_orders),
        "hdemo_sk": key(rows["household_demographics"], n_orders),
        "bill_addr_sk": key(rows["customer_address"], n_orders),
        "ship_addr_sk": D.nullable(
            rng, key(rows["customer_address"], n_orders)),
    }
    o = {c: v[line_order] for c, v in o.items()}
    # -- once a line ------------------------------------------------------
    item = distinct_items(rng, line_order, rows["item"])
    ship_date = sold[line_order] + rng.integers(1, 121, n)
    web_page, web_site = key(rows["web_page"]), key(rows["web_site"])
    ship_mode = key(rows["ship_mode"])
    warehouse = D.nullable(rng, key(rows["warehouse"]))
    promo = key(rows["promotion"])
    qty = rng.integers(1, 101, n)
    wholesale = np.round(rng.uniform(1.0, 100.0, n), 2)
    list_price = np.round(wholesale * rng.uniform(1.0, 2.0, n), 2)
    sales_price = np.round(list_price * rng.uniform(0.2, 1.0, n), 2)
    ext_sales = np.round(sales_price * qty, 2)
    ext_wholesale = np.round(wholesale * qty, 2)
    ext_tax = np.round(ext_sales * 0.08, 2)
    coupon = np.round(ext_sales * rng.choice([0.0, 0.0, 0.0, 0.1], n), 2)
    ship_cost = np.round(ext_sales * rng.uniform(0.0, 0.1, n), 2)
    net_paid = np.round(ext_sales - coupon, 2)
    net_paid_inc_tax = np.round(net_paid + ext_tax, 2)
    return {
        "ws_sold_date_sk": o["sold_date_sk"],
        "ws_sold_time_sk": o["sold_time_sk"],
        "ws_ship_date_sk": ship_date.astype(np.int64),
        "ws_item_sk": item,
        "ws_bill_customer_sk": o["customer_sk"],
        "ws_bill_cdemo_sk": o["cdemo_sk"],
        "ws_bill_hdemo_sk": o["hdemo_sk"],
        "ws_bill_addr_sk": o["bill_addr_sk"],
        "ws_ship_customer_sk": o["customer_sk"],
        "ws_ship_cdemo_sk": o["cdemo_sk"],
        "ws_ship_hdemo_sk": o["hdemo_sk"],
        "ws_ship_addr_sk": o["ship_addr_sk"],
        "ws_web_page_sk": web_page, "ws_web_site_sk": web_site,
        "ws_ship_mode_sk": ship_mode, "ws_warehouse_sk": warehouse,
        "ws_promo_sk": promo,
        "ws_order_number": (line_order + 1).astype(np.int64),
        "ws_quantity": qty.astype(np.int32),
        "ws_wholesale_cost": wholesale, "ws_list_price": list_price,
        "ws_sales_price": sales_price,
        "ws_ext_discount_amt": np.round((list_price - sales_price) * qty, 2),
        "ws_ext_sales_price": ext_sales,
        "ws_ext_wholesale_cost": ext_wholesale,
        "ws_ext_list_price": np.round(list_price * qty, 2),
        "ws_ext_tax": ext_tax, "ws_coupon_amt": coupon,
        "ws_ext_ship_cost": ship_cost,
        "ws_net_paid": net_paid, "ws_net_paid_inc_tax": net_paid_inc_tax,
        "ws_net_paid_inc_ship": np.round(net_paid + ship_cost, 2),
        "ws_net_paid_inc_ship_tax": np.round(net_paid_inc_tax + ship_cost, 2),
        "ws_net_profit": np.round(net_paid - ext_wholesale, 2),
    }
