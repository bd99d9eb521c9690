from .web_common import web_orders


def reference(frames, literals, float_dtype="float64"):
    return web_orders(frames, literals, own_warehouse=False, returned=True,
                      float_dtype=float_dtype)
