"""Share of the HBM roofline: the least bytes of the traced statements
(``lib/roofline.py``) over peak bandwidth, over the device busy seconds
inside their spans.  Nothing to read where no such statement was traced."""


def read(ctx, statements=None, **_):
    spans = ctx.trace.statement_spans(statements)
    device_s = sum(ctx.trace.device_s_in(s) for s in spans)
    if not spans or device_s <= 0:
        return None
    nbytes = sum(ctx.least_bytes(s[3]["stmt"]) for s in spans)
    return ctx.hbm_roofline_pct(nbytes, device_s)
