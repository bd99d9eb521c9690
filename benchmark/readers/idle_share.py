"""1 minus the union of device operation intervals over the traced slice,
on the least idle device, in percent."""


def read(ctx, **_):
    return ctx.trace.idle_pct()
