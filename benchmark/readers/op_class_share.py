"""Device time of the operations of the given classes (XLA's printed name
without its number) over device busy time, busiest device, in percent."""


def read(ctx, classes, **_):
    return ctx.trace.class_share_pct(set(classes))
