"""Device busy milliseconds inside the harness's statement spans of the
traced slice, per statement (optionally of the named statements only)."""


def read(ctx, statements=None, **_):
    spans = ctx.trace.statement_spans(statements)
    if not spans:
        return None
    return 1e3 * sum(ctx.trace.device_s_in(s) for s in spans) / len(spans)
