"""Programs handed to the XLA backend compiler inside the window (JAX's own
``backend_compile_duration`` events, counted by the harness: they cover the
stage cache, the distributed jit cache and the streamed steps alike).
Expected 0: every shape was warmed in set-up."""


def read(ctx, **_):
    return float(ctx.counters_after["xla_compiles"]
                 - ctx.counters_before["xla_compiles"])
