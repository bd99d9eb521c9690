"""Median of client wall minus the reply's ``durationMs`` (HTTP entry)."""

import statistics


def read(ctx, **_):
    over = [r["latency_ms"] - r["server_ms"] for r in ctx.records
            if r.get("server_ms") is not None and r["rows"] is not None]
    return statistics.median(over) if over else None
