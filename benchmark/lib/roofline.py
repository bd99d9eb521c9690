"""What a statement has to move at the least, whatever implements it: each
fact column it reads once, at the declared SQL width, at the
configuration's row count, plus its result.  These stages move bytes, so
the roofline is HBM bandwidth; the peaks come from ``peaks.json`` by
``device_kind`` and an unknown kind is an error."""

from __future__ import annotations

from . import datagen


def least_bytes(meta: dict, rows: dict) -> int:
    read = sum(int(rows[table]) * sum(
        datagen.TYPE_BYTES[datagen.column_type(table, c)] for c in cols)
        for table, cols in meta["reads"].items())
    return read + int(meta["result_rows_max"]) * int(meta["result_row_bytes"])


def peak(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (it has {sorted(peaks)})")
    return peaks[device_kind]


def hbm_roofline_pct(nbytes: float, device_s: float, peak_entry: dict):
    """Least seconds at peak HBM bandwidth over the device seconds taken."""
    return 100.0 * (nbytes / peak_entry["hbm_bytes_per_s"]) / device_s
