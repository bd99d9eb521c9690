"""Fact-table rows read by EVERY statement completed in the window (each
statement's facts at the configuration's row counts) over ALL the seconds
of the window, to the last completion."""


def value(records, window_s, setup_s):
    return sum(r["fact_rows"] for r in records
               if r["rows"] is not None) / window_s
