"""The comparison that decides ``correct``: every reply of the window
against the plain reference's rows for the same statement and literals.

Three numbers, each with a limit of its own (``limits.json``):

``answers_missing``  statements sent that brought no rows back (an error, a
                     refusal, no reply); limit 0.
``rows_wrong``       rows, over all replies, whose exact columns (integers,
                     counts, strings, NULLs) differ from the reference's row
                     at that place, plus the rows one side has and the other
                     lacks; limit 0.
``float_rel_gap``    the widest relative gap of a float64 column over rows
                     whose exact columns agree.

An ordered statement is compared place by place; an unordered one after
both sides are sorted by their exact columns.
"""

from __future__ import annotations

import math


def _norm(v):
    if v is None:
        return None
    if isinstance(v, float) or type(v).__name__.startswith("float"):
        f = float(v)
        return None if math.isnan(f) else f
    if isinstance(v, (bool, str)):
        return v
    if hasattr(v, "__int__"):
        return int(v)
    return v


def _rows(rows):
    return [tuple(_norm(v) for v in r) for r in rows]


def _exact_key(r):
    return tuple((x is None, x) for x in r if not isinstance(x, float))


def compare_reply(got, ref, ordered: bool):
    """(rows_wrong, float_rel_gap) of one reply against its reference."""
    got, ref = _rows(got), _rows(ref)
    if not ordered:
        got, ref = sorted(got, key=_exact_key), sorted(ref, key=_exact_key)
    wrong = abs(len(got) - len(ref))
    gap = 0.0
    for g, e in zip(got, ref):
        bad = len(g) != len(e)
        row_gap = 0.0
        for a, b in zip(g, e):
            if isinstance(a, float) and isinstance(b, float):
                row_gap = max(row_gap, abs(a - b) / max(abs(b), 1e-300))
            elif type(a) is not type(b) or a != b:
                bad = True
        if bad:
            wrong += 1
        else:
            gap = max(gap, row_gap)
    return wrong, gap


def judge(records, reference_rows, limits: dict):
    """``records``: the window's statements (``name``, ``literals_key``,
    ``rows`` or None, ``ordered``).  ``reference_rows``: (name, literals_key)
    -> rows.  Returns (correct, compared) where ``compared`` maps each
    number's name to {"value", "limit"}."""
    missing = wrong = 0
    gap = 0.0
    empty = 0
    for r in records:
        if r["rows"] is None:
            missing += 1
            continue
        ref = reference_rows[r["name"], r["literals_key"]]
        if not ref:
            empty += 1              # an empty reference checks nothing
        w, g = compare_reply(r["rows"], ref, r["ordered"])
        wrong += w
        gap = max(gap, g)
    values = {"answers_missing": missing, "rows_wrong": wrong,
              "float_rel_gap": gap, "references_empty": empty}
    compared = {k: {"value": values[k], "limit": limits[k]} for k in values}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    return correct and len(records) > 0, compared
