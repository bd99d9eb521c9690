"""The one general traffic generator: it reads a mix's parameters from
``benchmark/traffic/<mix>.json`` and hands out the window's statements.

A mix fixes: ``entry`` (``http`` | ``session``), ``fact_source``
(``parquet`` | ``cached``, with ``cached_columns``), ``loop`` (the window's
driver, ``benchmark/loops/<loop>.py``: ``closed``) and ``clients`` (1),
``order`` (statement names, sent in turn and again from the start),
``literals`` (per statement, per name in its text either ``{"value": v}``
or ``{"column": "table.column"}``: a value drawn from the seed out of those
present in that seeded dimension column),
``trace_statements`` (how many statements the traced slice holds) and, as
wanted, what set-up asserts of each warm call: ``expect_lowering``
(statement -> ``pallas`` | ``einsum`` | ``sort``, read from the dispatched
stage's text) and ``assert_redispatch`` (a repeated statement dispatches its
stage again: no result cache in the way).

Every seed sends the same statements in the same order; only the literals
differ.  Statement ``k``'s literals depend on the seed and ``k`` alone.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WARM = 7919          # the warm-up's literals come from a stream of their own


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


class Statement:
    def __init__(self, name: str):
        self.name = name
        with open(os.path.join(HERE, "statements", name + ".sql")) as fh:
            self.text = fh.read().strip()
        self.meta = load_json("statements", name + ".json")

    @property
    def tables(self):
        return list(self.meta["reads"]) + list(self.meta["dimensions"])

    def fact_rows(self, rows: dict) -> int:
        return sum(int(rows[t]) for t in self.meta["reads"])


class Traffic:
    def __init__(self, mix: str, seed: int):
        self.spec = load_json("traffic", mix + ".json")
        self.seed = int(seed)
        self.order = list(self.spec["order"])
        self.statements = {n: Statement(n) for n in dict.fromkeys(self.order)}
        self._domains = {}

    def tables(self):
        out = []
        for st in self.statements.values():
            out += [t for t in st.tables if t not in out]
        return out

    def bind(self, tables: dict):
        """Resolve every ``column`` domain against the seeded dimensions."""
        for name, lits in self.spec.get("literals", {}).items():
            for lit, dom in lits.items():
                if "column" in dom:
                    t, c = dom["column"].split(".")
                    vals = np.unique(np.asarray(tables[t][c]))
                    self._domains[name, lit] = [v.item() for v in vals]
                else:
                    self._domains[name, lit] = [dom["value"]]

    def _draw(self, name: str, stream: int, k: int) -> dict:
        rng = np.random.default_rng([self.seed, stream, k])
        return {lit: dom[int(rng.integers(len(dom)))]
                for (n, lit), dom in sorted(self._domains.items())
                if n == name}

    def statement(self, k: int):
        """(name, literals, text) of the window's ``k``-th statement."""
        name = self.order[k % len(self.order)]
        lit = self._draw(name, 1, k)
        return name, lit, self.statements[name].text.format(**lit)

    def warm_up(self):
        """One statement of each shape, with literals of its own."""
        for i, name in enumerate(self.statements):
            lit = self._draw(name, _WARM, i)
            yield name, lit, self.statements[name].text.format(**lit)
