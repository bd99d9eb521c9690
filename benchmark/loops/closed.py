"""``"loop": "closed"``: ONE client in a closed loop over whole passes of
the mix's ``order``.  The next statement goes out when the last one's rows
are on the client; at ``seconds`` the client stops issuing once the pass in
flight is complete, so every window holds the same mix of statements
however a pass's end falls against the mark, and everything sent completes
and counts.

A loop module is found by the name a traffic file gives under ``loop``
(``benchmark/loops/<loop>.py``) and exposes ``drive(eng, mix, rows,
seconds, tracer)`` -> (records, seconds to the last completion).  ``tracer``
is the harness's: ``tracer.span(name, **kw)`` is a context manager around
one call into the system, ``tracer.statement_done(k)`` is told each
completion (it closes the traced slice after the mix's
``trace_statements``) and ``tracer.open`` says whether the slice is still
being traced.
"""

from __future__ import annotations

import json
import time


def drive(eng, mix, rows, seconds, tracer):
    if int(mix.spec.get("clients", 1)) != 1:
        raise ValueError("loops/closed.py drives one client; "
                         f"the mix asks for {mix.spec['clients']}")
    records = []
    w0 = time.perf_counter()
    k = 0
    while True:
        name, lit, sql = mix.statement(k)
        rec = {"k": k, "name": name, "literals": lit,
               "literals_key": json.dumps(lit, sort_keys=True),
               "ordered": mix.statements[name].meta["ordered"],
               "fact_rows": mix.statements[name].fact_rows(rows),
               "rows": None, "server_ms": None}
        t0 = time.perf_counter()
        try:
            with tracer.span("statement", stmt=name, k=k):
                rec["rows"], rec["server_ms"] = eng.run(sql)
        except Exception as exc:       # the boundary: a failed statement is
            rec["error"] = repr(exc)   # an answer that never came
        t1 = time.perf_counter()
        rec["start_s"], rec["latency_ms"] = t0 - w0, (t1 - t0) * 1e3
        records.append(rec)
        k += 1
        tracer.statement_done(k)
        if t1 - w0 >= seconds and k % len(mix.order) == 0 \
                and not tracer.open:
            break
    return records, time.perf_counter() - w0
