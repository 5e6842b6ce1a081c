"""The program's request lifecycle spans, as the metric readers take them.

The engine's tracer (on with ``--trace 1``) records each phase of a request
as an async ``b``/``e`` pair with the request's id, on the engine's clock:
microseconds after the tracer's ``t0``, a ``time.monotonic()`` reading like
the run's own ``t0``.
"""
from __future__ import annotations

from typing import List, Optional


def durations_ms(run: dict, name: str) -> Optional[List[float]]:
    """Milliseconds of each request's first ``name`` phase that began
    inside the window; None without the tracer."""
    tr = run["tracer"]
    if tr is None or tr["t0"] is None:
        return None
    lo = (run["t0"] - tr["t0"]) * 1e6
    hi = lo + run["seconds"] * 1e6
    begin, vals = {}, []
    for ev in tr["events"]:
        if ev.get("name") != name or ev.get("cat") != "request":
            continue
        if ev["ph"] == "b" and ev["id"] not in begin:
            begin[ev["id"]] = ev["ts"]
        elif ev["ph"] == "e" and ev["id"] in begin:
            b = begin.pop(ev["id"])
            if lo <= b < hi:
                vals.append((ev["ts"] - b) * 1e-3)
            begin[ev["id"]] = float("nan")      # first phase only
    return vals
