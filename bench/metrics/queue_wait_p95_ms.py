"""Scheduler: 95th percentile of the time a request waited in the engine's
admission queue, from the lifecycle tracer's ``queued`` spans (engine
clock) of requests submitted inside the window.  Needs the tracer
(``--trace 1``)."""
from stats import percentile


def read(run):
    tr = run["tracer"]
    if tr is None or tr["t0"] is None:
        return None
    lo = (run["t0"] - tr["t0"]) * 1e6
    hi = lo + run["seconds"] * 1e6
    begin, vals = {}, []
    for ev in tr["events"]:
        if ev.get("name") != "queued" or ev.get("cat") != "request":
            continue
        if ev["ph"] == "b" and ev["id"] not in begin:
            begin[ev["id"]] = ev["ts"]
        elif ev["ph"] == "e" and ev["id"] in begin:
            b = begin.pop(ev["id"])
            if lo <= b < hi:
                vals.append((ev["ts"] - b) * 1e-3)
            begin[ev["id"]] = float("nan")      # first wait only
    run.setdefault("samples", {})["queue_wait_p95_ms"] = len(vals)
    return percentile(vals, 95)
