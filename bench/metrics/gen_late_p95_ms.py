"""Load generator: 95th percentile of how late a request was sent after it
was due (client clock).  Open loop only: a closed-loop caller sends when it
is ready, so nothing is ever late."""
from stats import percentile


def read(run):
    if run["loop"] != "open":
        return None
    vals = [r["sent"] - r["due"] for r in run["records"]
            if r["sent"] is not None]
    run.setdefault("samples", {})["gen_late_p95_ms"] = len(vals)
    p = percentile(vals, 95)
    return None if p is None else p * 1e3
