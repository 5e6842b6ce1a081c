"""95th percentile, over every request due in the window, of the time from
when it was due to its first streamed token (client clock).  A request that
failed, or never streamed a token, counts as missing: as long as the run
waited for it."""
from stats import percentile


def read(run):
    wait = run["seconds"] + run["grace_s"]
    vals = [(r["first"] if r["ok"] and r["first"] is not None else wait)
            - r["due"] for r in run["records"]]
    run.setdefault("samples", {})["ttft_p95_ms"] = len(vals)
    p = percentile(vals, 95)
    return None if p is None else p * 1e3
