"""HTTP front end: 95th percentile of how long a request took from the HTTP
handler's entry to the engine thread's ``submit_request``, from the
program's ``submit`` spans (engine clock) of requests received inside the
window.  The engine thread takes submissions only between ticks, so this
is the wait for the tick in progress.  Needs the tracer (``--trace 1``)."""
from lifecycle import durations_ms
from stats import percentile


def read(run):
    vals = durations_ms(run, "submit")
    if vals is None:
        return None
    run.setdefault("samples", {})["submit_wait_p95_ms"] = len(vals)
    return percentile(vals, 95)
