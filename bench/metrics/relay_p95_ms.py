"""HTTP front end: 95th percentile of how long a request's first tokens took
from the engine's feed to its handle to the end of the HTTP thread's write
of the SSE chunk that carries them, from the program's ``relay`` spans
(engine clock) whose first token came inside the window.  Needs the tracer
(``--trace 1``)."""
from lifecycle import durations_ms
from stats import percentile


def read(run):
    vals = durations_ms(run, "relay")
    if vals is None:
        return None
    run.setdefault("samples", {})["relay_p95_ms"] = len(vals)
    return percentile(vals, 95)
