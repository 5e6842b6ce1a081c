"""Model step: model FLOPs of the work done in the window over the window
times the chip's bf16 peak (``peaks.json``, by ``device_kind``).

Work done: every token delivered in the window once through the whole model
at its position, plus the prompts of requests whose first token came in
the window, less the prompt tokens the prefix cache spliced in (taken as
the first tokens of their prompts).  Rejected drafts, the drafter, its
training and padding are not model work.  FLOPs from the configuration
(``flops.py``)."""
import flops
from stats import counter


def read(run):
    c, secs = run["config"], run["seconds"]
    work = 0
    for r in run["records"]:
        p = len(run["window"][r["i"]]["prompt"])
        work += flops.span_flops(c, p, p + r["in_window"])
        if r["first"] is not None and 0 <= r["first"] < secs:
            work += flops.span_flops(c, 0, p)
    hits = counter(run, "dvi_serving_prefix_hits_total")
    if hits:
        each = int(counter(run, "dvi_serving_prefix_hit_tokens_total") / hits)
        work -= hits * flops.span_flops(c, 0, each)
    return 100.0 * work / (secs * run["peaks"]["bf16_flops_per_s"])
