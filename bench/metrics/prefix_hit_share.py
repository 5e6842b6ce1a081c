"""KV pool / prefix cache: prompt tokens served from cached pages, as a
share of the prompt tokens sent inside the window (engine counter over the
window, client's prompt lengths)."""
from stats import counter


def read(run):
    sent = sum(len(run["window"][r["i"]]["prompt"]) for r in run["records"]
               if r["sent"] is not None)
    if not sent:
        return None
    return 100.0 * counter(run, "dvi_serving_prefix_hit_tokens_total") / sent
