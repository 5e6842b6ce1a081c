"""Speculative core: tokens the verifier committed per speculative block of
one lane (engine counters over the window): 1 plus the accepted drafts."""
from stats import counter


def read(run):
    blocks = counter(run, "dvi_serving_blocks_total")
    if not blocks:
        return None
    return counter(run, "dvi_serving_committed_tokens_total") / blocks
