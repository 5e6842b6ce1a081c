"""Scheduler: prefill's share of the device's busy time in the traced
stretch (chunk steps and admission programs), from the self time of the
operations under the program's ``prefill.chunk`` and ``prefill.admit``
device scopes (``scope_reduce``).  Needs the scope split of the trace
(``--trace 1``)."""
from scope_reduce import share


def read(run):
    return share(run, "prefill_share", ("prefill.chunk", "prefill.admit"))
