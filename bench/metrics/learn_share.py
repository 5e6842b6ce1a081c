"""Speculative core: online learning's share of the device's busy time in
the traced stretch (replay-buffer logging and drafter updates), from the
self time of the operations under the program's ``learn.log`` and
``learn.update`` device scopes (``scope_reduce``).  Needs the scope split of
the trace (``--trace 1``)."""
from scope_reduce import share


def read(run):
    return share(run, "learn_share", ("learn.log", "learn.update"))
