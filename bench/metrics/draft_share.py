"""Speculative core: the draft feeds' share of the device's busy time in
the traced stretch, from the self time of the operations under the
program's ``draft`` device scope (``scope_reduce``).  Needs the scope split
of the trace (``--trace 1``)."""
from scope_reduce import share


def read(run):
    return share(run, "draft_share", ("draft",))
