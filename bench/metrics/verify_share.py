"""Model step: the verify pass's share of the device's busy time in the
traced stretch (deep layers, verifier logits, accept/reject), from the self
time of the operations under the program's ``verify`` device scope
(``scope_reduce``).  Needs the scope split of the trace (``--trace 1``)."""
from scope_reduce import share


def read(run):
    return share(run, "verify_share", ("verify",))
