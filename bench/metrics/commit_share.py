"""KV pool and prefix cache: the commit's share of the device's busy time in
the traced stretch (cache and token commit, the superstep's in-graph
bookkeeping), from the self time of the operations under the program's
``commit`` device scope (``scope_reduce``).  Needs the scope split of the
trace (``--trace 1``)."""
from scope_reduce import share


def read(run):
    return share(run, "commit_share", ("commit",))
