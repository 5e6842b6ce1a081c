"""Device: the share of the traced stretch in which no operation ran on the
chip (profiler trace)."""


def read(run):
    tr = run["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
