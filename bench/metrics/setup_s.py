"""Seconds from the start of the process to when the window's first
request was due: loading, weights, compiling or loading every program, and
the warm-up."""


def read(run):
    return run["setup_s"]
