"""Generated tokens delivered to clients inside the window, per second of
the window (client clock)."""


def read(run):
    return sum(r["in_window"] for r in run["records"]) / run["seconds"]
