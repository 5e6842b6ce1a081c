"""Engine host tick: the share of the window the engine thread spent
blocked on the device, waiting for a superstep's summary (engine counter
over the window)."""
from stats import counter


def read(run):
    return 100.0 * counter(run, "dvi_serving_sync_wait_seconds_total") / run[
        "seconds"]
