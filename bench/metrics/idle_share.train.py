"""Device idle share of the train cell's window: one minus the union
of the intervals in which an op ran on the device, over the traced
window (``bench/trace.py``)."""


def read(run, trace):
    if trace is None or not trace.window_s:
        return None
    return 100.0 * trace.idle_share
