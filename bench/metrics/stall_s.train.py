"""Capture stall of the train cell: the harness's host clock around
every ``maybe_snapshot()`` that took a save, summed over the window."""


def read(run, trace):
    stalls = run.get("stalls") or []
    return sum(stalls) if stalls else None
