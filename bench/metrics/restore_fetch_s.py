"""Blob reads of the restore: ``fetch_s`` of the restore's
``Incarnation.timings``, the ``ckpt.restore.fetch`` spans' seconds
summed over the decode workers (so over threads, not wall time)."""


def read(run, trace):
    return (run.get("restore") or {}).get("fetch_s")
