"""``materialize_s`` of the restore's ``Incarnation.timings``, taken after the
window in a process whose JAX caches were cleared, so replay loads the
step from the persistent compilation cache as a fresh process would."""


def read(run, trace):
    return (run.get("restore") or {}).get("materialize_s")
