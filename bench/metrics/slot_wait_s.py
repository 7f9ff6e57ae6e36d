"""Capture's wait for a free staging slot over the window's saves:
``slot_wait_seconds`` of the pipeline's ``stats``, the
``ckpt.capture.slot_wait`` spans on the caller thread, taken only when
every slot still holds a save being encoded (0 when none had to wait)."""


def read(run, trace):
    st = run.get("stats") or {}
    if not st.get("saves") or "slot_wait_seconds" not in st:
        return None
    return st["slot_wait_seconds"]
