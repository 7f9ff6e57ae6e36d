"""Commit of the window's saves: ``commit_seconds`` of the pipeline's
``stats``, the ``ckpt.commit`` spans on the encode thread (the wait for
the last blob writes, then the manifest's write, fsync and rename)."""


def read(run, trace):
    st = run.get("stats") or {}
    if not st.get("saves") or "commit_seconds" not in st:
        return None
    return st["commit_seconds"]
