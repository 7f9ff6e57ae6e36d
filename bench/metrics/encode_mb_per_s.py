"""Encode and commit rate of the snapshot pipeline over the window's
saves: the manager's ``bytes_logical`` over its
``encode_commit_seconds``."""


def read(run, trace):
    st = run.get("stats") or {}
    sec = st.get("encode_commit_seconds", 0.0)
    if not st.get("saves") or sec <= 0:
        return None
    return st["bytes_logical"] / 1e6 / sec
