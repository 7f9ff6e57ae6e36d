"""Seconds the encode thread was runnable but off a CPU, over the
window's saves: the ``ckpt.encode`` spans' seconds
(``encode_commit_seconds``) less the commit's (``commit_seconds``: the
last writes and the manifest), less the waits for a free in-flight write
(``encode_put_wait_seconds``), less the thread's CPU time
(``encode_cpu_seconds``). What is left is the GIL, the scheduler, and
the blob-existence checks against the store; it is inferred, not
measured."""

KEYS = ("encode_commit_seconds", "commit_seconds",
        "encode_put_wait_seconds", "encode_cpu_seconds")


def read(run, trace):
    st = run.get("stats") or {}
    if not st.get("saves") or not all(k in st for k in KEYS):
        return None
    return (st["encode_commit_seconds"] - st["commit_seconds"]
            - st["encode_put_wait_seconds"] - st["encode_cpu_seconds"])
