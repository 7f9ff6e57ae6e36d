"""CPU seconds of encode work per second of encode, over the window's
saves: the encode thread's ``encode_cpu_seconds`` plus the chunk pool's
``encode_pool_cpu_seconds`` (each transform's ``time.thread_time()``,
summed over the workers), over the ``ckpt.encode`` spans' seconds
outside their commit (``encode_commit_seconds`` − ``commit_seconds``).
A program without the chunk pool leaves it out."""

KEYS = ("encode_cpu_seconds", "encode_pool_cpu_seconds",
        "encode_commit_seconds", "commit_seconds")


def read(run, trace):
    st = run.get("stats") or {}
    if not st.get("saves") or not all(k in st for k in KEYS):
        return None
    sec = st["encode_commit_seconds"] - st["commit_seconds"]
    if sec <= 0:
        return None
    return (st["encode_cpu_seconds"] + st["encode_pool_cpu_seconds"]) / sec
