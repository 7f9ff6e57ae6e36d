"""CPU seconds of the encode thread over the window's saves, outside
the commit: ``encode_cpu_seconds`` of the pipeline's ``stats``, the
thread's ``time.thread_time()`` over the ``ckpt.encode`` span up to its
``ckpt.commit`` (zlib, blake2b, XOR, the copies into the mirror)."""


def read(run, trace):
    st = run.get("stats") or {}
    if not st.get("saves") or "encode_cpu_seconds" not in st:
        return None
    return st["encode_cpu_seconds"]
