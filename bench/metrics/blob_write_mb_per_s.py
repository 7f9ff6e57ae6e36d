"""Blob write rate of one writer thread over the window's saves: the
pipeline's ``bytes_written`` over ``write_seconds``, the seconds of the
``ckpt.write`` spans (temp write, fsync, rename) summed over the writer
threads."""


def read(run, trace):
    st = run.get("stats") or {}
    sec = st.get("write_seconds")
    if not sec or not st.get("bytes_written"):
        return None
    return st["bytes_written"] / 1e6 / sec
