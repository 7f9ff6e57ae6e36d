"""Device-to-host rate of the snapshot capture over the window's saves:
the bytes the capture's blocking copies moved over their seconds, both
summed by the ``ckpt.capture.d2h`` spans into the pipeline's ``stats``
(``d2h_bytes``, ``d2h_seconds``). The copies are the dense copy of each
leaf a base takes, and on a dirty-chunk link the fused capture's count,
indices and compaction buffer (its padded capacity, all of it moved),
or the gather fallback's mask and gathered chunks. Each copy's seconds
start once the array is ready, so a kernel's run is not counted."""


def read(run, trace):
    st = run.get("stats") or {}
    sec, n = st.get("d2h_seconds"), st.get("d2h_bytes")
    if not sec or not n:
        return None
    return n / 1e9 / sec
