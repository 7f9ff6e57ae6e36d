"""Share of the HBM roofline reached by the capture kernels: the bytes
every fingerprint and fused-capture kernel call reads and writes (from
the shapes in its HLO text, ``bench/trace.py``) at the chip's peak HBM
bandwidth (``bench/peaks.json``), over their summed device time."""

KERNELS = ("_fingerprint_impl", "_fused_capture_impl")


def read(run, trace):
    if trace is None:
        return None
    calls = [trace.kernels[k] for k in KERNELS if k in trace.kernels]
    seconds = sum(c["seconds"] for c in calls)
    if not calls or seconds <= 0:
        return None
    moved = sum(c["bytes"] for c in calls)
    return 100.0 * moved / run["peaks"]["hbm_bytes_per_s"] / seconds
