"""Model FLOP/s utilization of the train cell: the model FLOPs of the
window's work (``flops.py`` of the configuration, from its shapes) over
the window's host-clock length, as a share of the chip's peak bf16
FLOP/s (``bench/peaks.json``)."""


def read(run, trace):
    flops, window = run.get("model_flops"), run.get("window_s")
    if not flops or not window:
        return None
    return 100.0 * flops / window / run["peaks"]["bf16_flops_per_s"]
