"""The whole serving step's share of the chip's bf16 peak: the dense net's
model FLOPs per row (``work.dense_forward_flops``) times the rows served
per second of the window."""
import work


def read(run, trace, peaks, kind):
    if peaks is None:
        return None
    rate = run.layer["rows_delivered"] / run.layer["window_s"]
    peak = work.peak(kind, "bf16_flops_per_s", peaks)
    return 100.0 * run.layer["dense_flops_per_row"] * rate / peak
