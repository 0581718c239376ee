"""The pooled L1 lookup's share of its roofline: the bytes the lookup
needs (gathered rows with their scales, slot indices, the f32 [B, T, D]
output; ``work.gather_bytes_per_row``) over the chip's HBM bandwidth,
against the ``_pooled_stack`` program's device time. Memory-bound: it
needs no FLOPs. The same bytes whatever implements the gather."""
import work

MODULES = (r"_pooled_stack",)


def read(run, trace, peaks, kind):
    if peaks is None:
        return None
    t = trace.module_time(MODULES)[0]
    rows = run.layer["rows_delivered"]
    if not t or not rows:
        return None
    bw = work.peak(kind, "hbm_bytes_per_s", peaks)
    return 100.0 * rows * run.layer["gather_bytes_per_row"] / bw / t
