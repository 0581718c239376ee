"""The whole training step's share of the chips' bf16 peak: the dense
net's forward and backward model FLOPs per sample
(``work.train_flops_per_sample``) times samples per second of the window,
over chips times peak."""
import work


def read(run, trace, peaks, kind):
    if peaks is None:
        return None
    rate = run.layer["steps"] * run.layer["batch"] / run.layer["window_s"]
    peak = work.peak(kind, "bf16_flops_per_s", peaks) * run.layer["chips"]
    return 100.0 * work.train_flops_per_sample(run.config) * rate / peak
