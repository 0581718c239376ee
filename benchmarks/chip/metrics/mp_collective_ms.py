"""Device time of the embedding exchange and the gradient reduction per
step (all-to-all, all-gather, reduce-scatter, all-reduce and collective
permutes); the slowest chip's."""
OPS = (r"all-to-all", r"all-gather", r"reduce-scatter", r"all-reduce",
       r"collective-permute")
STEP = (r"train_step",)


def read(run, trace, peaks, kind):
    out = []
    for t, n in zip(trace.op_time(OPS), trace.module_count(STEP)):
        if n and t:
            out.append(1e3 * t / n)
    return max(out) if out else None
