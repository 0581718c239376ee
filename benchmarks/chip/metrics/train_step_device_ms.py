"""Device time of the jitted train step per step; the slowest chip's."""
MODULES = (r"train_step",)


def read(run, trace, peaks, kind):
    out = []
    for t, n in zip(trace.module_time(MODULES),
                    trace.module_count(MODULES)):
        if n:
            out.append(1e3 * t / n)
    return max(out) if out else None
