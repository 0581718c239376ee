"""Device time of the ``_pooled_stack`` program (the L1 gather kernel with
its relayouts and pooling) per dispatch."""
MODULES = (r"_pooled_stack",)


def read(run, trace, peaks, kind):
    n = trace.module_count(MODULES)[0]
    if not n:
        return None
    return 1e3 * trace.module_time(MODULES)[0] / n
