"""Device time of the served dense net (the server's jitted
``model.apply_dense``) per pooled-gather dispatch."""
MODULES = (r"jit__lambda",)
PER = (r"_pooled_stack",)


def read(run, trace, peaks, kind):
    n = trace.module_count(PER)[0]
    if not n:
        return None
    t = trace.module_time(MODULES)[0]
    return 1e3 * t / n if t else None
