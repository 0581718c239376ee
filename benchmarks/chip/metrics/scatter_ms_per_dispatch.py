"""Device time of the L1 payload scatters (``payload_store.py``'s eager
``at[].set``) per pooled-gather dispatch."""
MODULES = (r"scatter",)
PER = (r"_pooled_stack",)


def read(run, trace, peaks, kind):
    n = trace.module_count(PER)[0]
    if not n:
        return None
    return 1e3 * trace.module_time(MODULES)[0] / n
