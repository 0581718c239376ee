"""Share of L1 lookups that hit, over every table, from the caches'
``counters()`` differenced over the window (``core/hps``)."""


def read(run, trace, peaks, kind):
    h, m = run.layer.get("l1_hits"), run.layer.get("l1_misses")
    if h is None or h + m == 0:
        return None
    return 100.0 * h / (h + m)
