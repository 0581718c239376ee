"""Rows served per pooled-gather dispatch in the traced window: how full
the admission and batching layer (``serve/server.py``) makes each group."""
MODULES = (r"_pooled_stack",)


def read(run, trace, peaks, kind):
    n = trace.module_count(MODULES)[0]
    if not n:
        return None
    return run.layer["rows_delivered"] / n
