"""Share of the traced window in which no operation ran on the chip."""


def read(run, trace, peaks, kind):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
