"""Share of the traced window in which no operation ran, averaged over the
chips: what the host loop (batch generation, transfer, the sync on each
step's loss) leaves the devices idle."""


def read(run, trace, peaks, kind):
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)
