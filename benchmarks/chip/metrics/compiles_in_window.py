"""XLA backend compiles inside the window, counted by the benchmark's own
``jax.monitoring`` listener. Set-up warms every shape it can; what is left
compiles while requests wait."""


def read(run, trace, peaks, kind):
    return float(run.layer["compiles_in_window"])
