"""Programs compiled, or loaded from the persistent cache, inside the
measured window (``jax.monitoring`` backend-compile events).  Set-up warms
every shape the traffic uses, so this should read 0."""


def read(run):
    return float(run["window_compiles"])
