"""XLA compilations inside the measured window (``jax.monitoring``'s
backend-compile events; the persistent cache is off in the window, so
each is a real compile)."""


def read(rd):
    return float(len(rd.run["compiles"]))
