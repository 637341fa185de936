"""Backend compile requests inside the window (``jax.monitoring``), hits of
the persistent compilation cache included: each is a program the process
had not compiled before the window."""


def read(w):
    return float(w["compiles"])
