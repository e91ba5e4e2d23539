"""Compilations plus persistent-cache loads inside the measured window, as
JAX's monitoring events count them.  Set-up warms every shape, so this
should read 0."""


def read(run):
    return float(run.compiles)
