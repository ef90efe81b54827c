"""Compilations and persistent-cache loads inside the measured window
(JAX's backend-compile events)."""


def read(run):
    return float(run.compiles)
