"""Set-up time: process start to the start of the window (imports, JAX
start-up, warm-up of every shape the mix can draw, and compilation or
compile-cache reads). Host clock."""


def read(run):
    return run.setup_s
