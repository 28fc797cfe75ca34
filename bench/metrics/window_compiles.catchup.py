"""Jaxpr traces inside the window, from the program's record of every
trace of the process (``jit.traces``, with its time): the warm-up should
have traced every shape the window uses, so this reads 0. Layer: jit /
dispatch."""
from bench import program


def read(ctx):
    return program.traces(ctx)
