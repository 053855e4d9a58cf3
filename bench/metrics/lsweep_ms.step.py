"""Device milliseconds of the lower-triangular sweep inside GMRES per step
of the window (``tick`` span, one service solve): the operations under
``gmres.precond/sweep.lower`` (its level loop and the gather in it)."""
from bench.program_trace import scope_ms


def read(run):
    return scope_ms(run, "gmres.precond/sweep.lower", per="tick")
