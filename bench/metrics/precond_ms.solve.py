"""Device milliseconds of the preconditioner applies inside GMRES per solve
of the window (``solve`` span): the operations under the ``gmres.precond``
scope, the sweep where it really runs (one apply per Arnoldi step and one
per restart)."""
from bench.program_trace import scope_ms


def read(run):
    return scope_ms(run, "gmres.precond", per="solve")
