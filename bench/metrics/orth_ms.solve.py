"""Device milliseconds of GMRES's Gram-Schmidt orthogonalization per solve
of the window (``solve`` span): the operations under the program's
``gmres.orthogonalize`` scope (the MGS loop and the norm after it)."""
from bench.program_trace import scope_ms


def read(run):
    return scope_ms(run, "gmres.orthogonalize", per="solve")
