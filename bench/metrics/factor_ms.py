"""Device milliseconds of the numeric factorization per value push.

Every engine of the program compiles through ``bitmath.hoisted_jit`` as the
XLA module ``jit__eval``, so the factor program is told apart by the host
span it runs in: the benchmark's ``push_values`` span, in which the
factorization is the only program dispatched."""
from bench.readers import program_ms

PROGRAM = "jit__eval*"
SPAN = "push_values"


def read(run):
    return program_ms(run, PROGRAM, SPAN)
