"""Percent of the HBM roofline of one numeric factorization: the bytes
``bench/work.py`` counts from the pattern over (device time x bandwidth)."""
from bench.readers import roofline

PROGRAM = "jit__eval*"
SPAN = "push_values"


def read(run):
    return roofline(run, PROGRAM, SPAN, "factor")
