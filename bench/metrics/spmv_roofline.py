"""Percent of the HBM roofline of one ELL SpMV (``make_ell_matvec``),
dispatched alone after the window in ``probe.spmv`` spans."""
from bench.readers import roofline

PROGRAM = "jit__eval*"
SPAN = "probe.spmv"


def read(run):
    return roofline(run, PROGRAM, SPAN, "spmv", "probe_calls")
