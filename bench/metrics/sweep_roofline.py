"""Percent of the HBM roofline of one preconditioner apply."""
from bench.readers import roofline

PROGRAM = "jit__eval*"
SPAN = "probe.sweep"


def read(run):
    return roofline(run, PROGRAM, SPAN, "sweep", "probe_calls")
