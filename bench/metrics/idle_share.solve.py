"""Percent of the measured window in which no operation ran on the chip
(1 - busy / window, from the trace)."""
from bench.readers import idle_share


def read(run):
    return idle_share(run)
