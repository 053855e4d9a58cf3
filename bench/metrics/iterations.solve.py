"""Mean inner GMRES iterations per solve of the window (``SolveResult``)."""
from bench.readers import mean


def read(run):
    return mean(run.counters.get("iterations", []))
