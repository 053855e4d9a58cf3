"""Mean inner GMRES iterations per step of the window (the service response) (``SolveResult``)."""
from bench.readers import mean


def read(run):
    return mean(run.counters.get("iterations", []))
