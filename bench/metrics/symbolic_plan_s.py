"""Host seconds of symbolic ILU(k) planning in the run (the program's
``ilu:plan.symbolic`` span around the fill-pattern builder): set-up only."""
from bench.program_trace import span_total_s


def read(run):
    return span_total_s("ilu:plan.symbolic")
