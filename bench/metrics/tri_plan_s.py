"""Host seconds of triangular planning in the run (the program's
``ilu:plan.triangular`` span around ``build_triangular_plan``): set-up
only."""
from bench.program_trace import span_total_s


def read(run):
    return span_total_s("ilu:plan.triangular")
