"""Host seconds of factor planning in the run (the program's
``ilu:plan.factor`` span around ``build_factor_plan``): set-up only."""
from bench.program_trace import span_total_s


def read(run):
    return span_total_s("ilu:plan.factor")
