"""Device milliseconds of one preconditioner apply (``PrecondApply``, the
L-then-U sweep), dispatched alone after the window in ``probe.sweep``
spans: inside the GMRES program the sweep is fused and has no name."""
from bench.readers import program_ms

PROGRAM = "jit__eval*"
SPAN = "probe.sweep"


def read(run):
    return program_ms(run, PROGRAM, SPAN, "probe_calls")
