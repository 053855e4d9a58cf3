"""Device-idle milliseconds inside the program's value-push host steps
(the ``ilu:push.*`` spans other than ``ilu:push.factorize``: the value
scatter, the fetch of the factor, the CSR gather, the audit, the
triangular rebind and the device put) per ``push_values`` span."""
from bench.program_trace import push_idle_ms


def read(run):
    return push_idle_ms(run)
