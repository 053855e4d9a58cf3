"""Host seconds of the set-up calls that plan and factor (the benchmark's
``plan`` span: ``ilu`` and ``precond()`` in the library cell,
``register_matrix`` in the service cell), less the compile seconds inside."""


def read(run):
    return run.counters.get("plan_s")
