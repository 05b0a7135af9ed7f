"""Packed plane: the program's ``plane.kernel`` spans (the host's dispatch
of the decision kernel, not its device time) as a share of the window.
Read from the program's tracer, on only in a traced run."""


def read(run):
    return run.program_share("plane.kernel")
