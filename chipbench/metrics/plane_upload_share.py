"""Packed plane: the program's ``plane.upload`` spans (float32 cast and
host-to-device copy of a pass's numpy operands) as a share of the window.
Read from the program's tracer, on only in a traced run."""


def read(run):
    return run.program_share("plane.upload")
