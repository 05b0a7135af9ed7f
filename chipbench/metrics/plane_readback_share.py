"""Packed plane: the program's ``plane.readback`` spans (the device
compare, the wait for the kernel and the copy back) as a share of the
window.  Read from the program's tracer, on only in a traced run."""


def read(run):
    return run.program_share("plane.readback")
