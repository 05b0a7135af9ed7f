"""Backend compiles inside the measured window (JAX's monitoring events);
the warm-up should leave none."""


def read(run):
    return float(run.compiles_in_window)
