"""Device: the share of the traced window in which no operation ran on
the chip (one minus the union of device operation intervals)."""


def read(run):
    trace = run.trace
    if trace is None or trace.devices == 0 or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
