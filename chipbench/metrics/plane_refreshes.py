"""Packed plane: rebuilds of the fleet's resident float32 plane in the
window (the program's counter ``plane.refreshes``).  The tracer adds a
counter at its first count, so where the window traced fleet passes and
the counter is absent, none was rebuilt; where the tracer was off or no
fleet pass ran, nothing is read."""


def read(run):
    program = run.program
    if program is None or "fleet.pass" not in program["spans"]:
        return None
    return float(program["counters"].get("plane.refreshes", 0))
