"""Packed plane: the benchmark's span around the fleet's fused scoring
call (float32 check and conversion, upload, kernel, readback, row-weighted
reduction) as a share of the window.  Recorded only in a traced run."""


def read(run):
    if run.spans is None or not run.spans.counts.get("plane_pass"):
        return None
    return 100.0 * run.spans.seconds["plane_pass"] / run.window_s
