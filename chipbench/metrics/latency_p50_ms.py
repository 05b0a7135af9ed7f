"""Median latency over every event due in the window, from when the event
was due to when the pump that served it returned."""
import numpy as np


def read(run):
    return float(np.percentile(run.latencies_s, 50)) * 1e3
