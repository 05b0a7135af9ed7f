"""Serving front end: 95th percentile of the wait from when an event was
due to the start of the pump that took it (the benchmark's clock)."""
import numpy as np


def read(run):
    return float(np.percentile(run.queue_waits_s, 95)) * 1e3
