"""Events completed per second: every event due in the window, over the
time from the window's start until the last of them was served."""


def read(run):
    return len(run.latencies_s) / run.window_s
