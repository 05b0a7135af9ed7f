"""Seconds from process start to the window's start: tables, engines,
warm-up serving and kernel compiles (or persistent-cache loads)."""


def read(run):
    return run.setup_s
