"""Policy and layout manager: growth of the engines' own decide timers
(candidate builds included), summed over tenants, as a share of the
window."""


def read(run):
    return 100.0 * run.engine_delta["decide"] / run.window_s
