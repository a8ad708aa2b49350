"""Set-up: process start to the window's first tick (imports, weights,
scheduler build, compiles or cache loads, warm-up ticks)."""


def read(run):
    return run.setup_s
