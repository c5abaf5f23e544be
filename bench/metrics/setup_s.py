"""Set-up: process start to the start of the window (generation,
load, warm-up and compilation)."""


def read(run):
    return run.setup_s
