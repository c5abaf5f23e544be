"""Seconds per whole-graph query: the window over the queries
completed in it, each from the ``Engine.query`` call to its host
result."""


def read(run):
    if run.loop != "repeat" or not run.completed:
        return None
    return run.window_s / run.completed
