"""Requests per batched launch over the window:
``pipeline.batched_queries`` / ``pipeline.batched_launches``."""
from ehbench.readers import ratio


def read(run):
    return ratio(run, "pipeline.batched_queries", "pipeline.batched_launches")
