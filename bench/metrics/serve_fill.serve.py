"""Share of the landed batched frontier slots that held a live row:
``pipeline.batched_rows`` / ``pipeline.batched_slots`` (B x cap of each
batched launch) over the window."""
from ehbench.readers import ratio


def read(run):
    return ratio(run, "pipeline.batched_rows", "pipeline.batched_slots")
