"""Settle-side host work per batch: the program's `feedback` stage seconds
(delivery of the verdicts to the controller, then the slasher feed, both
on the one completion thread) over the window's batches."""
from benchmark import observe

LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return observe.stage_ms_per_batch(run, "feedback")
