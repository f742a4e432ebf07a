"""Slasher feed per batch: the program's `feedback` stage, part
`slasher_feed` (every accepted attesting index through the slasher, after
delivery, on the completion thread), over the window's batches."""
from benchmark import span_metrics

LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return span_metrics.stage_op_ms_per_batch(run, "feedback", "slasher_feed")
