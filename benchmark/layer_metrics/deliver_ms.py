"""Delivery per batch: the program's `feedback` stage, part `deliver` (the
verdicts into fork choice), over the window's batches."""
from benchmark import span_metrics

LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return span_metrics.stage_op_ms_per_batch(run, "feedback", "deliver")
