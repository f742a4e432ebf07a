"""Median over the window's batches of the flight record's collect_wait_s:
from the arrival of the batch's oldest item to the collector popping the
batch (no host work inside, unlike queue_wait_s)."""
from benchmark import span_metrics

LAYER, UNIT = "firehose batching", "ms"


def read(run):
    return span_metrics.flight_median_ms(run, "collect_wait_s")
