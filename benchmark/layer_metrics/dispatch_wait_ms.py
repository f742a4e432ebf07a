"""Median over the window's batches of the flight record's dispatch_wait_s:
how long the pool thread was blocked on the pipeline's dispatch semaphore
before handing its batch to the completion thread."""
from benchmark import span_metrics

LAYER, UNIT = "firehose batching", "ms"


def read(run):
    return span_metrics.flight_median_ms(run, "dispatch_wait_s")
