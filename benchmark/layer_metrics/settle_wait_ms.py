"""Median over the window's batches of the flight record's settle_wait_s:
how long a dispatched batch sat in the completion queue behind its
predecessor's settle and feedback."""
from benchmark import span_metrics

LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return span_metrics.flight_median_ms(run, "settle_wait_s")
