"""`dispatch_wait_ms` in the mixed cell (slot-thirds), under a base name
of its own for the reason given in collect_wait_ms_mixed.py. The same
reading as benchmark/layer_metrics/dispatch_wait_ms.py: how long a
prepared batch stood at the pipeline's semaphore."""
from benchmark import span_metrics

LAYER, UNIT = "firehose batching", "ms"


def read(run):
    return span_metrics.flight_median_ms(run, "dispatch_wait_s")
