"""`collect_wait_ms` in the mixed cell (slot-thirds), under a base name of
its own: tests/benchmark_harness/test_span_readers.py pins the manifest's
entries of base `collect_wait_ms` to the two clean cells', and a PR that
adds a cell may not edit that file. The same reading as
benchmark/layer_metrics/collect_wait_ms.py: the deadline and the hold for
a vote batch, nothing for a burst batch that leaves full."""
from benchmark import span_metrics

LAYER, UNIT = "firehose batching", "ms"


def read(run):
    return span_metrics.flight_median_ms(run, "collect_wait_s")
