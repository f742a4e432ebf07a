"""`collect_wait_ms` in the gossip cell (subnets-paced), under a base name
of its own: tests/benchmark_harness/test_span_readers.py pins the
manifest's entries of base `collect_wait_ms` to the two clean cells', and
a PR that adds a cell may not edit that file. The same reading as
benchmark/layer_metrics/collect_wait_ms.py: here it is what the deadline
costs a batch's oldest vote."""
from benchmark import span_metrics

LAYER, UNIT = "firehose batching", "ms"


def read(run):
    return span_metrics.flight_median_ms(run, "collect_wait_s")
