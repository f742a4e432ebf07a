"""`slasher_feed_ms` in the hostile cell (singles-forged), under a base name
of its own: tests/benchmark_harness/test_span_readers.py pins the
manifest's entries of base `slasher_feed_ms` to the two clean cells',
and a PR that adds a cell may not edit that file. The same reading as
benchmark/layer_metrics/slasher_feed_ms.py."""
from benchmark import span_metrics

LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return span_metrics.stage_op_ms_per_batch(run, "feedback", "slasher_feed")
