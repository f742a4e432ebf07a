"""Median over the window's batches of the flight record's queue_wait_s
(host clock): how long the batch's oldest item waited to be batched."""
import statistics

LAYER, UNIT = "firehose batching", "ms"


def read(run):
    waits = [r["queue_wait_s"] for r in run["flight"] if r["kind"] == "batch"]
    return statistics.median(waits) * 1000.0 if waits else None
