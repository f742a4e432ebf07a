"""The cell's end-to-end percentile over its aggregates alone: nearest-
rank 95th percentile of (verdict delivered - aggregate due), host clock,
as the driver puts it into what the window saw. None where the driver
tells the kinds apart no further."""
LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return run["seen"].get("aggregates_p95_ms")
