"""The cell's end-to-end percentile over its single votes alone: nearest-
rank 95th percentile of (verdict delivered - vote due), host clock, as the
driver puts it into what the window saw. None where the driver tells the
kinds apart no further."""
LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return run["seen"].get("votes_p95_ms")
