"""Items given a verdict in the window over the window's seconds (host
clock), in a cell whose end-to-end metric is the tail: against the
offered rate it says whether the node keeps up with the stream."""
LAYER, UNIT = "firehose settle and delivery", "sets/s"


def read(run):
    return run["seen"].get("sigsets_per_s")
