"""Share of the window's batches that held items of more than one width
bucket (single votes beside aggregates): the window's growth of
`attestation_mixed_batches_total` over the window's batches (flight
records). None, never a raise, where the program has no such counter or
the window no batch."""
from benchmark import observe

LAYER, UNIT = "firehose batching", "%"
MIXED = "attestation_mixed_batches_total"


def read(run):
    batches = sum(1 for r in run["flight"] if r["kind"] == "batch")
    if not batches or not any(name == MIXED for name, _labels in run["after"]):
        return None
    return 100.0 * observe.series_delta(run["before"], run["after"],
                                        MIXED) / batches
