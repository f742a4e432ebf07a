"""Share of the window's batches that the collector closed by its
deadline, short of the batch bound: the window's growth of
`attestation_batches_closed_total{by="deadline"}` over that of all its
`by`. Such a batch runs padded in the node's one batch bucket, so this is
how often the padding engages. None, never a raise, where the program has
no such counter or closed no batch."""
from benchmark import observe

LAYER, UNIT = "firehose batching", "%"
CLOSED = "attestation_batches_closed_total"


def read(run):
    closed = observe.series_delta(run["before"], run["after"], CLOSED)
    if not closed:
        return None
    return 100.0 * observe.series_delta(run["before"], run["after"], CLOSED,
                                        by="deadline") / closed
