"""Real items over padded slots in the descents' device calls: the
window's growth of `attestation_isolation_probe_items_total` over that of
`attestation_isolation_probe_slots_total`. A probe runs the failed batch's
own executable, so a half of 32, 16, ... 1 fills 64 slots: this is what
the one-executable rule costs in padding. None, never a raise, where the
program has no such counters or made no probe."""
from benchmark import observe

LAYER, UNIT = "firehose batching", "%"
ITEMS = "attestation_isolation_probe_items_total"
SLOTS = "attestation_isolation_probe_slots_total"


def read(run):
    slots = observe.series_delta(run["before"], run["after"], SLOTS)
    if not slots:
        return None
    return 100.0 * observe.series_delta(run["before"], run["after"],
                                        ITEMS) / slots
