"""Real committee members over padded member slots in the first passes'
device calls: the window's growth of
`attestation_first_pass_members_total` over that of
`attestation_first_pass_member_slots_total` (batch slots x the width
bucket dispatched). A node that has seen one full-size aggregate runs
every call in the aggregates' width bucket, so a batch of single votes
fills one slot in 256 of each of its rows: this is what the one-executable
rule costs on the member axis. None, never a raise, where the program has
no such counters or made no first pass."""
from benchmark import observe

LAYER, UNIT = "firehose batching", "%"
MEMBERS = "attestation_first_pass_members_total"
SLOTS = "attestation_first_pass_member_slots_total"


def read(run):
    slots = observe.series_delta(run["before"], run["after"], SLOTS)
    if not slots:
        return None
    return 100.0 * observe.series_delta(run["before"], run["after"],
                                        MEMBERS) / slots
