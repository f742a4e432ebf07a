"""The descent over a failed batch, per isolated batch: the window's
growth of the program's `verify_stage_seconds_sum{stage="fallback"}` (the
whole descent: every probe's decompression, packing, upload, kernel call
and readback, observed once where the stage ends) over the growth of
`attestation_isolated_batches_total`. None, never a raise, where the
program has no such counter (a program from before it) or no batch was
isolated."""
from benchmark import observe

LAYER, UNIT = "firehose settle and delivery", "ms"
ISOLATED = "attestation_isolated_batches_total"


def read(run):
    isolated = observe.series_delta(run["before"], run["after"], ISOLATED)
    if not isolated:
        return None
    total = observe.series_delta(run["before"], run["after"],
                                 "verify_stage_seconds_sum", stage="fallback")
    return total / isolated * 1000.0
