"""Prevalidation per batch: the program's `host_prep` stage, part
`prevalidate` (committee lookup, fork-choice windows, one pubkey
decompression per member), over the window's batches."""
from benchmark import span_metrics

LAYER, UNIT = "host prep", "ms"


def read(run):
    return span_metrics.stage_op_ms_per_batch(run, "host_prep", "prevalidate")
