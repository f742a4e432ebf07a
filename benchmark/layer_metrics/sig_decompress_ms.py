"""Signature decompression per batch: the program's `host_prep` stage, part
`g2_decompress` (one pure-Python square root per item), over the window's
batches."""
from benchmark import span_metrics

LAYER, UNIT = "host prep", "ms"


def read(run):
    return span_metrics.stage_op_ms_per_batch(run, "host_prep", "g2_decompress")
