"""Operand packing per batch: the program's `host_prep` stage, part
`pack_aggregate_idx` (limb packing, the hash-to-G2 cache, randomizers, the
MSM plan), over the window's batches."""
from benchmark import span_metrics

LAYER, UNIT = "host prep", "ms"


def read(run):
    return span_metrics.stage_op_ms_per_batch(run, "host_prep", "pack_aggregate_idx")
