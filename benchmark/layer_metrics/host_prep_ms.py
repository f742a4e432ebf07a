"""Host preparation per batch: the program's `host_prep` stage seconds
(prevalidation, G2 decompression, registry sync, operand packing) over the
window's batches."""
from benchmark import observe

LAYER, UNIT = "host prep", "ms"


def read(run):
    return observe.stage_ms_per_batch(run, "host_prep")
