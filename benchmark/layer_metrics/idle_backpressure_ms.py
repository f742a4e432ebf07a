"""Device idle time the pipeline's back-pressure held the next call back,
per batch: the window's growth of the program's
`verify_device_idle_seconds_total{cause="hold"|"pool_wait"}` (the formed
batch waited at the collector for a slot: a short one for the pipeline, any
for the bound on batches in flight, whose pool threads wait for the
settles and feeds on the completion thread; then for a pool thread) over
the window's batches."""
from benchmark import timeline_metrics as tm

LAYER, UNIT = "device", "ms"


def read(run):
    return tm.idle_ms_per_batch(run, ("hold", "pool_wait"))
