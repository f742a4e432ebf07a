"""Device idle time the host's preparation of the next call held it back,
per batch: the window's growth of the program's
`verify_device_idle_seconds_total{cause="prevalidate"|"host_prep"}`
(prevalidation, then decompression, registry sync, packing and upload up
to the dispatch) over the window's batches."""
from benchmark import timeline_metrics as tm

LAYER, UNIT = "device", "ms"


def read(run):
    return tm.idle_ms_per_batch(run, ("prevalidate", "host_prep"))
