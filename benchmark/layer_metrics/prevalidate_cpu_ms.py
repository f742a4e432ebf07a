"""CPU time of prevalidation per batch: the window's growth of the
program's `verify_stage_cpu_seconds_total{stage="host_prep",
op="prevalidate"}` (the stage thread's own CPU clock) over the window's
batches; `prevalidate_ms` less this is time the thread waited inside the
stage (for the GIL, a lock or I/O)."""
from benchmark import timeline_metrics as tm

LAYER, UNIT = "host prep", "ms"


def read(run):
    return tm.stage_cpu_ms_per_batch(run, "host_prep", "prevalidate")
