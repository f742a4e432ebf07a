"""The interpreter's collections inside the window: the growth of the
program's `process_gc_pause_seconds_total` over all generations, in
milliseconds. A collection stops every thread of the process; one of the
oldest generation took 350-470 ms before the heap was frozen after
set-up."""
from benchmark import timeline_metrics as tm

LAYER, UNIT = "process", "ms"


def read(run):
    if not tm.has(run, tm.GC_PAUSE):
        return None
    return tm.growth(run, tm.GC_PAUSE) * 1000.0
