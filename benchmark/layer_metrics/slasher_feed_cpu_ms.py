"""CPU time of the slasher feed per batch: the window's growth of the
program's `verify_stage_cpu_seconds_total{stage="feedback",
op="slasher_feed"}` (the completion thread's own CPU clock) over the
window's batches; `slasher_feed_ms` less this is time the thread waited
inside the stage (for the GIL, a lock or I/O)."""
from benchmark import timeline_metrics as tm

LAYER, UNIT = "firehose settle and delivery", "ms"


def read(run):
    return tm.stage_cpu_ms_per_batch(run, "feedback", "slasher_feed")
