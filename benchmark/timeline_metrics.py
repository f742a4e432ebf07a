"""What the readers of the program's device timeline, stage CPU clock and
collection counters share: a counter's growth over the window, in total
or over the window's batches. Each returns None, and never raises, where
the program has no such series (a program from before the timeline): the
result line then leaves the metric out.
"""

from __future__ import annotations

from benchmark import observe

BUSY = "verify_device_seconds_total"
IDLE = "verify_device_idle_seconds_total"
STAGE_CPU = "verify_stage_cpu_seconds_total"
GC_PAUSE = "process_gc_pause_seconds_total"


def has(run: dict, name: str, **labels) -> bool:
    """Whether the window's closing exposition holds a series of `name`
    whose labels include `labels`."""
    want = set(labels.items())
    return any(n == name and want <= set(lab) for n, lab in run["after"])


def growth(run: dict, name: str, **labels) -> float:
    return observe.series_delta(run["before"], run["after"], name, **labels)


def batches(run: dict) -> int:
    return sum(1 for r in run["flight"] if r["kind"] == "batch")


def idle_ms_per_batch(run: dict, causes) -> "float | None":
    """The window's growth of the device's idle seconds charged to
    `causes`, over the window's batches, in milliseconds."""
    n = batches(run)
    if not n or not all(has(run, IDLE, cause=c) for c in causes):
        return None
    return sum(growth(run, IDLE, cause=c) for c in causes) / n * 1000.0


def stage_cpu_ms_per_batch(run: dict, stage: str, op: str) -> "float | None":
    """The window's growth of the CPU seconds of one stage's part, over
    the window's batches, in milliseconds."""
    n = batches(run)
    if not n or not has(run, STAGE_CPU, stage=stage, op=op):
        return None
    return growth(run, STAGE_CPU, stage=stage, op=op) / n * 1000.0
