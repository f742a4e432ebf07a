"""What the readers of the program's split spans share: a stage's part
(`op` label of `verify_stage_seconds`), a wait of the flight record, a
phase of the compile scope. Each returns None, and never raises, where the
program has no such series or field (a program from before the split): the
result line then leaves the metric out.
"""

from __future__ import annotations

import statistics

from benchmark import observe

STAGE_SUM = "verify_stage_seconds_sum"
PHASE_TOTAL = "verify_compile_phase_seconds_total"


def batch_rows(run: dict) -> "list[dict]":
    return [r for r in run["flight"] if r["kind"] == "batch"]


def stage_op_ms_per_batch(run: dict, stage: str, op: str) -> "float | None":
    """The window's growth of verify_stage_seconds_sum{stage, op} (host
    clock) over the window's batches, in milliseconds."""
    batches = len(batch_rows(run))
    want = {("stage", stage), ("op", op)}
    if not batches or not any(
        name == STAGE_SUM and want <= set(labels)
        for name, labels in run["after"]
    ):
        return None
    total = observe.series_delta(run["before"], run["after"], STAGE_SUM,
                                 stage=stage, op=op)
    return total / batches * 1000.0


def flight_median_ms(run: dict, field: str) -> "float | None":
    """Median over the window's batches of one wait of the flight record
    (host clock), in milliseconds."""
    values = [r[field] for r in batch_rows(run) if field in r]
    return statistics.median(values) * 1000.0 if values else None


def setup_phase_s(run: dict, phase: str) -> "float | None":
    """Seconds the program spent in one JAX phase inside its compile
    scope up to the window's start: the ABSOLUTE value of the exposition
    taken there, which holds all of set-up."""
    for (name, labels), value in run["before"].items():
        if name == PHASE_TOTAL and ("phase", phase) in labels:
            return value
    return None
