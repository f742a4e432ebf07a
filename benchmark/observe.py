"""What the harness observes of a run besides the device trace: the
program's Prometheus exposition parsed into numbers, percentiles, host
spans written into the profiler's trace, and the profiler session.
"""

from __future__ import annotations

import contextlib
import glob
import math
import os
import re
import sys
import time

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str) -> "dict[tuple, float]":
    """{(name, ((label, value), ...)): number} of a Prometheus text
    exposition, labels sorted."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(2) or "")))
        try:
            out[(m.group(1), labels)] = float(m.group(3))
        except ValueError:
            continue
    return out


def series_delta(before: dict, after: dict, name: str, **labels) -> float:
    """Sum over every series of `name` whose labels include `labels` of
    (after - before)."""
    want = set(labels.items())
    total = 0.0
    for (n, lab), value in after.items():
        if n == name and want <= set(lab):
            total += value - before.get((n, lab), 0.0)
    return total


def stage_ms_per_batch(run: dict, stage: str) -> "float | None":
    """The window's growth of the program's
    verify_stage_seconds_sum{stage=...} (host clock) over the window's
    batches, in milliseconds: what the stage readers share."""
    batches = sum(1 for r in run["flight"] if r["kind"] == "batch")
    if not batches:
        return None
    total = series_delta(run["before"], run["after"],
                         "verify_stage_seconds_sum", stage=stage)
    return total / batches * 1000.0


def kernels_called(before: dict, after: dict) -> "dict[str, int]":
    """{kernel: device calls between the two expositions}."""
    out = {}
    for (n, lab), value in after.items():
        if n == "device_kernel_calls_total":
            d = value - before.get((n, lab), 0.0)
            if d:
                out[dict(lab).get("kernel", "")] = int(d)
    return out


def percentile(values: "list[float]", q: float) -> float:
    """The q-th percentile by nearest rank over ALL values (an item with
    no answer is +inf and sits at the top)."""
    if not values:
        return float("nan")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def annotate(name: str):
    """A host span in the profiler's own trace (no-op cost when no trace
    is being taken: one C++ call)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


class TraceSession:
    """One short device trace inside the window. The program's capture
    session is opened annotation-only (its dispatch sites then write
    `scheme/kernel/bN` spans); the trace itself is started here with the
    Python tracer off, which would otherwise slow the host it measures."""

    # The chip records every executed operation, and the verify kernels
    # run ~1.25 million of them per CALL (loops with tiny bodies): ~90 MB
    # of trace and ~35 s of `stop_trace` per call traced (my chip runs, PR
    # 23; the TPU's other trace modes record either the same or nothing).
    # So a traced window holds one call.

    def __init__(self, profiler, trace_dir: "str | None") -> None:
        self.profiler, self.dir = profiler, trace_dir
        self.wanted = trace_dir is not None
        self.done = False
        self._span = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        # the reduction reads events, not the program's HLO: leaving the
        # (large, unrolled) module out shortens `stop_trace`
        options.enable_hlo_proto = False
        os.makedirs(self.dir, exist_ok=True)
        self.profiler.start(trace_dir=None, note="benchmark")
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def mark_begin(self) -> None:
        """The traced window starts here (span `bench/traced`), on the
        calling thread; `mark_end` must come from the same thread."""
        self._span = annotate("bench/traced")
        self._span.__enter__()

    def mark_end(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def stop(self) -> None:
        import jax

        self.mark_end()
        jax.profiler.stop_trace()
        self.profiler.stop()
        self.done = True

    def file(self) -> "str | None":
        if not self.done:
            return None
        found = sorted(glob.glob(
            os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")
        ))
        return found[-1] if found else None
