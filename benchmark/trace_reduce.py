"""From a profiler trace (`.xplane.pb`) to the numbers the device metrics
read: the device's busy time as the union of its operations' intervals,
the verify kernel's time per call, the operations that took most time and
the longest idle gaps by what the host was doing.

`load(path)` reads the file with `jax.profiler.ProfileData` alone and
returns plain lists; `reduce(...)` is pure Python over those lists, so the
tests check it on hand-built traces.

Layout of a TPU trace as this reduction reads it (checked by hand on the
first chip trace of PR 23): one plane per chip named `/device:TPU:<i>`
with a line `XLA Ops` (one event per executed HLO operation) and a line
`XLA Modules` (one event per executed program, named
`jit_<function>(<fingerprint>)`); host planes (`/host:CPU`) with one line
per thread holding `TraceMe` events, among them the
`jax.profiler.TraceAnnotation` spans of the benchmark (`bench/...`) and of
the program's capture session (`<scheme>/<kernel>/b<bucket>`). All
timestamps are nanoseconds on one clock.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPAN = re.compile(r"^(bench/[\w.\-/]+|[a-z0-9_]+/[A-Za-z0-9_]+/b\d+)$")
#: spans that only bound the traced window: not something the host "does"
CONTAINERS = ("bench/traced",)
#: an HLO operation's name carries its whole signature: keep its head
NAME_CHARS = 96


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]} with every event as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            row = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    row[key].append((ev.name[:NAME_CHARS], float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if HOST_SPAN.match(ev.name):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns)))
    return {"devices": devices, "host": host}


def union(intervals: "list[tuple[float, float]]") -> "list[tuple[float, float]]":
    """Merged, sorted intervals."""
    out: "list[list[float]]" = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(events, lo: float, hi: float):
    """Events cut to [lo, hi]; those outside are dropped."""
    out = []
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(trace: dict, kernel_match: str, window_span: str = "bench/traced",
           top: int = 10) -> "dict | None":
    """The reduced trace, or None when no operation ran on a device in it.

    window      the host span named `window_span` (the last one, if it was
                entered more than once); without one, the extent of the
                device's events
    busy_s      union of the operations' intervals inside the window,
                averaged over the chips that ran any
    kernel      module events whose name contains `kernel_match`, inside
                the window: calls and seconds, summed over chips
    device_ops  the operations that took most time: [[name, seconds], ...]
    idle_gaps   each gap between busy intervals goes to the host span that
                overlaps most of it; summed by span name, longest first
    """
    ns = 1e-9
    per_chip = {
        name: row["ops"] or row["modules"]
        for name, row in trace["devices"].items()
        if row["ops"] or row["modules"]
    }
    if not per_chip:
        return None
    spans = [s for s in trace["host"] if s[0] == window_span]
    if spans:
        _, lo, hi = spans[-1]
    else:
        lo = min(a for evs in per_chip.values() for _, a, _ in evs)
        hi = max(b for evs in per_chip.values() for _, _, b in evs)
    if hi <= lo:
        return None
    busy_total, op_seconds, gaps = 0.0, {}, {}
    host = [s for s in clip(trace["host"], lo, hi) if s[0] not in CONTAINERS]
    for evs in per_chip.values():
        inside = clip(evs, lo, hi)
        merged = union([(a, b) for _, a, b in inside])
        busy_total += sum(b - a for a, b in merged)
        for name, a, b in inside:
            op_seconds[name] = op_seconds.get(name, 0.0) + (b - a) * ns
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, best_s = "no_host_span", 0.0
            for name, a, b in host:
                s = _overlap(g0, g1, a, b)
                if s > best_s:
                    best, best_s = name, s
            gaps[best] = gaps.get(best, 0.0) + (g1 - g0) * ns
    if busy_total <= 0.0:
        return None
    calls, kernel_s = 0, 0.0
    for row in trace["devices"].values():
        for name, a, b in clip(row["modules"], lo, hi):
            if kernel_match in name:
                calls += 1
                kernel_s += (b - a) * ns

    def ranked(table):
        return [[k, v] for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_total * ns / len(per_chip),
        "chips": len(per_chip),
        "kernel_calls": calls,
        "kernel_s": kernel_s,
        "device_ops": ranked(op_seconds),
        "idle_gaps": ranked(gaps),
    }
