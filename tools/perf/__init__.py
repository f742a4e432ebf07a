"""Append-only perf ledger + regression gate.

Every bench.py mode emits its JSON result lines through ONE helper,
`emit_bench_line`: the stdout/stderr line stays byte-identical to the
historical inline `print(json.dumps(...))` (existing parsers keep
working), and an enriched row is appended to the JSONL ledger at
`tools/perf/ledger.jsonl`:

    {"metric": ..., "value": ..., "unit": ...,   <- the payload, verbatim
     "config": {...},                            <- mode knobs (BENCH_N, ...)
     "platform": "cpu|tpu|host", "commit": "<short sha>",
     "host_cores": N, "ts": <unix seconds>}

`python -m tools.perf --check` compares the NEWEST row per metric
against the rolling median of up to `--window` prior rows, with a
per-metric tolerance band and a direction inferred from the unit/name
(throughputs regress downward, latencies regress upward), and exits
nonzero naming the regressed metric. bench.py runs it in its preflight
next to lint/shapes/fuzz (BENCH_SKIP_PERF_CHECK=1 overrides).

Corrupt rows (truncated writes, non-JSON lines, non-numeric values) are
skipped and counted, never fatal: an append-only ledger shared by
crashing benches must degrade, not wedge the gate.

Env knobs: BENCH_LEDGER=0 disables the append, BENCH_LEDGER_PATH
relocates the ledger (tests), GRANDINE_COMMIT overrides the stamped
commit (CI detached checkouts).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional

LEDGER_PATH = os.path.join(os.path.dirname(__file__), "ledger.jsonl")

#: default relative tolerance band; per-metric overrides below. Wide on
#: purpose: single runs on a shared host swing widely, and the rolling
#: MEDIAN plus this band is what separates noise from the seeded-2x
#: regressions the gate must catch.
DEFAULT_TOLERANCE = 0.40
TOLERANCES = {
    "bls_multi_verify_throughput": 0.40,
    "verify_scheduler_throughput": 0.40,
    "replay_throughput": 0.40,
    # compressed-ingest e2e (bench.py --compressed): prep-inclusive wall
    # rate — regressing it means the host-prep bottleneck is creeping
    # back in, the exact thing the compressed plane exists to kill
    "bls_compressed_e2e_throughput": 0.40,
    # overload soak (bench.py --overload): worst HIGH-lane p95 ms while
    # a 4x LOW-lane burst runs under brownout control — regressing it
    # means shedding LOW traffic no longer protects HIGH traffic
    "verify_overload_soak": 0.40,
}

#: a metric needs this many PRIOR rows before the gate engages
MIN_HISTORY = 2

_COMMIT_CACHE: "list[Optional[str]]" = [None]


def git_commit() -> str:
    """Short commit hash stamped on every ledger row. Cached per
    process; GRANDINE_COMMIT overrides (CI); "unknown" off a checkout."""
    cached = _COMMIT_CACHE[0]
    if cached is not None:
        return cached
    commit = os.environ.get("GRANDINE_COMMIT")
    if not commit:
        try:
            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__)))),
                capture_output=True, text=True, timeout=10,
            ).stdout.strip() or "unknown"
        except Exception:
            commit = "unknown"
    _COMMIT_CACHE[0] = commit
    return commit


def detect_platform() -> str:
    """The accelerator platform, WITHOUT importing jax (a ledger append
    from a host-only process must stay host-only)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return "host"
    # a process that imported jax and cannot list its devices has no
    # platform to stamp: let it raise rather than file the row as "host"
    return str(jax.devices()[0].platform)


def emit_bench_line(payload: dict, *, stream=None, ledger: bool = True,
                    config: "Optional[dict]" = None,
                    ledger_path: "Optional[str]" = None) -> dict:
    """Print `payload` exactly as `json.dumps(payload)` (byte-compatible
    with the inline prints this helper replaced) and append the enriched
    row to the perf ledger. `ledger=False` skips the append (child-
    process intermediate lines, error-path zero lines). Ledger trouble
    never raises — the bench number matters more than the bookkeeping."""
    print(json.dumps(payload), file=stream if stream is not None else
          sys.stdout)
    if not ledger or os.environ.get("BENCH_LEDGER") == "0":
        return dict(payload)
    row = dict(payload)
    row.setdefault("config", dict(config or {}))
    row.setdefault("platform", detect_platform())
    row.setdefault("commit", git_commit())
    row.setdefault("host_cores", os.cpu_count() or 1)
    row.setdefault("ts", time.time())
    path = (ledger_path or os.environ.get("BENCH_LEDGER_PATH")
            or LEDGER_PATH)
    try:
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError:
        pass
    return row


def direction_of(metric: str, unit: str) -> "Optional[str]":
    """"higher" (throughput-like: bigger is better), "lower" (latency/
    duration-like), or None (unchecked — breakdown dicts, counts)."""
    u = (unit or "").lower()
    m = (metric or "").lower()
    if "/s" in u or m.endswith(("throughput", "_rate", "sigs_per_sec")):
        return "higher"
    if u in ("s", "ms", "us", "seconds") or "latency" in m or (
        m.endswith(("_seconds", "_s", "_ms"))
    ):
        return "lower"
    return None


def load_rows(path: str):
    """(rows, corrupt_count): parse the ledger, skipping rows that are
    not JSON objects with a string metric and a numeric value."""
    rows = []
    corrupt = 0
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return [], 0
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            row = json.loads(line)
        except ValueError:
            corrupt += 1
            continue
        if not isinstance(row, dict) or not isinstance(
            row.get("metric"), str
        ):
            corrupt += 1
            continue
        value = row.get("value")
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            # breakdown/report rows (dict values) are legal ledger
            # citizens, just not gateable — only malformed lines are
            # "corrupt"
            continue
        rows.append(row)
    return rows, corrupt


def _median(xs: "list[float]") -> float:
    s = sorted(xs)
    mid = len(s) // 2
    if len(s) % 2:
        return s[mid]
    return (s[mid - 1] + s[mid]) / 2.0


def check_ledger(path: "Optional[str]" = None, window: int = 8,
                 tolerance: "Optional[float]" = None):
    """Gate the newest row of every metric against the rolling median
    of up to `window` prior rows. Returns (failures, report): `failures`
    is a list of human lines naming each regressed metric; `report` is
    one dict per metric with the comparison inputs (also covers metrics
    passed or skipped, so --check output is auditable)."""
    path = path or os.environ.get("BENCH_LEDGER_PATH") or LEDGER_PATH
    rows, corrupt = load_rows(path)
    by_metric: "dict[str, list[dict]]" = {}
    for row in rows:
        by_metric.setdefault(row["metric"], []).append(row)
    failures: "list[str]" = []
    report: "list[dict]" = []
    for metric, history in sorted(by_metric.items()):
        newest = history[-1]
        prior = history[:-1][-window:]
        entry = {
            "metric": metric,
            "value": newest["value"],
            "unit": newest.get("unit", ""),
            "prior_rows": len(prior),
        }
        if len(prior) < MIN_HISTORY:
            entry["status"] = "insufficient-history"
            report.append(entry)
            continue
        direction = direction_of(metric, str(newest.get("unit", "")))
        if direction is None:
            entry["status"] = "unchecked"
            report.append(entry)
            continue
        med = _median([float(r["value"]) for r in prior])
        tol = (tolerance if tolerance is not None
               else TOLERANCES.get(metric, DEFAULT_TOLERANCE))
        entry.update({
            "median": med, "tolerance": tol, "direction": direction,
        })
        value = float(newest["value"])
        if direction == "higher":
            floor = med * (1.0 - tol)
            regressed = value < floor
            entry["bound"] = floor
        else:
            ceil = med * (1.0 + tol)
            regressed = value > ceil
            entry["bound"] = ceil
        entry["status"] = "regressed" if regressed else "ok"
        report.append(entry)
        if regressed:
            failures.append(
                f"perf regression: {metric} = {value:g} "
                f"{newest.get('unit', '')} vs rolling median {med:g} "
                f"(tolerance {tol:.0%}, {direction}-is-better, "
                f"{len(prior)} prior rows)"
            )
    if corrupt:
        report.append({"metric": "_ledger", "status": "corrupt-rows",
                       "corrupt": corrupt})
    return failures, report


__all__ = [
    "LEDGER_PATH",
    "DEFAULT_TOLERANCE",
    "TOLERANCES",
    "MIN_HISTORY",
    "emit_bench_line",
    "git_commit",
    "detect_platform",
    "direction_of",
    "load_rows",
    "check_ledger",
]
