"""Perf ledger + regression gate (tools/perf): byte-compatible stdout
emission with enriched JSONL append, rolling-median gating that catches
a seeded 2x slowdown and tolerates band-width noise, direction
inference, corrupt-row resilience, and the CLI exit codes bench.py's
preflight keys off.
"""

import json
import os

import pytest

from tools.perf import (
    DEFAULT_TOLERANCE,
    MIN_HISTORY,
    check_ledger,
    direction_of,
    emit_bench_line,
    git_commit,
    load_rows,
)
from tools.perf.__main__ import main as perf_main


@pytest.fixture
def ledger(tmp_path, monkeypatch):
    path = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("BENCH_LEDGER_PATH", path)
    monkeypatch.delenv("BENCH_LEDGER", raising=False)
    return path


def _seed(path, metric, values, unit="sigs/s"):
    with open(path, "a") as f:
        for v in values:
            f.write(json.dumps(
                {"metric": metric, "unit": unit, "value": v}
            ) + "\n")


# ---------------------------------------------------------- emission


def test_emit_bench_line_stdout_byte_compatible(ledger, capsys):
    payload = {"metric": "bls_multi_verify_throughput",
               "unit": "sigs/s", "value": 123.4, "n": 512}
    emit_bench_line(payload, config={"n": 512})
    out = capsys.readouterr().out
    # the printed line is EXACTLY what the inline print produced before
    assert out == json.dumps(payload) + "\n"
    rows, corrupt = load_rows(ledger)
    assert corrupt == 0 and len(rows) == 1
    row = rows[0]
    assert row["metric"] == payload["metric"]
    assert row["value"] == payload["value"]
    assert row["config"] == {"n": 512}
    assert row["commit"] == git_commit()
    assert row["host_cores"] == (os.cpu_count() or 1)
    assert row["platform"] and isinstance(row["ts"], float)


def test_emit_bench_line_ledger_opt_outs(ledger, capsys, monkeypatch):
    emit_bench_line({"metric": "m", "value": 1, "unit": "s"},
                    ledger=False)
    assert load_rows(ledger)[0] == []
    monkeypatch.setenv("BENCH_LEDGER", "0")
    emit_bench_line({"metric": "m", "value": 1, "unit": "s"})
    assert load_rows(ledger)[0] == []
    capsys.readouterr()


def test_emit_bench_line_stream_kwarg(ledger, capsys):
    import sys

    emit_bench_line({"metric": "m", "value": 2, "unit": "s"},
                    stream=sys.stderr)
    captured = capsys.readouterr()
    assert captured.out == ""
    assert json.loads(captured.err) == {"metric": "m", "value": 2,
                                        "unit": "s"}


# ------------------------------------------------------------- gating


def test_direction_inference():
    assert direction_of("bls_multi_verify_throughput", "sigs/s") == "higher"
    assert direction_of("anything", "blobs/s") == "higher"
    assert direction_of("coldstart_restart_to_first_verified_batch",
                        "s") == "lower"
    assert direction_of("verify_p50_latency", "ms") == "lower"
    assert direction_of("mainnet_soak", "mixed") is None
    assert direction_of("verify_chaos_soak", "faults survived") is None


def test_check_green_on_fresh_and_noisy_ledger(ledger):
    failures, report = check_ledger(path=ledger)
    assert failures == [] and report == []
    # band-width noise around a stable median must pass
    _seed(ledger, "bls_multi_verify_throughput",
          [100.0, 104.0, 96.0, 101.0, 99.0, 100.0 * (1 - 0.35)])
    failures, report = check_ledger(path=ledger)
    assert failures == []
    entry = report[0]
    assert entry["status"] == "ok" and entry["direction"] == "higher"


def test_seeded_2x_slowdown_fails_naming_metric(ledger):
    _seed(ledger, "bls_multi_verify_throughput",
          [100.0, 102.0, 98.0, 50.0])  # throughput halved
    failures, report = check_ledger(path=ledger)
    assert len(failures) == 1
    assert "bls_multi_verify_throughput" in failures[0]
    assert report[0]["status"] == "regressed"
    # lower-is-better metrics regress UPWARD: a 2x latency fails too
    _seed(ledger, "verify_p50_latency", [10.0, 10.5, 9.5, 20.0],
          unit="ms")
    failures, _ = check_ledger(path=ledger)
    assert any("verify_p50_latency" in f for f in failures)


def test_min_history_and_unchecked(ledger):
    _seed(ledger, "bls_multi_verify_throughput", [100.0, 1.0])
    failures, report = check_ledger(path=ledger)
    assert failures == []  # only 1 prior row < MIN_HISTORY
    assert MIN_HISTORY == 2
    assert report[0]["status"] == "insufficient-history"
    _seed(ledger, "verify_chaos_soak", [5, 5, 5, 0], unit="faults survived")
    failures, report = check_ledger(path=ledger)
    assert failures == []  # directionless units are never gated
    assert any(e["status"] == "unchecked" for e in report)


def test_corrupt_rows_skipped_not_fatal(ledger):
    with open(ledger, "a") as f:
        f.write("this is not json\n")
        f.write('{"metric": 42, "value": 1}\n')        # non-string metric
        f.write('[1, 2, 3]\n')                          # not an object
        f.write('{"metric": "trunc", "value": ')        # truncated write
        f.write("\n")
    _seed(ledger, "bls_multi_verify_throughput", [100.0, 99.0, 101.0, 98.0])
    # dict-valued breakdown rows are legal, just not gateable
    with open(ledger, "a") as f:
        f.write(json.dumps({"metric": "verify_scheduler_mixed_workload",
                            "unit": "ms", "value": {"block": 1}}) + "\n")
    rows, corrupt = load_rows(ledger)
    assert corrupt == 4
    assert len(rows) == 4
    failures, report = check_ledger(path=ledger)
    assert failures == []
    assert any(e.get("status") == "corrupt-rows" and e["corrupt"] == 4
               for e in report)


def test_rolling_window_and_tolerance_override(ledger):
    # 10 prior rows; window=8 must ignore the two oldest outliers
    _seed(ledger, "replay_throughput",
          [10_000.0, 10_000.0] + [100.0] * 8 + [95.0])
    failures, report = check_ledger(path=ledger, window=8)
    assert failures == []
    assert report[0]["median"] == pytest.approx(100.0)
    # explicit tolerance override tightens the band
    failures, _ = check_ledger(path=ledger, window=8, tolerance=0.01)
    assert len(failures) == 1
    assert DEFAULT_TOLERANCE == pytest.approx(0.40)


# ---------------------------------------------------------------- CLI


def test_cli_exit_codes(ledger, capsys):
    assert perf_main(["--check"]) == 0
    out = capsys.readouterr()
    assert "no regressions" in out.err
    _seed(ledger, "verify_scheduler_throughput", [100.0, 100.0, 100.0, 10.0])
    assert perf_main(["--check"]) == 1
    out = capsys.readouterr()
    assert "verify_scheduler_throughput" in out.err
    # report mode (no --check) still exits 1 on regression, and prints
    # one auditable JSON line per metric
    assert perf_main([]) == 1
    out = capsys.readouterr()
    entry = json.loads(out.out.splitlines()[0])
    assert entry["metric"] == "verify_scheduler_throughput"
    assert entry["status"] == "regressed"


# ------------------------------------------------- one process per chip


def test_bench_parents_import_without_jax():
    """bench.py's cold-start and device-sweep parents spawn children
    that need the chip; a parent that has touched JAX holds it. The
    module, and the cache-directory helper the cold-start parent calls,
    must import without JAX."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, bench\n"
        "from grandine_tpu.runtime.warmup import jit_cache_dir\n"
        "jit_cache_dir()\n"
        "assert 'jax' not in sys.modules, 'bench.py imported jax'\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


def test_detect_platform_does_not_hide_a_failed_device_lookup(monkeypatch):
    """A process that imported jax and cannot list its devices must not
    be filed as "host"."""
    import sys
    import types

    from tools.perf import detect_platform

    broken = types.SimpleNamespace(devices=lambda: 1 / 0)
    monkeypatch.setitem(sys.modules, "jax", broken)
    with pytest.raises(ZeroDivisionError):
        detect_platform()
    monkeypatch.delitem(sys.modules, "jax")
    assert detect_platform() == "host"
