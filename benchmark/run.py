"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
                             [--control <name>]

One process, JAX imported once. Fails (never falls back) when JAX finds no
TPU or fewer chips than the cell asks for. Prints phase lines stamped with
the seconds since the process started, so a run that is cut shows where
its time went; the last line of standard output is the result object and
the last lines of standard error are every number compared beside its
limit. With `--trace 0` the metrics are the cell's end-to-end metrics,
with `--trace 1` its per-layer metrics. `--control <name>` runs the
program with one guarantee of the configuration broken (the driver's
`CONTROLS`): such a run has to print `correct` false.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(**row) -> None:
    print(json.dumps({**row, "at_s": round(time.perf_counter() - T0, 1)}),
          flush=True)


def find_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(
            f"jax.devices() is {dev.platform!r}, not 'tpu': the benchmark "
            "measures the chip and has no host path"
        )
    if len(devices) < chips:
        raise SystemExit(f"{len(devices)} devices visible, the cell asks "
                         f"for {chips}")
    return dev, devices[:chips]


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             control: "str | None" = None) -> dict:
    """The whole of a run but the printing; returns the result object.
    `require_tpu=False` is for the rehearsal tests alone: the result then
    carries no device-named metric."""
    from benchmark import loader, trace_reduce, work

    cell = loader.load_cell(root, workload)
    dev, devices = find_devices(cell["chips"], require_tpu)
    on_tpu = dev.platform == "tpu"
    say(phase="device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices))
    module = loader.load_driver(cell["bench_dir"], cell["config"]["driver"])
    if control is not None:
        module.CONTROLS[control]()
        say(phase="control", broken=control)
    driver = module.Driver(cell, seed, say)
    trace_dir = None
    if trace and on_tpu:
        trace_dir = os.path.join(root, ".bench_scratch", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T0
        say(phase="window", seconds=seconds, setup_s=round(setup_s, 1))
        seen = driver.run(seconds, trace_dir)
        say(phase="closed", **{k: v for k, v in seen.items() if k != "gen"})
        driver.settle()
        driver.traced_batch(trace)
        say(phase="traced" if trace_dir else "untraced")
        peak = max(
            ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices), default=0,
        )
        driver.stop()
        checks = driver.checks()
        say(phase="compared")
    finally:
        driver.stop()
        driver.close()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics, breakdown = {}, None
    if not trace:
        values = dict(seen, setup_s=setup_s)
        for m in cell["end_to_end"]:
            value = values[m["name"]]
            if value != value or value in (float("inf"), float("-inf")):
                value = 1e12  # an item never answered: correct is false
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reduced = None
        if trace_dir is not None:
            path = driver.trace.file()
            if path is None:
                raise SystemExit("the profiler wrote no trace")
            reduced = trace_reduce.reduce(
                trace_reduce.load(path), cell["kernel_module_match"]
            )
            if reduced is None:
                raise SystemExit("no operation ran on the device in the "
                                 "traced window")
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
            shutil.rmtree(trace_dir, ignore_errors=True)
        seen_by_readers = {
            "before": driver.counters_before, "after": driver.counters_after,
            "flight": driver.flight_rows, "gen": seen["gen"],
            "trace": reduced, "calls": driver.calls(),
            "peaks": work.load_peaks(dev.device_kind) if on_tpu else None,
            "window_s": seen.get("window_s"),
            "window_calls": driver.window_calls, "seen": seen,
        }
        for m in cell["per_layer"]:
            reader = loader.load_reader(cell["bench_dir"], m["name"])
            value = reader.read(seen_by_readers)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in checks}
    correct = all(
        (value == limit if name == "sampled" else value <= limit)
        for name, value, limit in checks
    )
    failed = sum(int(v) for n, v, _ in checks
                 if n in ("rejected_valid", "missing_verdicts"))
    result = {"correct": bool(correct), "attempted": int(seen["attempted"]),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), control=args.control)
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"compared {name} = {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
