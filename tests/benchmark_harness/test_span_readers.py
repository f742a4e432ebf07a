"""The readers of the program's split spans (benchmark/span_metrics.py and
one file per metric under benchmark/layer_metrics/): each on a hand-built
run, on a run of a program from before the split (nothing to read: None,
no raise), and through one `--trace 1` rehearsal of each kind of cell at
the tiny size, where the parts have to sum to the whole the accepted
metrics read (`host_prep_ms`, `feedback_ms`). The tiny root's
BENCHMARK.json is not edited: the entries are appended in the temporary
copy the fixture makes."""

import json
import math
import os

import pytest

from benchmark import loader, observe, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

STAGE_PARTS = {
    "prevalidate_ms": ("host_prep", "prevalidate"),
    "sig_decompress_ms": ("host_prep", "g2_decompress"),
    "pack_ms": ("host_prep", "pack_aggregate_idx"),
    "deliver_ms": ("feedback", "deliver"),
    "slasher_feed_ms": ("feedback", "slasher_feed"),
}
WAITS = {
    "settle_wait_ms": "settle_wait_s",
    "collect_wait_ms": "collect_wait_s",
    "dispatch_wait_ms": "dispatch_wait_s",
}
PHASES = {"setup_trace_s": "trace", "setup_lower_s": "lower",
          "setup_backend_s": "backend"}
CELLS = {".tput": "firehose-tiny.singles", ".lat": "firehose-tiny.aggregates"}


def new_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        rows = json.load(fh)["per_layer"]
    bases = set(STAGE_PARTS) | set(WAITS) | set(PHASES)
    return [r for r in rows if r["name"].split(".")[0] in bases]


def exposition(stage_sums: dict, phases: "dict | None" = None) -> dict:
    lines = [
        f'verify_stage_seconds_sum{{stage="{s}",lane="attestation",'
        f'op="{op}"}} {v}' for (s, op), v in stage_sums.items()
    ]
    lines += [f'verify_compile_phase_seconds_total{{phase="{p}"}} {v}'
              for p, v in (phases or {}).items()]
    return observe.parse_exposition("\n".join(lines))


def hand_built_run() -> dict:
    before = exposition(
        {("host_prep", "prevalidate"): 1.0, ("host_prep", "g2_decompress"): 2.0,
         ("host_prep", "pack_aggregate_idx"): 0.5, ("feedback", "deliver"): 0.25,
         ("feedback", "slasher_feed"): 4.0},
        {"trace": 100.0, "lower": 40.0, "backend": 20.0,
         "cache_retrieval": 3.0},
    )
    after = exposition(
        {("host_prep", "prevalidate"): 1.4, ("host_prep", "g2_decompress"): 2.2,
         ("host_prep", "pack_aggregate_idx"): 0.6, ("feedback", "deliver"): 0.27,
         ("feedback", "slasher_feed"): 4.8},
        {"trace": 100.0, "lower": 40.0, "backend": 20.0},
    )
    flight = [
        {"kind": "batch", "settle_wait_s": s, "collect_wait_s": c,
         "dispatch_wait_s": d}
        for s, c, d in ((0.1, 0.01, 0.0), (0.3, 0.02, 0.5), (4.0, 0.03, 0.7),
                        (0.2, 0.04, 0.6))
    ] + [{"kind": "breaker"}]
    return {"before": before, "after": after, "flight": flight}


def test_the_manifest_gained_the_nineteen_entries_with_their_readers():
    rows = new_entries()
    assert len(rows) == 19
    assert sorted(r["name"] for r in rows) == sorted(
        [b + s for b in list(STAGE_PARTS) + list(WAITS) for s in CELLS]
        + list(PHASES))
    for row in rows:
        reader = loader.load_reader(BENCH, row["name"])
        assert (reader.UNIT, reader.LAYER) == (row["unit"], row["layer"])
        assert row["better"] == "lower"
        if row["name"] in PHASES:
            assert row["moves"] == "setup_s" and len(row["workloads"]) == 2
            assert row["source"] == "program_counter"
        else:
            assert row["source"] == "program_span"
            assert row["moves"] == ("sigsets_per_s" if row["name"].endswith(
                ".tput") else "verdict_p95_ms")


@pytest.mark.parametrize("metric,want", [
    ("prevalidate_ms.tput", 100.0), ("sig_decompress_ms.lat", 50.0),
    ("pack_ms.tput", 25.0), ("deliver_ms.lat", 5.0),
    ("slasher_feed_ms.tput", 200.0),
    ("settle_wait_ms.lat", 250.0), ("collect_wait_ms.tput", 25.0),
    ("dispatch_wait_ms.lat", 550.0),
    ("setup_trace_s", 100.0), ("setup_lower_s", 40.0),
    ("setup_backend_s", 20.0),
])
def test_reader_on_a_hand_built_run(metric, want):
    reader = loader.load_reader(BENCH, metric)
    assert reader.read(hand_built_run()) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(
    [b + ".tput" for b in list(STAGE_PARTS) + list(WAITS)] + list(PHASES)))
def test_reader_finds_nothing_in_a_program_from_before_the_split(metric):
    """As the parent commit exposes it: no `op` label, no wait fields, no
    phase counters. The reader returns None and does not raise, so the
    result line leaves the metric out."""
    text = ('verify_stage_seconds_sum{stage="host_prep",lane="attestation"}'
            ' 3.0\nverify_stage_seconds_sum{stage="feedback",'
            'lane="attestation"} 1.0')
    old = {"before": {}, "after": observe.parse_exposition(text),
           "flight": [{"kind": "batch", "queue_wait_s": 0.1}]}
    assert loader.load_reader(BENCH, metric).read(old) is None
    empty = {"before": {}, "after": {}, "flight": []}
    assert loader.load_reader(BENCH, metric).read(empty) is None


@pytest.fixture(scope="module")
def span_root(tiny_root):
    """The tiny root with this PR's entries appended for the tiny cells,
    and one reader of the test's own (`registry_sync_ms`: the fourth part
    of host prep, which the benchmark does not report)."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as fh:
        original = fh.read()
    m = json.loads(original)
    for row in new_entries():
        suffix = "." + row["name"].rsplit(".", 1)[-1]
        cells = [CELLS[suffix]] if suffix in CELLS else list(CELLS.values())
        m["per_layer"].append(dict(row, workloads=cells))
    extra = os.path.join(tiny_root, "benchmark", "layer_metrics",
                         "registry_sync_ms.py")
    with open(extra, "w") as fh:
        fh.write(
            "from benchmark import span_metrics\n\n"
            'LAYER, UNIT = "host prep", "ms"\n\n\n'
            "def read(run):\n"
            "    return span_metrics.stage_op_ms_per_batch(\n"
            '        run, "host_prep", "registry_sync")\n')
    for suffix, cell in CELLS.items():
        m["per_layer"].append({
            "name": "registry_sync_ms" + suffix, "unit": "ms",
            "better": "lower", "source": "program_span", "layer": "host prep",
            "moves": "setup_s", "workloads": [cell]})
    with open(path, "w") as fh:
        json.dump(m, fh)
    try:
        yield tiny_root
    finally:
        with open(path, "w") as fh:
            fh.write(original)
        os.remove(extra)


@pytest.mark.parametrize("suffix,seconds", [(".tput", 2.0), (".lat", 3.0)])
def test_traced_rehearsal_prints_every_new_metric_and_parts_sum(
        span_root, suffix, seconds):
    res = run.run_cell(span_root, CELLS[suffix], 2**31 + 24, seconds, True,
                       require_tpu=False)
    assert res["correct"] is True, res["compared"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for base in list(STAGE_PARTS) + list(WAITS):
        value = got[base + suffix]
        assert math.isfinite(value) and value >= 0.0, (base, value)
    for name in PHASES:
        assert math.isfinite(got[name]) and got[name] > 0.0, (name, got)
    # the parts sum to the whole the accepted metrics read
    host_prep = sum(got[b + suffix] for b in (
        "prevalidate_ms", "sig_decompress_ms", "pack_ms", "registry_sync_ms"))
    assert host_prep == pytest.approx(got["host_prep_ms" + suffix], rel=0.02)
    feedback = got["deliver_ms" + suffix] + got["slasher_feed_ms" + suffix]
    assert feedback == pytest.approx(got["feedback_ms" + suffix], rel=0.02)
    assert got["host_prep_ms" + suffix] > 0 and got["feedback_ms" + suffix] > 0
    # the accepted batching metric still reads, and holds its parts
    assert got["queue_wait_ms" + suffix] >= got["collect_wait_ms" + suffix]
