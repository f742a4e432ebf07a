"""The readers of the program's device timeline, stage CPU clock and
collection counters (benchmark/timeline_metrics.py and one file per base
name under benchmark/layer_metrics/): each on a hand-built run, on a run
of a program from before the timeline (nothing to read: None, no raise),
the manifest's 22 appended entries, and one `--trace 1` rehearsal of the
tiny singles cell that prints all six. The tiny root's BENCHMARK.json is
not edited: the entries are appended in the temporary copy the fixture
makes."""

import json
import math
import os

import pytest

from benchmark import loader, observe, run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")

CELLS = {".tput": "firehose-50k.singles-backlog",
         ".lat": "firehose-50k.aggregates-slot",
         ".forged": "firehose-50k-hostile.singles-forged",
         ".paced": "firehose-50k-gossip.subnets-paced",
         ".mixed": "firehose-50k-mixed.slot-thirds"}
#: base name -> (unit, layer, suffixes)
BASES = {
    "device_idle_pct": ("%", "device", tuple(CELLS)),
    "idle_prep_ms": ("ms", "device", tuple(CELLS)),
    "idle_backpressure_ms": ("ms", "device", tuple(CELLS)),
    "prevalidate_cpu_ms": ("ms", "host prep", (".tput", ".lat")),
    "slasher_feed_cpu_ms": ("ms", "firehose settle and delivery",
                            (".tput", ".lat")),
    "gc_pause_ms": ("ms", "process", (".lat", ".paced", ".mixed")),
}
TINY = "firehose-tiny.singles"


def exposition(samples: dict) -> dict:
    return observe.parse_exposition("\n".join(
        f"{name}{{{labels}}} {value}" if labels else f"{name} {value}"
        for (name, labels), value in samples.items()))


def idle(**seconds):
    return {("verify_device_idle_seconds_total", f'cause="{c}"'): s
            for c, s in seconds.items()}


def hand_built_run() -> dict:
    kernel = 'kernel="agg_fast_verify_msm_idx",scheme="bls"'
    prep = 'lane="attestation",op="prevalidate",stage="host_prep"'
    feed = 'lane="attestation",op="slasher_feed",stage="feedback"'
    before = exposition({
        ("verify_device_seconds_total", kernel): 100.0,
        **idle(traffic=1.0, collect=0.5, hold=0.25, pool_wait=0.0,
               prevalidate=2.0, host_prep=1.0, descent=0.0, gc=0.0,
               other=3.0),
        ("verify_stage_cpu_seconds_total", prep): 10.0,
        ("verify_stage_cpu_seconds_total", feed): 5.0,
        ("process_gc_pause_seconds_total", 'generation="0"'): 1.0,
        ("process_gc_pause_seconds_total", 'generation="2"'): 0.5,
    })
    after = exposition({
        ("verify_device_seconds_total", kernel): 118.0,
        **idle(traffic=1.5, collect=0.5, hold=0.45, pool_wait=0.6,
               prevalidate=4.0, host_prep=1.4, descent=0.0, gc=0.01,
               other=3.0),
        ("verify_stage_cpu_seconds_total", prep): 12.0,
        ("verify_stage_cpu_seconds_total", feed): 5.8,
        ("process_gc_pause_seconds_total", 'generation="0"'): 1.02,
        ("process_gc_pause_seconds_total", 'generation="2"'): 0.5,
    })
    flight = [{"kind": "batch"}] * 8 + [{"kind": "breaker"}]
    return {"before": before, "after": after, "flight": flight,
            "window_s": 36.0}


@pytest.mark.parametrize("metric,want", [
    ("device_idle_pct.tput", 50.0),            # 18 s busy of 36
    ("idle_prep_ms.paced", 300.0),             # 2.4 s over 8 batches
    ("idle_backpressure_ms.forged", 100.0),    # 0.8 s over 8 batches
    ("prevalidate_cpu_ms.lat", 250.0),
    ("slasher_feed_cpu_ms.tput", 100.0),
    ("gc_pause_ms.mixed", 20.0),
])
def test_reader_on_a_hand_built_run(metric, want):
    reader = loader.load_reader(BENCH, metric)
    assert reader.read(hand_built_run()) == pytest.approx(want)


@pytest.mark.parametrize("base", sorted(BASES))
def test_reader_finds_nothing_in_a_program_from_before_the_timeline(base):
    """As the parent commit exposes it: device seconds that are host
    deltas, the stages' wall clock, no idle, CPU or collection series. The
    reader returns None and does not raise, so the result line leaves the
    metric out."""
    text = ('verify_device_seconds_total{kernel="fast_aggregate",'
            'scheme="bls"} 30.0\n'
            'verify_stage_seconds_sum{stage="host_prep",lane="attestation",'
            'op="prevalidate"} 3.0')
    old = {"before": {}, "after": observe.parse_exposition(text),
           "flight": [{"kind": "batch"}], "window_s": 36.0}
    reader = loader.load_reader(BENCH, base + BASES[base][2][0])
    assert reader.read(old) is None
    assert reader.read({"before": {}, "after": {}, "flight": [],
                        "window_s": None}) is None


def test_the_manifest_gained_the_22_entries_with_their_readers():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    want = [b + s for b, (_, _, sufs) in BASES.items() for s in sufs]
    assert len(want) == 22
    # appended, every earlier entry where it was
    assert [r["name"] for r in m["per_layer"][-22:]] == want
    for row in m["per_layer"][-22:]:
        base, suffix = row["name"].rsplit(".", 1)
        unit, layer, _ = BASES[base]
        module = loader.load_reader(BENCH, row["name"])
        assert (module.UNIT, module.LAYER) == (unit, layer) == (
            row["unit"], row["layer"])
        assert (row["source"], row["better"]) == ("program_counter", "lower")
        assert row["workloads"] == [CELLS["." + suffix]]
        assert row["moves"] == ("sigsets_per_s" if suffix in ("tput", "forged")
                                else "verdict_p95_ms")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           base + ".py"))


@pytest.fixture(scope="module")
def timeline_root(tiny_root):
    """The tiny root with one entry of each new base name appended for
    the tiny singles cell, beside the wall-clock `prevalidate_ms`."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as fh:
        original = fh.read()
    m = json.loads(original)
    for base, (unit, layer, _) in list(BASES.items()) + [
            ("prevalidate_ms", ("ms", "host prep", ()))]:
        m["per_layer"].append({
            "name": base + ".tput", "unit": unit, "better": "lower",
            "source": "program_counter", "layer": layer,
            "moves": "sigsets_per_s", "workloads": [TINY]})
    with open(path, "w") as fh:
        json.dump(m, fh)
    try:
        yield tiny_root
    finally:
        with open(path, "w") as fh:
            fh.write(original)


def test_traced_rehearsal_prints_every_timeline_metric(timeline_root):
    res = run.run_cell(timeline_root, TINY, 2**31 + 35, 2.0, True,
                       require_tpu=False)
    assert res["correct"] is True, res["compared"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for base in BASES:
        value = got[base + ".tput"]
        assert math.isfinite(value) and value >= 0.0, (base, value)
    assert 0.0 <= got["device_idle_pct.tput"] < 100.0
    # a stage's CPU seconds are inside its wall clock
    assert 0.0 < got["prevalidate_cpu_ms.tput"] <= (
        got["prevalidate_ms.tput"] * 1.02 + 0.1)
