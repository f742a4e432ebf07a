"""The three readers this cell brings (benchmark/layer_metrics/
isolation_ms.py, isolation_probes_per_batch.py, probe_fill_pct.py) on
recorded expositions and flight rows, with and without the program's
descent counters (a program from before them: None, no raise); and the
manifest's entries for the hostile cell with their readers: the clean
cells' own files where the name falls back to them, a file of their own
for the three whose base name test_span_readers.py pins to those cells."""

import json
import os

import pytest

from benchmark import loader, observe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "firehose-50k-hostile.singles-forged"
NEW = ("isolation_ms", "isolation_probes_per_batch", "probe_fill_pct")
BORROWED = ("batch_fill_pct", "host_prep_ms", "kernel_ms",
            "verify_64x4_roofline", "device_idle_est_pct")
#: readings the clean cells have too, whose base names
#: test_span_readers.py pins to those cells: a base of their own here
OWN_BASE = ("dispatch_wait_ms_forged", "settle_wait_ms_forged",
            "slasher_feed_ms_forged")
ORDER = (["batch_fill_pct.forged", "host_prep_ms.forged"] + list(OWN_BASE)
         + ["kernel_ms.forged", "verify_64x4_roofline.forged",
            "device_idle_est_pct.forged"] + [n + ".forged" for n in NEW])

RECORDED = """\
# HELP attestation_verifier_fallbacks_total batches degraded to singular verification
# TYPE attestation_verifier_fallbacks_total counter
attestation_verifier_fallbacks_total {isolated}
attestation_isolation_probes_total {probes}
attestation_isolation_probe_items_total {items}
attestation_isolation_probe_slots_total {slots}
attestation_isolated_batches_total {isolated}
verify_stage_seconds_sum{{lane="attestation",op="",stage="fallback"}} {fallback_s}
verify_stage_seconds_sum{{lane="attestation",op="prevalidate",stage="host_prep"}} 99.0
"""


def exposition(isolated, fallback_s):
    probes = 12 * isolated
    return observe.parse_exposition(RECORDED.format(
        isolated=float(isolated), probes=float(probes),
        items=float(126 * isolated), slots=float(64 * probes),
        fallback_s=fallback_s))


def rows(probes):
    return ([{"kind": "batch", "items": 64, "bucket": 64, "probes": p}
             for p in probes] + [{"kind": "breaker"}])


def reader(name):
    return loader.load_reader(BENCH, name + ".forged")


def test_the_manifest_has_the_forged_entries_with_their_readers():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    forged = [r for r in m["per_layer"] if "forged" in r["name"]]
    assert [r["name"] for r in forged] == ORDER
    # appended, every earlier entry where it was
    assert m["per_layer"][-len(forged):] == forged
    for row in forged:
        module = loader.load_reader(BENCH, row["name"])
        assert (module.UNIT, module.LAYER) == (row["unit"], row["layer"])
        assert row["moves"] == "sigsets_per_s"
        assert row["workloads"] == [CELL]
    # a borrowed reader is the clean cells' own file, a new one its own
    for name in BORROWED:
        assert reader(name) is loader.load_reader(BENCH, name + ".tput")
    for name in NEW + OWN_BASE:
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    (rate,) = [r for r in m["end_to_end"] if r["name"] == "sigsets_per_s"]
    assert rate["workloads"] == ["firehose-50k.singles-backlog", CELL]
    cell = loader.load_cell(REPO, CELL)
    assert cell["config"]["driver"] == "firehose_hostile"
    assert [x["name"] for x in cell["end_to_end"]] == ["sigsets_per_s",
                                                       "setup_s"]
    assert [x["name"] for x in cell["per_layer"]] == [
        r["name"] for r in forged]
    clean = loader.load_cell(REPO, "firehose-50k.singles-backlog")
    assert cell["config"]["shapes"] == clean["config"]["shapes"]
    assert not [x for x in clean["per_layer"] if "forged" in x["name"]]


def test_own_base_readers_read_what_their_clean_siblings_read():
    flight = [{"kind": "batch", "dispatch_wait_s": d, "settle_wait_s": w}
              for d, w in ((5.0, 1.5), (6.0, 1.7), (5.5, 2.1))]
    sums = 'verify_stage_seconds_sum{lane="attestation",op="slasher_feed",' \
           'stage="feedback"} %s'
    seen = {"before": observe.parse_exposition(sums % 1.0),
            "after": observe.parse_exposition(sums % 1.6), "flight": flight}
    for own in OWN_BASE:
        sibling = loader.load_reader(
            BENCH, own[: -len("_forged")] + ".tput")
        got = loader.load_reader(BENCH, own).read(seen)
        assert got == sibling.read(seen) and got > 0
        assert loader.load_reader(BENCH, own).read(
            {"before": {}, "after": {}, "flight": []}) is None


@pytest.mark.parametrize("before,after,want", [
    ((0, 0.0), (15, 33.0), 2200.0),     # 15 descents, 2.2 s each
    ((2, 4.0), (3, 6.5), 2500.0),       # the warm-up's are not the window's
])
def test_isolation_ms_on_a_recorded_exposition(before, after, want):
    seen = {"before": exposition(*before), "after": exposition(*after)}
    assert reader("isolation_ms").read(seen) == pytest.approx(want)


@pytest.mark.parametrize("probes,want", [
    ([12, 12, 12], 12.0),
    ([12, 0, 13], 12.5),       # a batch that passed made no descent
    ([4], 4.0),
])
def test_probes_per_batch_on_flight_rows(probes, want):
    got = reader("isolation_probes_per_batch").read({"flight": rows(probes)})
    assert got == want


def test_probe_fill_on_a_recorded_exposition():
    seen = {"before": exposition(2, 4.0), "after": exposition(17, 37.0)}
    # 126 real items in 12 x 64 slots
    assert reader("probe_fill_pct").read(seen) == pytest.approx(
        100.0 * 126 / 768)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_from_before_the_counters(name):
    """As the parent commit exposes it: the fallback stage and counter,
    no descent counters, no `probes` in the flight row. None, and no
    raise: the result line leaves the metric out."""
    text = "\n".join(
        line for line in RECORDED.format(
            isolated=3.0, probes=0.0, items=0.0, slots=0.0, fallback_s=1.0
        ).splitlines() if "isolat" not in line)
    old = observe.parse_exposition(text)
    flight = [{"kind": "batch", "items": 64, "bucket": 64}]
    module = reader(name)
    assert module.read({"before": {}, "after": old, "flight": flight}) is None
    assert module.read({"before": {}, "after": {}, "flight": []}) is None
    # the counters there, and no descent in the window
    idle = exposition(5, 9.0)
    assert module.read({"before": idle, "after": idle,
                        "flight": rows([0, 0])}) is None
