"""The mixed cell end to end on the CPU at the minimal preset, through
run.py's own functions (tiny tree under tests/benchmark_harness/
tiny_mixed/: 168 validators, so a committee has 5-6 members and an
aggregate runs in member bucket 8 beside the votes' 4; batches of at most
4; a slot's 21 votes each due at a time of its own inside a phase of half
a second and its 8 aggregates together where the phase ends): `correct`
true with every batch of the window in the ONE width bucket and a batch
of both kinds really formed; every metric a CPU can read printed; the
control `narrow_votes` makes it false through
`other_width_bucket_batches`; a program whose verifier has no
`width_floor` is refused before anything is built or warmed. Then the
readers this cell brings, on hand-built runs, and the manifest's entries.
One file: its first run compiles the tiny wide kernel, the others reuse
it."""

import json
import os
import shutil

import pytest

from benchmark import loader, observe, run

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmark")
CELL = "firehose-tiny-mixed.slot-thirds"
REAL = "firehose-50k-mixed.slot-thirds"
#: what a CPU run can read: every entry but the three from a device trace
MIXED = ("gen_late_ms.mixed", "queue_wait_ms.mixed", "batch_fill_pct.mixed",
         "collect_wait_ms_mixed", "dispatch_wait_ms_mixed", "width_fill_pct",
         "mixed_batch_pct", "host_prep_ms.mixed", "feedback_ms.mixed",
         "completed_sets_per_s.mixed", "votes_p95_ms", "aggregates_p95_ms")
DEVICE = ("kernel_ms.mixed", "verify_64x256_roofline.mixed",
          "device_idle_est_pct.mixed")
KINDS = ("other_width_bucket_batches", "mixed_batches_missing",
         "mixed_valid_refused", "mixed_forged_accepted",
         "mixed_sample_missing", "mixed_verdict_mismatch")
#: a slot's 21 votes and 8 aggregates, five window slots of 1.5 s: three
#: would do but for the batch bound of 4, which lets a slot's last votes
#: and first aggregates fall on a batch boundary one time in four
ITEMS, SECONDS = 5 * (21 + 8), 7.5


@pytest.fixture(scope="module")
def mixed_root(tiny_root):
    """`tiny_root` (which builds the verifier with the tiny sizes) with
    the tiny mixed tree laid over it."""
    shutil.copytree(os.path.join(HERE, "tiny_mixed"), tiny_root,
                    dirs_exist_ok=True)
    return tiny_root


def rehearse(root, seed, seconds, trace, **kw):
    return run.run_cell(root, CELL, seed, seconds, trace, require_tpu=False,
                        **kw)


def failing(res):
    return sorted(name for name, row in res["compared"].items()
                  if name != "sampled" and row["value"] > row["limit"])


def test_the_mixed_cell_is_correct_on_the_cpu(mixed_root):
    res = rehearse(mixed_root, 2**31 + 32, SECONDS, False)
    assert res["correct"] is True, res["compared"]
    assert failing(res) == []
    # every slot's 21 votes and 8 aggregates, each sent once
    assert res["failed"] == 0 and res["attempted"] == ITEMS
    assert sorted(res["metrics"]) == ["setup_s", "verdict_p95_ms"]
    assert 0 < res["metrics"]["verdict_p95_ms"]["value"] < 1e9
    compared = res["compared"]
    assert compared["sampled"] == {"value": 4, "limit": 4}
    # the paced driver's 23 counts and this cell's six
    assert len(compared) == 23 + len(KINDS)
    for name in KINDS + ("missing_verdicts", "window_compiles",
                         "other_kernel_calls", "other_bucket_batches",
                         "padded_batches_missing", "host_path_batches",
                         "rejected_valid", "unmatched_verdicts"):
        assert compared[name] == {"value": 0, "limit": 0}, name


def test_every_mixed_metric_the_cpu_can_read_is_printed(mixed_root):
    res = rehearse(mixed_root, 2**31 + 33, SECONDS, True)
    assert res["correct"] is True, res["compared"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert sorted(metrics) == sorted(MIXED)
    assert 0 < metrics["batch_fill_pct.mixed"] <= 100.0
    # a vote fills one member slot in 8, an aggregate 4-6: between the two
    assert 100.0 / 8 / 4 < metrics["width_fill_pct"] < 100.0 * 6 / 8
    assert 0 < metrics["mixed_batch_pct"] < 100.0
    assert 0 < metrics["votes_p95_ms"] < 1e9
    assert 0 < metrics["aggregates_p95_ms"] < 1e9
    assert metrics["queue_wait_ms.mixed"] >= metrics["collect_wait_ms_mixed"]
    assert metrics["dispatch_wait_ms_mixed"] >= 0
    assert metrics["completed_sets_per_s.mixed"] > 0
    assert metrics["gen_late_ms.mixed"] >= 0
    assert metrics["host_prep_ms.mixed"] > 0
    assert metrics["feedback_ms.mixed"] > 0
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_control_narrow_votes_is_not_correct(mixed_root, monkeypatch):
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    driver = loader.load_driver(os.path.join(mixed_root, "benchmark"),
                                "firehose_mixed").Driver
    # planted by the driver itself when the window opens; this only has
    # the originals put back
    monkeypatch.setattr(
        AttestationVerifier, "_raise_width_floor",
        AttestationVerifier.__dict__["_raise_width_floor"])
    monkeypatch.setattr(driver, "forget_floor", False)
    res = rehearse(mixed_root, 2**31 + 34, SECONDS, False,
                   control="narrow_votes")
    assert "other_width_bucket_batches" in failing(res)
    # the votes' own bucket is another shape: a first call inside the
    # window
    assert "window_compiles" in failing(res)
    assert res["correct"] is False


def test_a_program_without_width_floor_is_refused_before_warm_up(
        mixed_root, monkeypatch):
    """The parent commit's program: its verifier has no `width_floor`, and
    a batch of votes after the aggregates is another executable. The
    driver must raise at once: no node is built, nothing enters the
    compile scope."""
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier
    from grandine_tpu.tpu import compile_scope

    monkeypatch.delattr(AttestationVerifier, "width_floor")
    module = loader.load_driver(os.path.join(mixed_root, "benchmark"),
                                "firehose_mixed")
    said = []
    driver = module.Driver(loader.load_cell(mixed_root, CELL), 5,
                           lambda **row: said.append(row))
    before = compile_scope.totals()
    with pytest.raises(module.Refused, match="width_floor"):
        driver.setup()
    assert compile_scope.totals() == before
    assert driver.node is None and driver.pool is None
    assert [row["phase"] for row in said] == ["refused"]
    with pytest.raises(module.Refused, match="width_floor"):
        rehearse(mixed_root, 5, 1.0, False)


# -- the readers this cell brings ------------------------------------------

def exposition(members, slots, mixed=None):
    lines = [f"attestation_first_pass_members_total {float(members)}",
             f"attestation_first_pass_member_slots_total {float(slots)}"]
    if mixed is not None:
        lines.append(f"attestation_mixed_batches_total {float(mixed)}")
    return observe.parse_exposition("\n".join(lines))


BATCHES = [{"kind": "batch"}] * 4 + [{"kind": "breaker"}]


@pytest.mark.parametrize("before,after,want", [
    ((0, 0), (64, 64 * 256), 100.0 / 256),         # a full batch of votes
    ((0, 0), (64 * 130, 64 * 256), 100.0 * 130 / 256),   # of aggregates
    ((8320, 16384), (8320 + 47, 2 * 16384), 100.0 * 47 / 16384),
    ((5, 16384), (5, 16384), None),    # the series there, no first pass
])
def test_width_fill_pct_on_a_recorded_exposition(before, after, want):
    seen = {"before": exposition(*before), "after": exposition(*after),
            "flight": BATCHES}
    got = loader.load_reader(BENCH, "width_fill_pct").read(seen)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("before,after,want", [
    (0, 1, 25.0), (3, 3, 0.0), (1, 5, 100.0),
])
def test_mixed_batch_pct_on_a_recorded_exposition(before, after, want):
    seen = {"before": exposition(0, 0, before),
            "after": exposition(9, 99, after), "flight": BATCHES}
    got = loader.load_reader(BENCH, "mixed_batch_pct").read(seen)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", ["votes_p95_ms", "aggregates_p95_ms"])
def test_the_split_tail_is_read_from_what_the_window_saw(name):
    reader = loader.load_reader(BENCH, name)
    assert reader.read({"seen": {name: 512.5, "verdict_p95_ms": 1.0}}) == 512.5
    # a driver that does not tell the kinds apart: nothing, no raise
    assert reader.read({"seen": {"verdict_p95_ms": 1.0}}) is None


@pytest.mark.parametrize("name,want", [
    ("collect_wait_ms_mixed", 55.0),
    ("dispatch_wait_ms_mixed", 1300.0),
])
def test_own_base_waits_read_what_their_clean_siblings_read(name, want):
    rows = [{"kind": "batch", "collect_wait_s": c, "dispatch_wait_s": d}
            for c, d in ((0.05, 0.0), (0.055, 1.3), (0.12, 1.5))]
    seen = {"flight": rows + [{"kind": "breaker"}]}
    got = loader.load_reader(BENCH, name).read(seen)
    assert got == pytest.approx(want)
    sibling = loader.load_reader(BENCH, name[: -len("_mixed")] + ".tput")
    assert got == sibling.read(seen)


@pytest.mark.parametrize("name", ["width_fill_pct", "mixed_batch_pct",
                                  "collect_wait_ms_mixed",
                                  "dispatch_wait_ms_mixed"])
def test_reader_finds_nothing_in_a_program_without_the_series(name):
    """As the parent commit exposes it: no member counters, no mixed
    counter; and a run with no batch at all. None, and no raise: the
    result line leaves the metric out."""
    text = ("attestation_verifier_batches_total 7.0\n"
            "attestation_first_pass_items_total 448.0\n"
            "attestation_first_pass_slots_total 448.0")
    old = observe.parse_exposition(text)
    reader = loader.load_reader(BENCH, name)
    assert reader.read({"before": {}, "after": old,
                        "flight": [{"kind": "batch", "items": 64,
                                    "bucket": 64}]}) is None
    assert reader.read({"before": {}, "after": {}, "flight": []}) is None


def test_the_manifest_has_the_mixed_entries_with_their_readers():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    mixed = [r for r in m["per_layer"] if r.get("workloads") == [REAL]]
    assert sorted(r["name"] for r in mixed) == sorted(MIXED + DEVICE)
    # appended behind PR 30's, in one run (not held to be the LAST of the
    # list: the next cell appends behind them, as the contract asks)
    at = [i for i, r in enumerate(m["per_layer"]) if r in mixed]
    assert at == list(range(at[0], at[0] + len(mixed)))
    assert m["per_layer"][at[0] - 1]["name"] == "gen_late_ms.paced"
    for row in mixed:
        module = loader.load_reader(BENCH, row["name"])
        assert (module.UNIT, module.LAYER) == (row["unit"], row["layer"])
        assert row["moves"] == "verdict_p95_ms"
    # a borrowed reader is the clean cells' own file, a new one its own
    for name in ("gen_late_ms", "batch_fill_pct", "queue_wait_ms",
                 "host_prep_ms", "feedback_ms", "completed_sets_per_s",
                 "kernel_ms", "verify_64x256_roofline",
                 "device_idle_est_pct"):
        assert loader.load_reader(BENCH, name + ".mixed") is (
            loader.load_reader(BENCH, name + ".lat"))
    for name in ("width_fill_pct", "mixed_batch_pct", "votes_p95_ms",
                 "aggregates_p95_ms", "collect_wait_ms_mixed",
                 "dispatch_wait_ms_mixed"):
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    (tail,) = [r for r in m["end_to_end"] if r["name"] == "verdict_p95_ms"]
    assert tail["workloads"][:3] == [
        "firehose-50k.aggregates-slot", "firehose-50k-gossip.subnets-paced",
        REAL]
    assert [c["name"] for c in m["configs"]][:4] == [
        "firehose-50k", "firehose-50k-hostile", "firehose-50k-gossip",
        "firehose-50k-mixed"]
    assert [w["name"] for w in m["workloads"]][4] == REAL
    cell = loader.load_cell(REPO, REAL)
    assert cell["config"]["driver"] == "firehose_mixed" and cell["chips"] == 1
    assert [x["name"] for x in cell["end_to_end"]] == ["verdict_p95_ms",
                                                       "setup_s"]
    assert [x["name"] for x in cell["per_layer"]] == [
        r["name"] for r in mixed]
    gossip = loader.load_cell(REPO, "firehose-50k-gossip.subnets-paced")
    assert cell["config"]["shapes"] == gossip["config"]["shapes"]
    assert cell["config"]["reduced"] == []
    assert len(cell["config"]["source"]) <= 200
    assert cell["config"]["source"] != gossip["config"]["source"]
    # the gossip configuration's seven lines (the sixth in PR 31's
    # wording) and two of its own
    ours, theirs = (cell["config"]["guarantees"],
                    gossip["config"]["guarantees"])
    assert len(ours) == len(theirs) + 2 == 9
    assert [a == b for a, b in zip(ours, theirs)] == [
        True] * 5 + [False, True]
    assert ours[5].endswith("as soon as the pipeline has a slot for it")
    assert "ONE executable, 64 x 256" in ours[7]
    assert (cell["kernel"], cell["width_bucket"]) == (
        "agg_fast_verify_msm_idx", 256)
    aggregates = loader.load_cell(REPO, "firehose-50k.aggregates-slot")
    traffic = cell["traffic"]
    for key in ("members", "aggregators_per_committee",
                "missing_members_max", "slot_seconds", "tick_lead_s"):
        assert traffic[key] == aggregates["traffic"][key], key
    for key in ("pacing", "phase_start_s", "phase_seconds", "tick_max_s",
                "first_slot", "slots", "slot_seconds", "tick_lead_s"):
        assert traffic[key] == gossip["traffic"][key], key
    assert traffic["votes"] == {"members": gossip["traffic"]["members"]}
    assert traffic["aggregates_due_s"] == (
        traffic["phase_start_s"] + traffic["phase_seconds"]) == 8.0
