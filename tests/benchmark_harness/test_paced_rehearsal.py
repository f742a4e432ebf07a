"""The gossip cell end to end on the CPU at the minimal preset, through
run.py's own functions (tiny tree under tests/benchmark_harness/
tiny_paced/: batches of at most 4, a slot's 8 votes each due at a time of
its own inside a phase of half a second): `correct` true with partial
batches really formed and every one of them in the one bucket; every
metric a CPU can read printed; the control `drop_partial_batches` makes it
false through `missing_verdicts`; a program whose verifier has no
`batch_bucket` is refused before anything is built or warmed. Then the
three readers this cell brings, on hand-built runs, and the manifest's
entries. One file: its first run compiles the tiny verify kernel, the
others reuse it."""

import json
import os
import shutil

import pytest

from benchmark import loader, observe, run

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "benchmark")
CELL = "firehose-tiny-gossip.subnets-paced"
REAL = "firehose-50k-gossip.subnets-paced"
#: what a CPU run can read: every entry but the three from a device trace
PACED = ("batch_fill_pct.paced", "deadline_closed_pct",
         "queue_wait_ms.paced", "collect_wait_ms_paced",
         "dispatch_wait_ms_paced", "host_prep_ms.paced",
         "feedback_ms.paced", "completed_sets_per_s.paced",
         "gen_late_ms.paced")
DEVICE = ("kernel_ms.paced", "verify_64x4_roofline.paced",
          "device_idle_est_pct.paced")
PADDING = ("deadline_closed_sample_missing",
           "deadline_closed_verdict_mismatch", "padded_batches_missing",
           "other_bucket_batches", "closed_by_disagrees",
           "padded_valid_refused", "padded_forged_accepted")


@pytest.fixture(scope="module")
def paced_root(tiny_root):
    """`tiny_root` (which builds the verifier with the tiny sizes) with
    the tiny gossip tree laid over it."""
    shutil.copytree(os.path.join(HERE, "tiny_paced"), tiny_root,
                    dirs_exist_ok=True)
    return tiny_root


def rehearse(root, seed, seconds, trace, **kw):
    return run.run_cell(root, CELL, seed, seconds, trace, require_tpu=False,
                        **kw)


def failing(res):
    return sorted(name for name, row in res["compared"].items()
                  if name != "sampled" and row["value"] > row["limit"])


def test_the_gossip_cell_is_correct_on_the_cpu(paced_root):
    res = rehearse(paced_root, 2**31 + 30, 4.5, False)
    assert res["correct"] is True, res["compared"]
    assert failing(res) == []
    # three slots of 8 votes, each sent once
    assert res["failed"] == 0 and res["attempted"] == 24
    assert sorted(res["metrics"]) == ["setup_s", "verdict_p95_ms"]
    assert 0 < res["metrics"]["verdict_p95_ms"]["value"] < 1e9
    compared = res["compared"]
    assert compared["sampled"] == {"value": 4, "limit": 4}
    for name in PADDING + ("missing_verdicts", "window_compiles",
                           "other_kernel_calls", "host_path_batches",
                           "rejected_valid", "unmatched_verdicts"):
        assert compared[name] == {"value": 0, "limit": 0}, name


def test_every_paced_metric_the_cpu_can_read_is_printed(paced_root):
    res = rehearse(paced_root, 2**31 + 31, 4.5, True)
    assert res["correct"] is True, res["compared"]
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert sorted(metrics) == sorted(PACED)
    # batches closed by the deadline, short of the bound of 4, each in the
    # one bucket: the fill is under 100 %
    assert 0 < metrics["batch_fill_pct.paced"] < 100.0
    assert 0 < metrics["deadline_closed_pct"] <= 100.0
    assert metrics["collect_wait_ms_paced"] > 0
    assert metrics["queue_wait_ms.paced"] >= metrics["collect_wait_ms_paced"]
    assert metrics["dispatch_wait_ms_paced"] >= 0
    assert metrics["completed_sets_per_s.paced"] > 0
    assert metrics["gen_late_ms.paced"] >= 0
    assert "busy_s" not in res["device"] and "breakdown" not in res


def test_control_drop_partial_batches_is_not_correct(paced_root,
                                                     monkeypatch):
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    driver = loader.load_driver(os.path.join(paced_root, "benchmark"),
                                "firehose_paced").Driver
    # planted by the driver itself when the window opens; this only has
    # the originals put back
    monkeypatch.setattr(
        AttestationVerifier, "_verify_batch_traced",
        AttestationVerifier.__dict__["_verify_batch_traced"])
    monkeypatch.setattr(driver, "drop_partial", False)
    # the driver waits a minute for an answer that is late; a test of
    # answers that never come cannot
    monkeypatch.setattr(driver, "ANSWER_TIMEOUT_S", 2.0)
    res = rehearse(paced_root, 2**31 + 32, 4.5, False,
                   control="drop_partial_batches")
    assert "missing_verdicts" in failing(res)
    assert res["compared"]["missing_verdicts"]["value"] > 0
    assert res["correct"] is False and res["failed"] > 0


def test_a_program_without_batch_bucket_is_refused_before_warm_up(
        paced_root, monkeypatch):
    """The parent commit's program: its verifier has no `batch_bucket`,
    and a partial batch would compile an executable of its own inside the
    window. The driver must raise at once: no node is built, nothing
    enters the compile scope."""
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier
    from grandine_tpu.tpu import compile_scope

    monkeypatch.delattr(AttestationVerifier, "batch_bucket")
    module = loader.load_driver(os.path.join(paced_root, "benchmark"),
                                "firehose_paced")
    said = []
    driver = module.Driver(loader.load_cell(paced_root, CELL), 5,
                           lambda **row: said.append(row))
    before = compile_scope.totals()
    with pytest.raises(module.Refused, match="batch_bucket"):
        driver.setup()
    assert compile_scope.totals() == before
    assert driver.node is None and driver.pool is None
    assert [row["phase"] for row in said] == ["refused"]
    with pytest.raises(module.Refused, match="batch_bucket"):
        rehearse(paced_root, 5, 1.0, False)


# -- the readers this cell brings ------------------------------------------

CLOSED = 'attestation_batches_closed_total{by="%s"} %s'


def exposition(full, deadline, stop=None):
    lines = [CLOSED % ("full", float(full)),
             CLOSED % ("deadline", float(deadline))]
    if stop is not None:
        lines.append(CLOSED % ("stop", float(stop)))
    return observe.parse_exposition("\n".join(lines))


@pytest.mark.parametrize("before,after,want", [
    ((0, 0), (10, 30), 75.0),
    ((2, 1), (2, 11), 100.0),          # the warm-up's are not the window's
    ((2, 1), (12, 1), 0.0),            # every window batch left full
    ((0, 0, 0), (6, 3, 1), 30.0),      # all `by` are the base
])
def test_deadline_closed_pct_on_a_recorded_exposition(before, after, want):
    seen = {"before": exposition(*before), "after": exposition(*after)}
    reader = loader.load_reader(BENCH, "deadline_closed_pct")
    assert reader.read(seen) == pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("collect_wait_ms_paced", 55.0),
    ("dispatch_wait_ms_paced", 1300.0),
])
def test_own_base_waits_read_what_their_clean_siblings_read(name, want):
    rows = [{"kind": "batch", "collect_wait_s": c, "dispatch_wait_s": d}
            for c, d in ((0.05, 0.0), (0.055, 1.3), (0.12, 1.5))]
    seen = {"flight": rows + [{"kind": "breaker"}]}
    got = loader.load_reader(BENCH, name).read(seen)
    assert got == pytest.approx(want)
    sibling = loader.load_reader(BENCH, name[: -len("_paced")] + ".tput")
    assert got == sibling.read(seen)


@pytest.mark.parametrize("name", ["deadline_closed_pct",
                                  "collect_wait_ms_paced",
                                  "dispatch_wait_ms_paced"])
def test_reader_finds_nothing_in_a_program_without_the_series(name):
    """As the parent commit exposes it: no `attestation_batches_closed_
    total`; and a run with no batch at all. None, and no raise: the
    result line leaves the metric out."""
    text = "attestation_verifier_batches_total 7.0"
    old = observe.parse_exposition(text)
    reader = loader.load_reader(BENCH, name)
    assert reader.read({"before": {}, "after": old,
                        "flight": [{"kind": "batch", "items": 64,
                                    "bucket": 64}]}) is None
    assert reader.read({"before": {}, "after": {}, "flight": []}) is None
    # the counter there, and no batch closed in the window
    idle = exposition(5, 9)
    if name == "deadline_closed_pct":
        assert reader.read({"before": idle, "after": idle,
                            "flight": []}) is None


def test_the_manifest_has_the_paced_entries_with_their_readers():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    paced = [r for r in m["per_layer"] if r.get("workloads") == [REAL]]
    assert sorted(r["name"] for r in paced) == sorted(PACED + DEVICE)
    # appended: every earlier entry where it was
    assert m["per_layer"][-len(paced):] == paced
    assert not [r for r in paced if "forged" in r["name"]]
    for row in paced:
        module = loader.load_reader(BENCH, row["name"])
        assert (module.UNIT, module.LAYER) == (row["unit"], row["layer"])
        assert row["moves"] == "verdict_p95_ms"
    # a borrowed reader is the clean cells' own file, a new one its own
    for name in ("batch_fill_pct", "queue_wait_ms", "host_prep_ms",
                 "feedback_ms", "kernel_ms", "device_idle_est_pct"):
        assert loader.load_reader(BENCH, name + ".paced") is (
            loader.load_reader(BENCH, name + ".tput"))
    for name in ("deadline_closed_pct", "collect_wait_ms_paced",
                 "dispatch_wait_ms_paced"):
        assert os.path.exists(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    (tail,) = [r for r in m["end_to_end"] if r["name"] == "verdict_p95_ms"]
    assert tail["workloads"] == ["firehose-50k.aggregates-slot", REAL]
    cell = loader.load_cell(REPO, REAL)
    assert cell["config"]["driver"] == "firehose_paced" and cell["chips"] == 1
    assert [x["name"] for x in cell["end_to_end"]] == ["verdict_p95_ms",
                                                       "setup_s"]
    assert [x["name"] for x in cell["per_layer"]] == [
        r["name"] for r in paced]
    clean = loader.load_cell(REPO, "firehose-50k.singles-backlog")
    assert cell["config"]["shapes"] == clean["config"]["shapes"]
    assert cell["config"]["reduced"] == []
    assert cell["config"]["guarantees"][: len(
        clean["config"]["guarantees"])] == clean["config"]["guarantees"]
    assert cell["kernel"] == clean["kernel"]
    assert cell["traffic"]["pacing"] == "slot_phase"
    assert (cell["traffic"]["phase_start_s"],
            cell["traffic"]["phase_seconds"],
            cell["traffic"]["slot_seconds"],
            cell["traffic"]["slots"]) == (4.0, 4.0, 12.0, 3)
    assert cell["traffic"]["tick_max_s"] <= 0.005
