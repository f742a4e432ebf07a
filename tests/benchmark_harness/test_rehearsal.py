"""Each kind of cell end to end on the CPU at the minimal preset, through
run.py's own functions (tiny configs under tests/benchmark_harness/tiny/):
the result object's keys, no device-named metric off a TPU, a missing chip
fails; the control (`--control equal_randomizers`) and each fault the cell
can have (half of a batch left out, an answer altered where it is
produced, a verifier that takes no notice of the verdict) make `correct`
come out false. One file: its first run compiles the tiny verify kernel
(about a minute on the CPU), the others reuse it."""

import pytest

from benchmark import run

DEVICE_METRICS = ("kernel_ms", "verify_", "device_idle")


def rehearse(root, cell, seed, seconds, trace, **kw):
    return run.run_cell(root, cell, seed, seconds, trace, require_tpu=False,
                        **kw)


def failing(res):
    return sorted(name for name, row in res["compared"].items()
                  if name != "sampled" and row["value"] > row["limit"])


@pytest.mark.parametrize("cell,seconds,trace,metric", [
    ("firehose-tiny.singles", 2.0, False, "sigsets_per_s"),
    ("firehose-tiny.aggregates", 3.0, False, "verdict_p95_ms"),
    ("firehose-tiny.singles", 2.0, True, "queue_wait_ms.tput"),
    ("firehose-tiny.aggregates", 3.0, True, "completed_sets_per_s"),
])
def test_cell_runs_end_to_end_on_the_cpu(tiny_root, cell, seconds, trace,
                                         metric):
    res = rehearse(tiny_root, cell, 2**31 + 7, seconds, trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"][metric]["value"] > 0
    if not trace:
        assert sorted(res["metrics"]) == sorted([metric, "setup_s"])
        assert res["metrics"]["setup_s"]["value"] > 0
    # off a TPU: nothing under a device metric's name, no busy time
    assert not [m for m in res["metrics"] if m.startswith(DEVICE_METRICS)]
    assert res["device"]["platform"] == "cpu"
    assert "busy_s" not in res["device"] and "breakdown" not in res
    assert failing(res) == []
    # the negative cases were really put: a whole batch refused item by
    # item through the served entry, none of it delivered
    assert res["compared"]["malformed_not_rejected"]["value"] == 0


def test_a_missing_chip_fails(tiny_root):
    with pytest.raises(SystemExit):
        run.find_devices(1, require_tpu=True)
    with pytest.raises(SystemExit):
        run.run_cell(tiny_root, "firehose-tiny.singles", 1, 1.0, False)


def test_control_equal_randomizers_is_not_correct(tiny_root, monkeypatch):
    """The control breaks the guarantee "64-bit random-linear-combination
    randomizers": with every randomizer equal, the forged pair cancels."""
    from grandine_tpu.tpu import bls as B

    # planted by run.py itself; this only has the original put back
    monkeypatch.setattr(B.TpuBlsBackend, "_rlc_pair",
                        B.TpuBlsBackend.__dict__["_rlc_pair"])
    res = rehearse(tiny_root, "firehose-tiny.singles", 11, 2.0, False,
                   control="equal_randomizers")
    assert failing(res) == ["forged_pair_accepted"]
    assert res["correct"] is False


def test_fault_answer_altered_is_not_correct(tiny_root, monkeypatch):
    """A kernel whose verdict is altered where it is produced (always
    "valid") accepts the forged pair and the signature outside G2."""
    from grandine_tpu.tpu import bls as B

    monkeypatch.setattr(B.TpuBlsBackend, "_settle",
                        lambda self, kernel, result: True)
    res = rehearse(tiny_root, "firehose-tiny.aggregates", 12, 3.0, False)
    assert failing(res) == ["forged_pair_accepted", "off_subgroup_accepted"]
    assert res["correct"] is False


def test_fault_verdict_ignored_is_not_correct(tiny_root, monkeypatch):
    """A verifier that delivers a batch whatever its verdict says: the
    batch nobody can decompress comes through the served entry."""
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    resolve = AttestationVerifier._resolve_batch
    monkeypatch.setattr(
        AttestationVerifier, "_resolve_batch",
        lambda self, prepared, ok, fl=None: resolve(self, prepared, True, fl),
    )
    res = rehearse(tiny_root, "firehose-tiny.singles", 14, 2.0, False)
    assert failing(res) == ["malformed_delivered", "malformed_not_rejected"]
    assert res["correct"] is False


def test_fault_half_of_the_batch_left_out_is_not_correct(tiny_root,
                                                         monkeypatch):
    """Planted when the window opens (the warm-up must still be answered):
    every batch delivers the verdicts of its first half only."""
    import os

    from benchmark import loader
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    driver = loader.load_driver(os.path.join(tiny_root, "benchmark"),
                                "firehose").Driver
    resolve, run_window = AttestationVerifier._resolve_batch, driver.run

    def half(self, prepared, ok, fl=None):
        return resolve(self, prepared[: len(prepared) // 2], ok, fl)

    def run_with_fault(self, seconds, trace_dir):
        monkeypatch.setattr(AttestationVerifier, "_resolve_batch", half)
        return run_window(self, seconds, trace_dir)

    monkeypatch.setattr(driver, "run", run_with_fault)
    # the driver waits a minute for an answer that is late; a test of
    # answers that never come cannot
    monkeypatch.setattr(driver, "ANSWER_TIMEOUT_S", 2.0)
    res = rehearse(tiny_root, "firehose-tiny.singles", 13, 2.0, False)
    assert res["compared"]["missing_verdicts"]["value"] > 0
    assert res["correct"] is False and res["failed"] > 0
