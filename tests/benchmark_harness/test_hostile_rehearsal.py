"""The hostile cell end to end on the CPU at the minimal preset, through
run.py's own functions (tiny tree under tests/benchmark_harness/
tiny_hostile/: batches of 4, one forged vote in each): `correct` true with
every forged vote rejected and every honest one delivered; the control
`deliver_failed_batch` makes it false through `forged_delivered`; a
program without the descent's counters is refused before anything is
warmed. One file: its first run compiles the tiny verify kernel, the
others reuse it."""

import os
import shutil

import pytest

from benchmark import loader, run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "firehose-tiny-hostile.singles-forged"
#: what a CPU run can read: every entry but the three from a device trace
FORGED = ("batch_fill_pct.forged", "host_prep_ms.forged",
          "dispatch_wait_ms_forged", "settle_wait_ms_forged",
          "slasher_feed_ms_forged", "isolation_ms.forged",
          "isolation_probes_per_batch.forged", "probe_fill_pct.forged")


@pytest.fixture(scope="module")
def hostile_root(tiny_root):
    """`tiny_root` (which builds the verifier with the tiny sizes) with
    the tiny hostile tree laid over it."""
    shutil.copytree(os.path.join(HERE, "tiny_hostile"), tiny_root,
                    dirs_exist_ok=True)
    return tiny_root


def rehearse(root, seed, seconds, trace, **kw):
    return run.run_cell(root, CELL, seed, seconds, trace, require_tpu=False,
                        **kw)


def failing(res):
    return sorted(name for name, row in res["compared"].items()
                  if name != "sampled" and row["value"] > row["limit"])


@pytest.fixture(scope="module")
def traced(hostile_root):
    # long enough for several whole batches on a CPU that other test
    # workers share: a flight record is written when its batch is done
    return rehearse(hostile_root, 2**31 + 26, 12.0, True)


def test_the_hostile_cell_is_correct_on_the_cpu(hostile_root):
    res = rehearse(hostile_root, 2**31 + 27, 3.0, False)
    assert res["correct"] is True, res["compared"]
    assert failing(res) == []
    assert res["failed"] == 0 and res["attempted"] >= 8
    assert sorted(res["metrics"]) == ["setup_s", "sigsets_per_s"]
    assert res["metrics"]["sigsets_per_s"]["value"] > 0
    compared = res["compared"]
    assert compared["sampled"] == {"value": 4, "limit": 4}
    for name in ("forged_delivered", "honest_rejected", "missing_verdicts",
                 "rejected_not_forged_submitted",
                 "isolated_not_batches_submitted", "window_compiles",
                 "other_kernel_calls", "host_path_batches",
                 "faulted_batches", "breaker_not_closed"):
        assert compared[name] == {"value": 0, "limit": 0}, name


def test_every_forged_metric_the_cpu_can_read_is_printed(traced):
    assert traced["correct"] is True, traced["compared"]
    metrics = traced["metrics"]
    assert sorted(metrics) == sorted(FORGED)
    # one forged vote in 4: both halves at each of two levels
    assert metrics["isolation_probes_per_batch.forged"]["value"] == 4.0
    # halves of 2, 2, 1, 1 in a bucket of 4
    assert metrics["probe_fill_pct.forged"]["value"] == pytest.approx(37.5)
    assert metrics["batch_fill_pct.forged"]["value"] == 100.0
    assert metrics["isolation_ms.forged"]["value"] > 0


def test_control_deliver_failed_batch_is_not_correct(hostile_root,
                                                     monkeypatch):
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    # planted by run.py itself; this only has the original put back
    monkeypatch.setattr(AttestationVerifier, "_resolve_batch",
                        AttestationVerifier.__dict__["_resolve_batch"])
    res = rehearse(hostile_root, 2**31 + 28, 3.0, False,
                   control="deliver_failed_batch")
    assert "forged_delivered" in failing(res)
    assert res["compared"]["forged_delivered"]["value"] > 0
    assert res["correct"] is False


def test_a_program_without_the_counter_is_refused_before_warm_up(
        hostile_root, monkeypatch):
    """The parent commit's program: no `attestation_isolation_probes_total`
    in its exposition. The driver must raise at once: no node is built,
    nothing enters the compile scope."""
    from grandine_tpu import metrics as metrics_mod
    from grandine_tpu.tpu import compile_scope

    init = metrics_mod.Metrics.__init__

    def without(self):
        init(self)
        del self.att_isolation_probes

    monkeypatch.setattr(metrics_mod.Metrics, "__init__", without)
    module = loader.load_driver(os.path.join(hostile_root, "benchmark"),
                                "firehose_hostile")
    before = compile_scope.totals()
    with pytest.raises(module.Refused, match="isolation_probes"):
        rehearse(hostile_root, 5, 1.0, False)
    assert compile_scope.totals() == before
