"""Single-pass fused verify tests (PR 12).

Three layers, cheapest first:

- Scheduler-contract tests with fake backends (no compiles): a fused
  backend gets exactly ONE device dispatch per batch (no separate
  subgroup pass), flight records carry the fused kernel label, and
  cross-lane merged batches keep per-lane verdict slices and flight
  attribution.
- Kernel differential witness (bucket-4 multi_verify family): the fused
  verdict equals the two-pass verdict (unfused RLC check AND the
  standalone ψ-ladder subgroup pass) over valid / forged / non-subgroup
  specimens, and the fused path's dispatch counters show one kernel
  call and zero subgroup calls.
- Slow tier: the same differential over the aggregate and rlc_partition
  kernel families, and an end-to-end autotune sweep cell.

The donation-aliasing regression runs the two-deep async pipeline with
`donate_buffers=True`: on CPU XLA declines the donation (warning only),
so the test pins the CONTRACT — two in-flight donated batches settle to
independent, correct verdicts — and becomes a true aliasing probe on
device backends where donation is real.
"""

import random
import threading
import time
import warnings

import numpy as np
import pytest

from grandine_tpu.crypto import bls as A
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime import verify_scheduler as vs
from grandine_tpu.runtime.thread_pool import Priority
from grandine_tpu.runtime.verify_scheduler import (
    LaneConfig,
    VerifyItem,
    VerifyScheduler,
)

rng = random.Random(0xF05ED)


def _rng_bytes(n: int) -> bytes:
    return bytes(rng.randrange(256) for _ in range(n))


def _nonsubgroup_sig(tag: bytes) -> "A.Signature":
    """An on-curve G2 point OUTSIDE the prime-order subgroup: passes
    decompression-style curve checks, must fail membership."""
    from grandine_tpu.crypto.hash_to_curve import (
        hash_to_field_fq2,
        map_to_curve_g2,
    )

    pt = map_to_curve_g2(hash_to_field_fq2(tag, b"SGT", 1)[0])
    assert not pt.in_subgroup_slow()
    return A.Signature(pt)


# --------------------------------------------- scheduler fused contract


class _CountingBackend:
    """Async-seam double that records every device dispatch so tests can
    assert the fused path's one-dispatch-per-batch invariant."""

    def __init__(self, truth=None, fused=True):
        self.truth = dict(truth or {})
        if fused:
            self.fuse_subgroup = True
        self.verify_batches: "list[int]" = []
        self.subgroup_batches: "list[int]" = []

    def g2_subgroup_check_batch_async(self, points):
        self.subgroup_batches.append(len(points))
        out = np.ones(len(points), dtype=bool)
        return lambda: out

    def fast_aggregate_verify_batch_async(self, messages, signatures, keys):
        self.verify_batches.append(len(messages))
        ok = all(self.truth.get(bytes(m), True) for m in messages)
        return lambda: ok


def _interop_key():
    return A.SecretKey.from_bytes(bytes(31) + bytes([1]))


def _real_items(n, valid=True, tag=b"fused"):
    sk = _interop_key()
    items = []
    for i in range(n):
        msg = b"%s-%d" % (tag, i)
        signed = msg if valid else b"other-" + msg
        items.append(VerifyItem(
            msg, sk.sign(signed).to_bytes(),
            public_keys=(sk.public_key(),),
        ))
    return items


def test_fused_backend_one_dispatch_no_subgroup_pass():
    """A fused backend's batch makes exactly one device dispatch: the
    scheduler must NOT stack the separate subgroup ladder, and the
    flight record carries the fused kernel label."""
    backend = _CountingBackend(fused=True)
    m = Metrics()
    lanes = (LaneConfig("sync_message", Priority.LOW, 128, 0.05, 100, True),)
    s = VerifyScheduler(
        backend=backend, lanes=lanes, use_device=True, metrics=m
    )
    try:
        items = _real_items(2)
        assert s.submit("sync_message", items).result(30.0) is True
        assert backend.verify_batches == [2]
        assert backend.subgroup_batches == []  # fused: membership in-kernel
        recs = s.flight.snapshot(lane="sync_message")
        assert len(recs) == 1
        assert recs[0].kernel == "fast_aggregate_fused"
        assert recs[0].verdict is True and recs[0].items == 2
    finally:
        s.stop()


def test_unfused_backend_keeps_two_pass():
    """No fuse_subgroup attr → the legacy two-pass pipeline, byte for
    byte: subgroup ladder stacked ahead of the verify dispatch."""
    backend = _CountingBackend(fused=False)
    lanes = (LaneConfig("sync_message", Priority.LOW, 128, 0.05, 100, True),)
    s = VerifyScheduler(backend=backend, lanes=lanes, use_device=True)
    try:
        items = _real_items(2)
        assert s.submit("sync_message", items).result(30.0) is True
        assert backend.verify_batches == [2]
        assert backend.subgroup_batches == [2]
        recs = s.flight.snapshot(lane="sync_message")
        assert recs and recs[0].kernel == "fast_aggregate"
    finally:
        s.stop()


@pytest.fixture(scope="module")
def soak_items():
    return _real_items(4, tag=b"soak-good"), _real_items(
        2, valid=False, tag=b"soak-bad"
    )


@pytest.mark.parametrize(
    "lane", [ln.name for ln in vs.DEFAULT_LANES if ln.scheme == "bls"]
)
def test_fused_lane_soak_no_subgroup_dispatch_no_recompile(
    lane, soak_items, monkeypatch
):
    """Every BLS lane of the node's own lane table, through the fused
    path with the shape ledger sealed: valid and forged jobs settle to
    their exact verdicts with ZERO standalone subgroup dispatches, zero
    post-warm-up recompiles and nothing but the fused kernel label on
    the flight timeline."""
    from grandine_tpu.runtime.flight import BATCH
    from grandine_tpu.tpu import bls as B

    good, bad = soak_items
    truth = {it.message: True for it in good}
    truth.update({it.message: False for it in bad})
    monkeypatch.setattr(vs, "host_check_item", lambda it: truth[it.message])
    backend = _CountingBackend(fused=True, truth=truth)
    B.reset_shape_tracking()
    B.declare_warmup_complete()
    s = VerifyScheduler(backend=backend, use_device=True, metrics=Metrics())
    try:
        t_good = [s.submit(lane, good[i:i + 2]) for i in (0, 2)]
        t_bad = s.submit(lane, bad)
        s.flush(60.0)
        assert all(t.done() and t.ok for t in t_good)
        assert t_bad.done() and t_bad.ok is False and not t_bad.dropped
        assert backend.verify_batches  # the device seam was driven
        assert backend.subgroup_batches == []
        assert B.post_warmup_recompiles() == 0
        labels = {r.kernel for r in s.flight.snapshot(kind=BATCH)}
        assert labels == {"fast_aggregate_fused"}
    finally:
        s.stop()
        B.reset_shape_tracking()


# --------------------------------------------------- cross-lane merging


def test_merged_batch_preserves_lane_slices_and_flight(monkeypatch):
    """Two lanes whose deadlines share the merge window collapse into
    ONE device dispatch; each lane keeps its own verdict slice, flight
    record, and stats attribution."""
    good = _real_items(2, tag=b"good")
    bad = _real_items(2, valid=False, tag=b"bad")
    good_msgs = {it.message for it in good}
    monkeypatch.setattr(vs, "host_check_item",
                        lambda it: it.message in good_msgs)
    backend = _CountingBackend(
        fused=True, truth={it.message: False for it in bad}
    )
    lanes = (
        LaneConfig("attestation", Priority.LOW, 128, 0.25, 100, True),
        LaneConfig("sync_message", Priority.LOW, 128, 0.35, 100, True),
    )
    m = Metrics()
    s = VerifyScheduler(
        backend=backend, lanes=lanes, use_device=True, metrics=m,
        merge_window_s=5.0,
    )
    try:
        t_good = s.submit("attestation", good)
        t_bad = s.submit("sync_message", bad)
        assert t_good.result(30.0) is True
        assert t_bad.result(30.0) is False
        # one merged device dispatch carried both lanes' items
        assert backend.verify_batches[0] == 4
        assert s.stats["attestation"]["merged"] == 1
        assert s.stats["sync_message"]["merged"] == 1
        # per-lane flight attribution survives the shared pass
        att = s.flight.snapshot(lane="attestation")
        syn = s.flight.snapshot(lane="sync_message")
        assert att and att[0].items == 2 and att[0].verdict is True
        assert syn and syn[0].items == 2 and syn[0].verdict is False
        assert s.stats["attestation"]["accepted"] == 1
        assert s.stats["sync_message"]["rejected"] == 1
    finally:
        s.stop()


def test_merge_window_zero_never_merges(monkeypatch):
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    backend = _CountingBackend(fused=True)
    lanes = (
        LaneConfig("attestation", Priority.LOW, 128, 0.05, 100, True),
        LaneConfig("sync_message", Priority.LOW, 128, 0.08, 100, True),
    )
    s = VerifyScheduler(backend=backend, lanes=lanes, use_device=True)
    try:
        t1 = s.submit("attestation", _real_items(1, tag=b"a"))
        t2 = s.submit("sync_message", _real_items(1, tag=b"b"))
        assert t1.result(30.0) is True and t2.result(30.0) is True
        assert sorted(backend.verify_batches) == [1, 1]  # two dispatches
        assert s.stats["attestation"]["merged"] == 0
        assert s.stats["sync_message"]["merged"] == 0
    finally:
        s.stop()


def test_quarantine_lane_never_merges(monkeypatch):
    """Quarantined-origin traffic must keep its blast-radius isolation:
    neither side of a merge may include the quarantine lane."""
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    backend = _CountingBackend(fused=True)
    lanes = (
        LaneConfig("attestation", Priority.LOW, 128, 0.25, 100, True),
        LaneConfig("quarantine", Priority.LOW, 16, 0.30, 100, True),
    )
    s = VerifyScheduler(
        backend=backend, lanes=lanes, use_device=True, merge_window_s=5.0
    )
    try:
        t1 = s.submit("attestation", _real_items(1, tag=b"a"))
        t2 = s.submit("quarantine", _real_items(1, tag=b"q"))
        assert t1.result(30.0) is True and t2.result(30.0) is True
        assert sorted(backend.verify_batches) == [1, 1]
        assert s.stats["quarantine"]["merged"] == 0
    finally:
        s.stop()


# ------------------------------------- kernel differential (fast witness)


@pytest.fixture(scope="module")
def fused_metrics():
    return Metrics()


@pytest.fixture(scope="module")
def fused_backend(fused_metrics):
    """Fused + donating: the same jitted variant serves the differential
    witness and the pipeline aliasing regression (one compile)."""
    from grandine_tpu.tpu.bls import TpuBlsBackend

    with warnings.catch_warnings():
        # CPU XLA declines donation with a warning; the contract tests
        # still exercise the donate_argnums path end to end
        warnings.simplefilter("ignore")
        return TpuBlsBackend(
            fuse_subgroup=True, donate_buffers=True, metrics=fused_metrics
        )


@pytest.fixture(scope="module")
def unfused_backend():
    from grandine_tpu.tpu.bls import TpuBlsBackend

    return TpuBlsBackend(fuse_subgroup=False)


@pytest.fixture(scope="module")
def keys():
    return [A.SecretKey.keygen(_rng_bytes(32)) for _ in range(3)]


@pytest.mark.kernel
@pytest.mark.slow
def test_fused_multi_verify_differential(fused_backend, unfused_backend,
                                         keys, fused_metrics):
    """Fused verdict == two-pass verdict (unfused RLC AND the standalone
    subgroup pass) over valid / forged / non-subgroup specimens — and
    the fused path is a single device dispatch."""
    msgs = [b"fused-%d" % i for i in range(3)]
    pks = [sk.public_key() for sk in keys]
    valid = [sk.sign(m) for sk, m in zip(keys, msgs)]
    forged = list(valid)
    forged[1] = keys[1].sign(b"wrong message")
    nonsub = list(valid)
    nonsub[2] = _nonsubgroup_sig(b"ng-0")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for sigs in (valid, forged, nonsub):
            calls0 = fused_metrics.device_kernel_calls.value(
                "multi_verify_msm"
            )
            fused_v = fused_backend.multi_verify(msgs, sigs, pks)
            # exactly ONE kernel dispatch, NO separate subgroup kernel
            assert fused_metrics.device_kernel_calls.value(
                "multi_verify_msm"
            ) == calls0 + 1
            assert fused_metrics.device_kernel_calls.value(
                "g2_subgroup_check"
            ) == 0
            two_pass = bool(unfused_backend.multi_verify(msgs, sigs, pks))
            two_pass = two_pass and bool(
                unfused_backend.g2_subgroup_check_batch(
                    [s.point for s in sigs]
                ).all()
            )
            assert bool(fused_v) == two_pass
    # ground truth: valid passes, both corruptions fail
    assert fused_backend.multi_verify(msgs, valid, pks)
    assert not fused_backend.multi_verify(msgs, forged, pks)
    assert not fused_backend.multi_verify(msgs, nonsub, pks)


@pytest.mark.kernel
@pytest.mark.slow
def test_donation_pipeline_aliasing_regression(fused_backend, keys):
    """Two donated batches in flight (the two-deep pipeline) settle to
    independent, correct verdicts: no donated operand is read after its
    dispatch, so batch N+1's host prep cannot corrupt batch N."""
    msgs_a = [b"alias-a-%d" % i for i in range(3)]
    msgs_b = [b"alias-b-%d" % i for i in range(3)]
    pks = [sk.public_key() for sk in keys]
    sigs_a = [sk.sign(m) for sk, m in zip(keys, msgs_a)]
    sigs_b = list(sk.sign(m) for sk, m in zip(keys, msgs_b))
    sigs_b[0] = keys[0].sign(b"forged")  # B must fail, A must pass
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        settle_a = fused_backend.multi_verify_async(msgs_a, sigs_a, pks)
        settle_b = fused_backend.multi_verify_async(msgs_b, sigs_b, pks)
        # settle out of dispatch order: verdicts must not bleed
        assert bool(settle_b()) is False
        assert bool(settle_a()) is True


@pytest.mark.kernel
@pytest.mark.slow
def test_fused_aggregate_and_partition_differential(fused_backend,
                                                    unfused_backend, keys):
    """Full three-family differential: the aggregate (fast_aggregate
    MSM) and rlc_partition kernels agree with their two-pass equivalents
    on valid / forged / non-subgroup specimens."""
    msgs = [b"agg-%d" % i for i in range(2)]
    committees = [keys[:2], keys[1:3]]
    pk_lists = [[sk.public_key() for sk in ks] for ks in committees]
    valid = [
        A.Signature.aggregate([sk.sign(m) for sk in ks])
        for m, ks in zip(msgs, committees)
    ]
    forged = [valid[0], valid[0]]
    nonsub = [valid[0], _nonsubgroup_sig(b"ng-agg")]

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for sigs in (valid, forged, nonsub):
            fused_v = bool(fused_backend.fast_aggregate_verify_batch(
                msgs, sigs, pk_lists
            ))
            two_pass = bool(unfused_backend.fast_aggregate_verify_batch(
                msgs, sigs, pk_lists
            )) and bool(unfused_backend.g2_subgroup_check_batch(
                [s.point for s in sigs]
            ).all())
            assert fused_v == two_pass
        assert fused_backend.fast_aggregate_verify_batch(
            msgs, valid, pk_lists
        )
        assert not fused_backend.fast_aggregate_verify_batch(
            msgs, nonsub, pk_lists
        )

        # rlc_partition: per-group verdicts; the group holding the
        # non-subgroup signature fails, the clean group passes. The
        # group count buckets up to 4 — with n=2 that is one item per
        # group plus two padding-only groups, which report True.
        for sigs, expect in (
            (valid, [True, True]),
            (nonsub, [True, False]),
        ):
            fused_g = [bool(v) for v in np.asarray(
                fused_backend.rlc_partition_verify(
                    msgs, sigs, pk_lists, groups=2
                )
            )]
            sub_ok = unfused_backend.g2_subgroup_check_batch(
                [s.point for s in sigs]
            )
            unfused_g = [bool(v) for v in np.asarray(
                unfused_backend.rlc_partition_verify(
                    msgs, sigs, pk_lists, groups=2
                )
            )]
            two_pass_g = [
                u and bool(s) for u, s in zip(unfused_g, sub_ok)
            ]
            assert fused_g[:2] == two_pass_g == expect
            assert fused_g[2:] == unfused_g[2:] == [True, True]


# ----------------------------------------------------------- msm autotune


@pytest.mark.parametrize(
    "n_points,n_groups", [(32768, 256), (1 << 20, 1), (16384, 256)]
)
def test_pick_msm_window_above_max_bucket(n_points, n_groups):
    """A shape above MAX_BUCKET (32,768 x 256) crashed here: the table
    key was quantized with the dispatch plane's `_bucket`, which raises
    above MAX_BUCKET, as soon as ANY table was loaded."""
    from grandine_tpu.tpu import bls as B

    try:
        B.set_msm_tuning({"256:1": 4, "64:1": 4, "64:16": 4})
        assert 4 <= B.pick_msm_window(n_points, n_groups) <= 8
    finally:
        B.set_msm_tuning(None)


def test_pick_msm_window_consults_table():
    from grandine_tpu.tpu import bls as B

    try:
        model = B.pick_msm_window(64, 1)
        override = 7 if model != 7 else 8
        B.set_msm_tuning({"64:1": override})
        assert B.pick_msm_window(64, 1) == override
        assert B.pick_msm_window(63, 1) == override  # buckets up to 64
        # unmeasured shape falls back to the analytic model
        assert 4 <= B.pick_msm_window(4096, 16) <= 8
    finally:
        B.set_msm_tuning(None)


def test_msm_tuning_roundtrip_and_validation(tmp_path):
    from grandine_tpu.tpu import autotune as T
    from grandine_tpu.tpu import bls as B

    path = str(tmp_path / "msm_tune.json")
    try:
        out = T.write_tuning({"64:1": 5, "256:1": 4}, path=path)
        assert out == path
        assert B.load_msm_tuning(path) == {"64:1": 5, "256:1": 4}
        # out-of-range and malformed entries are dropped, not trusted
        (tmp_path / "bad.json").write_text(
            '{"platform": "cpu",'
            ' "windows": {"64:1": 99, "256:1": "x", "16:1": 6}}'
        )
        assert B.load_msm_tuning(str(tmp_path / "bad.json")) == {"16:1": 6}
        # a table measured on another platform does not steer this one
        (tmp_path / "chip.json").write_text(
            '{"platform": "tpu", "windows": {"16:1": 6}}'
        )
        assert B.load_msm_tuning(str(tmp_path / "chip.json")) is None
        assert B.load_msm_tuning(str(tmp_path / "missing.json")) is None
    finally:
        B.set_msm_tuning(None)


@pytest.mark.kernel
@pytest.mark.slow
def test_autotune_sweep_cell(tmp_path):
    """One tiny sweep cell end to end: measures, persists, and the
    persisted table wins the window lookup."""
    from grandine_tpu.tpu import autotune as T
    from grandine_tpu.tpu import bls as B

    path = str(tmp_path / "msm_tune.json")
    try:
        table = T.autotune(
            shapes=((8, 1),), windows=(4, 5), repeats=1, path=path,
            verbose=None,
        )
        assert set(table) == {"8:1"} and table["8:1"] in (4, 5)
        B.set_msm_tuning(B.load_msm_tuning(path))
        assert B.pick_msm_window(8, 1) == table["8:1"]
    finally:
        B.set_msm_tuning(None)
