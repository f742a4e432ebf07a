"""Unified verify scheduler tests: lane scheduling semantics (deadline
flush, priority, shed/backpressure), bisection isolation of poisoned
batches, graceful degradation off a faulted device backend, the
Verifier-seam adapter, and a differential check that the scheduled
gossip path makes the SAME accept/reject decisions as the eager inline
path — including forged sync-committee messages.

Host BLS verification on the pure-python anchor costs ~0.7 s/pairing, so
scheduling-semantics tests stub `host_check_item` (the crypto leaf) and
only the isolation/differential/robustness tests spend real signatures —
a handful each. All scheduler instances here run `use_device=False` or
an injected fake backend: no kernel compiles at test time.
"""

import threading
import time

import numpy as np
import pytest

from grandine_tpu.consensus import signing
from grandine_tpu.consensus.verifier import NullVerifier, SignatureInvalid
from grandine_tpu.fork_choice import Tick, TickKind
from grandine_tpu.metrics import Metrics
from grandine_tpu.p2p.network import InMemoryHub, Network
from grandine_tpu.pools.sync_committee_pool import SyncCommitteeAggPool
from grandine_tpu.runtime import verify_scheduler as vs
from grandine_tpu.runtime.controller import Controller
from grandine_tpu.runtime.thread_pool import Priority
from grandine_tpu.runtime.verify_scheduler import (
    LaneConfig,
    VerifyItem,
    VerifyScheduler,
)
from grandine_tpu.transition.genesis import interop_genesis_state
from grandine_tpu.types.config import Config
from grandine_tpu.types.containers import spec_types
from grandine_tpu.validator.duties import _interop_keys, produce_block

CFG = Config.minimal()
P = CFG.preset
NS = spec_types(P).deneb


@pytest.fixture(scope="module")
def genesis():
    return interop_genesis_state(16, CFG)


def _stub_item(tag: bytes) -> VerifyItem:
    """Key material is never touched when host_check_item is stubbed."""
    return VerifyItem(
        tag.ljust(32, b"\x00"), tag.ljust(96, b"\x00"), public_keys=("stub",)
    )


# ------------------------------------------------------- lane semantics


def test_deadline_flush_fires_without_further_submissions(monkeypatch):
    """A lone job flushes at max_wait — no follow-up submission, no
    max_batch trigger — and not (much) before the deadline."""
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    lanes = (LaneConfig("low", Priority.LOW, 1000, 0.05, 100, shed=True),)
    s = VerifyScheduler(lanes=lanes, use_device=False, metrics=Metrics())
    try:
        t0 = time.monotonic()
        ticket = s.submit("low", [_stub_item(b"a")])
        assert ticket.result(5.0) is True
        elapsed = time.monotonic() - t0
        assert 0.04 <= elapsed < 2.0
        assert s.stats["low"]["batches"] == 1
        assert s.stats["low"]["accepted"] == 1
    finally:
        s.stop()


def test_max_batch_flushes_before_deadline(monkeypatch):
    """Reaching max_batch items flushes immediately even when max_wait
    is far away (whichever-first policy)."""
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    lanes = (LaneConfig("low", Priority.LOW, 4, 60.0, 100, shed=True),)
    s = VerifyScheduler(lanes=lanes, use_device=False)
    try:
        tickets = [s.submit("low", [_stub_item(bytes([i]))]) for i in range(4)]
        for t in tickets:
            assert t.result(5.0) is True
        assert s.stats["low"]["max_batch_items"] == 4
    finally:
        s.stop()


def test_high_lane_picked_over_saturated_low_lane(monkeypatch):
    """Deterministic priority check: with both lanes overdue, _pick_lane
    selects the HIGH lane regardless of which is more overdue."""
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    lanes = (
        LaneConfig("high", Priority.HIGH, 64, 0.01, 100, shed=False),
        LaneConfig("low", Priority.LOW, 64, 0.001, 100, shed=True),
    )
    s = VerifyScheduler(lanes=lanes, use_device=False)
    try:
        # the condition's lock is re-entrant: holding it parks the
        # dispatcher so the queue state is ours to stage
        with s._cond:
            t_low = s.submit("low", [_stub_item(b"l")])
            t_high = s.submit("high", [_stub_item(b"h")])
            t_low.enqueued_at -= 10.0  # low is MORE overdue than high
            t_high.enqueued_at -= 1.0
            assert s._pick_lane(time.monotonic()) == "high"
        s.flush(10.0)
        assert t_high.ok and t_low.ok
    finally:
        s.stop()


def test_high_lane_never_starved_by_low_backlog(monkeypatch):
    """End-to-end: a HIGH job submitted behind a deep LOW backlog
    settles while most of the backlog is still queued."""
    monkeypatch.setattr(
        vs, "host_check_item", lambda it: time.sleep(0.02) or True
    )
    lanes = (
        LaneConfig("high", Priority.HIGH, 4, 0.001, 100, shed=False),
        LaneConfig("low", Priority.LOW, 4, 0.0, 1000, shed=True),
    )
    s = VerifyScheduler(lanes=lanes, use_device=False)
    try:
        low = [s.submit("low", [_stub_item(bytes([i]))]) for i in range(40)]
        t_high = s.submit("high", [_stub_item(b"hi")])
        assert t_high.result(10.0) is True
        assert sum(1 for t in low if not t.done()) > 0
        s.flush(30.0)
    finally:
        s.stop()


def test_low_lane_sheds_oldest_first_and_counts_drops(monkeypatch):
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    # never due (huge max_batch + max_wait): the queue only fills
    lanes = (LaneConfig("low", Priority.LOW, 10_000, 60.0, 4, shed=True),)
    m = Metrics()
    s = VerifyScheduler(lanes=lanes, use_device=False, metrics=m)
    tickets = [s.submit("low", [_stub_item(bytes([i]))]) for i in range(6)]
    try:
        # the two OLDEST jobs were shed; shed resolves False+dropped so
        # gossip accounting counts an "ignore", not a "reject"
        for t in tickets[:2]:
            assert t.done() and t.dropped and t.ok is False
        assert not any(t.done() for t in tickets[2:])
        assert s.stats["low"]["shed"] == 2
        assert m.verify_lane_dropped.value("low") == 2.0
    finally:
        s.stop()
    # stop() drains by DROPPING: the survivors resolve immediately with
    # dropped=True (an "ignore", never a "reject") — no result() caller
    # hangs to its full timeout during shutdown
    for t in tickets[2:]:
        assert t.done() and t.dropped and t.ok is False


def test_high_lane_backpressures_instead_of_shedding(monkeypatch):
    """A full HIGH lane blocks the submitter (bounded producer); it
    never drops — `shed` stays zero even at capacity."""
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    lanes = (LaneConfig("high", Priority.HIGH, 10_000, 60.0, 1, shed=False),)
    s = VerifyScheduler(lanes=lanes, use_device=False)
    first = s.submit("high", [_stub_item(b"a")])
    blocked: list = []
    th = threading.Thread(
        target=lambda: blocked.append(s.submit("high", [_stub_item(b"b")]))
    )
    th.start()
    time.sleep(0.3)
    assert th.is_alive()  # backpressured, not shed
    assert s.stats["high"]["shed"] == 0
    s.stop()
    th.join(5.0)
    assert not th.is_alive()
    assert first.done() and first.dropped  # drained (dropped) at stop
    # the blocked submission surfaces as an explicit drop, never silence
    assert blocked[0].done() and blocked[0].dropped


# ------------------------------------------- fake device backend (tests)


class _FakeAsyncBackend:
    """Async-seam double for the device backend: verdicts come from a
    truth table keyed by message bytes; records verify-batch sizes so
    tests can assert the bisection pattern; injects dispatch-time or
    settle-time faults."""

    def __init__(self, truth=None, fail_dispatch=False, fail_settle=False):
        self.truth = dict(truth or {})
        self.batches: "list[int]" = []
        self.fail_dispatch = fail_dispatch
        self.fail_settle = fail_settle

    def g2_subgroup_check_batch_async(self, points):
        if self.fail_dispatch:
            raise RuntimeError("injected dispatch fault")
        out = np.ones(len(points), dtype=bool)

        def settle():
            if self.fail_settle:
                raise RuntimeError("injected settle fault")
            return out

        return settle

    def fast_aggregate_verify_batch_async(self, messages, signatures, keys):
        if self.fail_dispatch:
            raise RuntimeError("injected dispatch fault")
        self.batches.append(len(messages))
        ok = all(self.truth.get(bytes(m), False) for m in messages)

        def settle():
            if self.fail_settle:
                raise RuntimeError("injected settle fault")
            return ok

        return settle


# -------------------------------------------------- bisection isolation


def test_bisection_admits_good_items_of_poisoned_batch():
    """One forged signature in a coalesced batch: the batch verdict
    fails, bisection descends ONLY into the failing half, and the good
    items' tickets still resolve True (real signatures; real host
    verification at the leaves)."""
    key = _interop_keys(0)
    msgs = [bytes([i]) * 32 for i in range(4)]
    sigs = [key.sign(m).to_bytes() for m in msgs[:3]]
    # forged: a REAL G2 point (decompresses fine) over the wrong message
    sigs.append(sigs[0])
    items = [
        VerifyItem(m, s, public_keys=(key.public_key(),))
        for m, s in zip(msgs, sigs)
    ]
    backend = _FakeAsyncBackend(truth={m: True for m in msgs[:3]})
    m = Metrics()
    lanes = (LaneConfig("sync_message", Priority.LOW, 128, 0.05, 100, True),)
    s = VerifyScheduler(
        backend=backend, lanes=lanes, use_device=True, metrics=m
    )
    try:
        tickets = [s.submit("sync_message", [it]) for it in items]
        verdicts = [t.result(60.0) for t in tickets]
        assert verdicts == [True, True, True, False]
        # one coalesced batch of 4; the good half passes whole, only the
        # bad half descends (its two singletons re-check)
        assert backend.batches == [4, 2, 2, 1, 1]
        assert s.stats["sync_message"]["accepted"] == 3
        assert s.stats["sync_message"]["rejected"] == 1
        assert m.verify_lane_batches.value("sync_message", "invalid") == 1.0
    finally:
        s.stop()


def test_open_merge_window_halves_two_lane_dispatches():
    """Two lanes with the same workload in the same compile bucket: with
    the merge window closed every lane batch is a device dispatch of its
    own, with it open each dispatch carries a batch of BOTH lanes — half
    the dispatches, every verdict unchanged."""
    key = _interop_keys(0)
    rounds = 4
    msgs = [bytes([0x40 + i]) * 32 for i in range(2 * rounds)]
    items = [
        VerifyItem(m, key.sign(m).to_bytes(), public_keys=(key.public_key(),))
        for m in msgs
    ]
    dispatches = {}
    for window_s in (0.0, 5.0):
        backend = _FakeAsyncBackend(truth={m: True for m in msgs})
        # jobs of 2 items under max_batch 2: a lane batch is one job, so
        # a merged pair (4 items) and the dispatch count depend on the
        # window alone
        lanes = (
            LaneConfig("attestation", Priority.LOW, 2, 0.05, 4096, False),
            LaneConfig("sync_message", Priority.LOW, 2, 0.08, 4096, False),
        )
        s = VerifyScheduler(
            backend=backend, lanes=lanes, use_device=True,
            merge_window_s=window_s,
        )
        try:
            # the condition's lock is re-entrant: holding it parks the
            # dispatcher until both lanes hold their whole workload
            with s._cond:
                tickets = [
                    s.submit(lane, items[2 * j:2 * j + 2])
                    for j in range(rounds)
                    for lane in ("attestation", "sync_message")
                ]
            s.flush(60.0)
            assert all(t.done() and t.ok for t in tickets)
            dispatches[window_s] = list(backend.batches)
            merged = sum(st["merged"] for st in s.stats.values())
            assert merged == (2 * rounds if window_s else 0)
        finally:
            s.stop()
    assert dispatches[0.0] == [2] * (2 * rounds)
    assert dispatches[5.0] == [4] * rounds


# --------------------------------------------------- fault degradation


def test_settle_fault_degrades_to_host_and_blocks_still_import(genesis):
    """A device backend that faults at readback: every lane degrades to
    the eager host path and the node KEEPS importing blocks through the
    scheduler's block lane."""
    backend = _FakeAsyncBackend(fail_settle=True)
    m = Metrics()
    s = VerifyScheduler(backend=backend, use_device=True, metrics=m)
    ctrl = Controller(
        genesis, CFG, verifier_factory=s.verifier_factory("block")
    )
    try:
        signed, _post = produce_block(
            genesis, 1, CFG, full_sync_participation=False
        )
        ctrl.on_tick(Tick(1, TickKind.PROPOSE))
        ctrl.on_gossip_block(signed)
        ctrl.wait()
        assert signed.message.hash_tree_root() in ctrl.store.blocks
        assert s.stats["block"]["device_faults"] >= 1
        assert m.verify_lane_batches.value("block", "degraded") >= 1.0
        # a LOW lane degrades the same way (valid item still accepted)
        key = _interop_keys(0)
        msg = b"\x07" * 32
        item = VerifyItem(
            msg, key.sign(msg).to_bytes(), public_keys=(key.public_key(),)
        )
        t = s.submit("sync_message", [item])
        assert t.result(30.0) is True
        assert s.stats["sync_message"]["device_faults"] >= 1
    finally:
        ctrl.stop()
        s.stop()


@pytest.mark.parametrize("first_call_compiles", [True, False])
def test_compile_inside_settle_is_not_a_device_fault(first_call_compiles):
    """jax.jit compiles where a shape is first CALLED, and some settles
    dispatch inside themselves (chunked batches, the sign plane). A
    first call that outlasts the settle deadline because it COMPILES
    (tpu/compile_scope.compiling) must not feed the breaker or send the
    batch to the host twin; the same delay outside a compile still must
    (the control: the watchdog has not been blinded)."""
    from contextlib import nullcontext

    from grandine_tpu.runtime.health import BackendHealthSupervisor
    from grandine_tpu.tpu.compile_scope import compiling

    deadline_s = 0.15

    class _SlowFirstCall(_FakeAsyncBackend):
        def fast_aggregate_verify_batch_async(self, messages, sigs, keys):
            inner = super().fast_aggregate_verify_batch_async(
                messages, sigs, keys
            )

            def settle():
                if len(self.batches) == 1:  # first call of the shape
                    scope = compiling() if first_call_compiles \
                        else nullcontext()
                    with scope:
                        time.sleep(4 * deadline_s)
                return inner()

            return settle

    key = _interop_keys(0)
    msg = b"\x09" * 32
    item = VerifyItem(
        msg, key.sign(msg).to_bytes(), public_keys=(key.public_key(),)
    )
    m = Metrics()
    health = BackendHealthSupervisor(metrics=m, settle_timeout_s=deadline_s)
    s = VerifyScheduler(
        backend=_SlowFirstCall(truth={msg: True}), use_device=True,
        metrics=m, health=health,
    )
    try:
        assert s.submit("sync_message", [item]).result(60.0) is True
        faults = s.stats["sync_message"]["device_faults"]
        degraded = m.verify_lane_batches.value("sync_message", "degraded")
        fired = m.verify_watchdog_fired.value("sync_message")
        if first_call_compiles:
            assert (faults, degraded, fired) == (0, 0.0, 0.0)
            assert m.verify_lane_batches.value("sync_message", "ok") == 1.0
            assert health.breaker.stats["opens"] == 0
        else:
            assert faults == 1 and degraded == 1.0 and fired == 1.0
    finally:
        s.stop()


def test_dispatch_fault_degrades_to_host(monkeypatch):
    """A fault at dispatch time (before any settle exists) is caught in
    _flush: counted, the batch host-checks, nothing drops."""
    monkeypatch.setattr(vs, "host_check_item", lambda it: True)
    key = _interop_keys(1)
    msg = b"\x09" * 32
    item = VerifyItem(
        msg, key.sign(msg).to_bytes(), public_keys=(key.public_key(),)
    )
    backend = _FakeAsyncBackend(fail_dispatch=True)
    m = Metrics()
    lanes = (LaneConfig("exit", Priority.LOW, 16, 0.01, 100, shed=True),)
    s = VerifyScheduler(
        backend=backend, lanes=lanes, use_device=True, metrics=m
    )
    try:
        t = s.submit("exit", [item])
        assert t.result(10.0) is True
        assert s.stats["exit"]["device_faults"] == 1
        assert m.verify_lane_batches.value("exit", "degraded") == 1.0
    finally:
        s.stop()


# ------------------------------------------------------- Verifier seam


def test_deferred_verifier_raises_on_invalid_batch(monkeypatch):
    monkeypatch.setattr(vs, "host_check_item", lambda it: False)
    lanes = (LaneConfig("block", Priority.HIGH, 64, 0.002, 100, False),)
    s = VerifyScheduler(lanes=lanes, use_device=False)
    try:
        v = s.deferred("block", timeout=10.0)
        v.verify_singular(b"\x00" * 32, b"\x00" * 96, "k")
        with pytest.raises(SignatureInvalid):
            v.finish()
        assert s.stats["block"]["rejected"] == 1
    finally:
        s.stop()


# ----------------------------------------- gossip boundary differential


def test_scheduled_gossip_matches_eager_on_every_object_kind(genesis):
    """Differential acceptance test: one receiver verifies through the
    scheduler, one through the eager inline path. A valid + forged
    specimen of EVERY signed gossip object kind — sync-committee
    message, contribution, proposer slashing, attester slashing,
    BLS-to-execution change, voluntary exit — must produce IDENTICAL
    accept/reject stats and pool contents on both."""
    from grandine_tpu.consensus import accessors
    from grandine_tpu.consensus.verifier import MultiVerifier
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.pools.operation_pool import OperationPool
    from grandine_tpu.types.combined import state_phase_of

    hub = InMemoryHub()
    ctrl_a = Controller(genesis, CFG, verifier_factory=NullVerifier)
    ctrl_e = Controller(genesis, CFG, verifier_factory=NullVerifier)
    ctrl_s = Controller(genesis, CFG, verifier_factory=NullVerifier)
    sched = VerifyScheduler(use_device=False, metrics=Metrics())
    try:
        net_a = Network(hub.join("a"), ctrl_a, CFG)
        pool_e, pool_s = SyncCommitteeAggPool(CFG), SyncCommitteeAggPool(CFG)
        op_e, op_s = OperationPool(CFG), OperationPool(CFG)
        net_e = Network(
            hub.join("e"), ctrl_e, CFG, sync_pool=pool_e,
            operation_pool=op_e,
        )
        net_s = Network(
            hub.join("s"), ctrl_s, CFG, sync_pool=pool_s,
            operation_pool=op_s, verify_scheduler=sched,
        )
        head_root = ctrl_a.snapshot().head_root
        bad_sig = b"\xc0" + b"\x00" * 95

        # --- sync-committee message ---------------------------------
        key = _interop_keys(0)
        root = signing.sync_committee_message_signing_root(
            genesis, head_root, 0, CFG
        )
        msg = NS.SyncCommitteeMessage(
            slot=1, beacon_block_root=head_root, validator_index=0,
            signature=key.sign(root).to_bytes(),
        )
        net_a.publish_sync_committee_message(msg)
        net_a.publish_sync_committee_message(msg.replace(signature=bad_sig))

        # --- contribution -------------------------------------------
        sub_size = P.SYNC_COMMITTEE_SIZE // CFG.sync_committee_subnet_count
        members = [
            bytes(pk)
            for pk in genesis.current_sync_committee.pubkeys[:sub_size]
        ]
        # sign as whichever validator holds the first subcommittee slot so
        # the test never depends on how the committee shuffle landed
        val_pubkeys = [bytes(v.pubkey) for v in genesis.validators]
        mkey = _interop_keys(val_pubkeys.index(members[0]))
        bits = [False] * sub_size
        bits[0] = True
        contribution = NS.SyncCommitteeContribution(
            slot=1, beacon_block_root=head_root, subcommittee_index=0,
            aggregation_bits=bits, signature=mkey.sign(root).to_bytes(),
        )
        # the aggregator must be a subcommittee member with a REAL
        # selection proof and outer signature (both now verified)
        agg_idx = val_pubkeys.index(members[0])
        proof = NS.ContributionAndProof(
            aggregator_index=agg_idx, contribution=contribution,
            selection_proof=mkey.sign(
                signing.sync_selection_proof_signing_root(
                    genesis,
                    NS.SyncAggregatorSelectionData(
                        slot=1, subcommittee_index=0
                    ),
                    CFG,
                )
            ).to_bytes(),
        )
        signed_contrib = NS.SignedContributionAndProof(
            message=proof,
            signature=mkey.sign(
                signing.contribution_and_proof_signing_root(
                    genesis, proof, CFG
                )
            ).to_bytes(),
        )
        net_a.publish_sync_contribution(signed_contrib)
        net_a.publish_sync_contribution(
            signed_contrib.replace(
                message=signed_contrib.message.replace(
                    contribution=contribution.replace(signature=bad_sig)
                )
            )
        )

        # --- proposer slashing --------------------------------------
        pkey = _interop_keys(1)

        def signed_header(body_root):
            header = NS.BeaconBlockHeader(
                slot=0, proposer_index=1, parent_root=b"\x00" * 32,
                state_root=b"\x00" * 32, body_root=body_root,
            )
            return NS.SignedBeaconBlockHeader(
                message=header,
                signature=pkey.sign(
                    signing.header_signing_root(genesis, header, CFG)
                ).to_bytes(),
            )

        pslashing = NS.ProposerSlashing(
            signed_header_1=signed_header(b"\x01" * 32),
            signed_header_2=signed_header(b"\x02" * 32),
        )
        net_a.publish_proposer_slashing(pslashing)
        net_a.publish_proposer_slashing(
            pslashing.replace(
                signed_header_2=pslashing.signed_header_2.replace(
                    signature=bad_sig
                )
            )
        )

        # --- attester slashing (real double vote) -------------------
        committee = accessors.get_beacon_committee(genesis, 0, 0, P)
        offenders = sorted(int(i) for i in committee)[:2]

        def indexed(data):
            sroot = signing.attestation_signing_root(genesis, data, CFG)
            sig = A.Signature.aggregate(
                [_interop_keys(i).sign(sroot) for i in offenders]
            )
            return NS.IndexedAttestation(
                attesting_indices=offenders, data=data,
                signature=sig.to_bytes(),
            )

        data1 = NS.AttestationData(
            slot=0, index=0, beacon_block_root=b"\x01" * 32,
            source=genesis.current_justified_checkpoint,
            target=NS.Checkpoint(epoch=0, root=b"\x01" * 32),
        )
        data2 = data1.replace(
            beacon_block_root=b"\x02" * 32,
            target=NS.Checkpoint(epoch=0, root=b"\x02" * 32),
        )
        aslashing = NS.AttesterSlashing(
            attestation_1=indexed(data1), attestation_2=indexed(data2)
        )
        net_a.publish_attester_slashing(aslashing)
        net_a.publish_attester_slashing(
            aslashing.replace(
                attestation_1=aslashing.attestation_1.replace(
                    signature=bad_sig
                )
            )
        )

        # --- BLS-to-execution change --------------------------------
        ckey = _interop_keys(3)
        change_msg = NS.BLSToExecutionChange(
            validator_index=3,
            from_bls_pubkey=ckey.public_key().to_bytes(),
            to_execution_address=b"\x02" * 20,
        )
        croot = signing.bls_to_execution_change_signing_root(
            genesis, change_msg, CFG
        )
        change = NS.SignedBLSToExecutionChange(
            message=change_msg, signature=ckey.sign(croot).to_bytes(),
        )
        net_a.publish_bls_change(change)
        net_a.publish_bls_change(change.replace(signature=bad_sig))

        # --- voluntary exit -----------------------------------------
        ekey = _interop_keys(5)
        unsigned_exit = NS.SignedVoluntaryExit(
            message=NS.VoluntaryExit(epoch=0, validator_index=5),
            signature=b"\x00" * 96,
        )
        collector = MultiVerifier()
        signing.extend_with_voluntary_exit(
            collector, genesis, unsigned_exit, CFG,
            state_phase_of(genesis, CFG),
        )
        exit_root = collector.triples[0].message
        signed_exit = unsigned_exit.replace(
            signature=ekey.sign(exit_root).to_bytes()
        )
        net_a.publish_voluntary_exit(signed_exit)
        net_a.publish_voluntary_exit(signed_exit.replace(signature=bad_sig))

        # --- settle both planes, compare decisions ------------------
        sched.flush(120.0)
        ctrl_e.wait()
        ctrl_s.wait()
        expected = {
            "sync_messages_in": 2, "sync_messages_rejected": 1,
            "sync_contributions_in": 2, "sync_contributions_rejected": 1,
            "proposer_slashings_in": 2, "proposer_slashings_rejected": 1,
            "attester_slashings_in": 2, "attester_slashings_rejected": 1,
            "bls_changes_in": 2, "bls_changes_rejected": 1,
            "voluntary_exits_in": 2, "voluntary_exits_rejected": 1,
        }
        for k, want in expected.items():
            got_e = net_e.stats.get(k, 0)
            got_s = net_s.stats.get(k, 0)
            assert got_s == got_e == want, (k, got_e, got_s, want)
        # pool contents match: the one valid specimen of each kind
        for op_pool in (op_e, op_s):
            contents = op_pool.contents()
            assert len(contents["proposer_slashings"]) == 1
            assert len(contents["attester_slashings"]) == 1
            assert len(contents["bls_to_execution_changes"]) == 1
            assert len(contents["voluntary_exits"]) == 1
        assert set(offenders) <= ctrl_e.store.equivocating
        assert ctrl_s.store.equivocating == ctrl_e.store.equivocating
        agg_e = pool_e.best_aggregate(1, head_root, NS)
        agg_s = pool_s.best_aggregate(1, head_root, NS)
        assert bytes(agg_s.sync_committee_signature) == bytes(
            agg_e.sync_committee_signature
        )
        assert list(agg_s.sync_committee_bits.array) == list(
            agg_e.sync_committee_bits.array
        )
        # the scheduled plane really carried every lane. The whole test
        # gossips through ONE peer, so the first invalid specimen
        # quarantines it and LATER sheddable-lane traffic may reroute
        # into the quarantine lane (a race against batch settling) —
        # count rerouted submissions with their source lanes.
        assert sched.stats["sync_message"]["submitted"] >= 1
        assert sched.stats["sync_contribution"]["submitted"] >= 1
        reroutable = ("slashing", "bls_change", "exit")
        direct = sum(sched.stats[ln]["submitted"] for ln in reroutable)
        q = sched.stats["quarantine"]
        # 2 proposer + 2 attester slashings, 2 bls changes, 2 exits
        assert direct + q["submitted"] == 8
        lanes = ("sync_message", "sync_contribution") + reroutable
        total_rejected = (
            sum(sched.stats[ln]["rejected"] for ln in lanes) + q["rejected"]
        )
        assert total_rejected == 6  # one invalid specimen per kind
    finally:
        sched.stop()
        ctrl_a.stop()
        ctrl_e.stop()
        ctrl_s.stop()


def test_sync_positions_cache_and_invalidation(genesis):
    """Satellite: the pubkey→positions table builds once per
    sync-committee period and the validator-set-change hook drops it."""
    hub = InMemoryHub()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier)
    try:
        net = Network(hub.join("x"), ctrl, CFG)
        pk = bytes(genesis.validators[0].pubkey)
        expected = tuple(
            i for i, p in enumerate(genesis.current_sync_committee.pubkeys)
            if bytes(p) == pk
        )
        assert expected  # 16 interop validators fill a 32-slot committee
        pos1 = net._sync_committee_positions(genesis, 1, pk)
        table = net._sync_positions[0]
        assert pos1 == expected
        # second lookup reuses the period's table (no rebuild)
        assert net._sync_committee_positions(genesis, 1, pk) == expected
        assert net._sync_positions[0] is table
        # unknown key resolves to no positions, not a KeyError
        assert net._sync_committee_positions(genesis, 1, b"\x01" * 48) == ()
        # a slot one period AHEAD resolves against next_sync_committee
        p = CFG.preset
        ahead = p.SLOTS_PER_EPOCH * p.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
        next_expected = tuple(
            i for i, pkb in enumerate(genesis.next_sync_committee.pubkeys)
            if bytes(pkb) == pk
        )
        assert net._sync_committee_positions(genesis, ahead, pk) == (
            next_expected
        )
        # two periods ahead is outside what the head state knows
        assert net._sync_committee_positions(genesis, 2 * ahead, pk) == ()
        # the controller hook (wired in Network.__init__) invalidates
        for cb in ctrl.on_validator_set_change:
            cb(None, None)
        assert net._sync_positions is None
    finally:
        ctrl.stop()


# --------------------------------------------------- blob-header lane


def test_blob_sidecar_header_rides_scheduler(genesis):
    """Controller._check_sidecar_header routes through the blob_header
    lane when a scheduler is wired; the block still imports."""
    from grandine_tpu.kzg.sidecar import make_blob_sidecars

    zero_blob = b"\x00" * (P.FIELD_ELEMENTS_PER_BLOB * 32)
    inf_g1 = b"\xc0" + b"\x00" * 47  # zero blob: commitment == infinity
    sched = VerifyScheduler(use_device=False, metrics=Metrics())
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier)
    ctrl.verify_scheduler = sched
    try:
        signed, _post = produce_block(
            genesis, 1, CFG, full_sync_participation=False,
            blob_kzg_commitments=[inf_g1],
        )
        sidecars = make_blob_sidecars(
            NS, P, signed, [zero_blob], proofs=[inf_g1]
        )
        ctrl.on_tick(Tick(1, TickKind.PROPOSE))
        for sc in sidecars:
            ctrl.on_gossip_blob_sidecar(sc)
        ctrl.on_gossip_block(signed)
        ctrl.wait()
        assert signed.message.hash_tree_root() in ctrl.store.blocks
        assert sched.stats["blob_header"]["batches"] >= 1
        assert sched.stats["blob_header"]["accepted"] >= 1
    finally:
        ctrl.stop()
        sched.stop()


# ------------------------------------------------- metrics + CI guard


def test_verify_stage_seconds_lane_label_defaults():
    """Widening verify_stage_seconds to (stage, lane, op) must not break
    the pre-existing single- and two-label call sites: they resolve to
    the attestation series with no `op`."""
    m = Metrics()
    m.verify_stage_seconds.labels("execute").observe(0.001)
    m.verify_stage_seconds.observe("execute", value=0.002)
    m.verify_stage_seconds.labels("execute", "sync_message").observe(0.003)
    m.verify_stage_seconds.labels("host_prep", "attestation",
                                  "prevalidate").observe(0.004)
    children = m.verify_stage_seconds.children()
    assert ("execute", "attestation", "") in children
    assert ("execute", "sync_message", "") in children
    assert ("host_prep", "attestation", "prevalidate") in children
    assert all(len(k) == 3 for k in children)
    assert m.verify_stage_seconds.labels(stage="fallback") is (
        m.verify_stage_seconds.labels("fallback", "attestation")
    )
    assert m.verify_stage_seconds.labels("fallback", "attestation", "") is (
        m.verify_stage_seconds.labels("fallback")
    )


# The inline-gossip-verify guard now runs as part of the grandine-lint
# suite: tests/test_lint.py::test_lint_clean_on_repo covers it (with the
# rest of the rules) through `python -m tools.lint`.
