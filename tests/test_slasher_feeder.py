"""The firehose's slasher feeder: the thread that feeds the slasher once a
batch's verdicts are delivered (`AttestationVerifier._feedback` hands the
accepted pairs over, `_feed_once` feeds them). Driven at `_feedback`, the
seam the completion thread and the host path both call, over a slasher
whose bulk call can be held at a gate. No JAX."""

import random
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from grandine_tpu.fork_choice.store import ValidAttestation
from grandine_tpu.metrics import Metrics
from grandine_tpu.pools import OperationPool
from grandine_tpu.runtime.attestation_verifier import AttestationVerifier
from grandine_tpu.slasher import Slasher
from grandine_tpu.tracing import Tracer
from grandine_tpu.transition.genesis import interop_genesis_state
from grandine_tpu.types.config import Config
from grandine_tpu.types.containers import spec_types

CFG = Config.minimal()
NS = spec_types(CFG.preset).deneb


@pytest.fixture(scope="module")
def genesis():
    return interop_genesis_state(16, CFG)


class Node:
    """What the verifier reads of its controller, and where it delivers."""

    def __init__(self, genesis) -> None:
        self.cfg = CFG
        self.pool = SimpleNamespace(n_threads=1)
        self.delivered: "list[list]" = []
        self._snapshot = SimpleNamespace(head_state=genesis)

    def snapshot(self):
        return self._snapshot

    def on_valid_attestation_batch(self, valids) -> None:
        self.delivered.append(list(valids))


class GatedSlasher(Slasher):
    """A slasher whose bulk call records what it was given and waits at
    `gate` while it is closed; `fail` makes the next call raise."""

    def __init__(self, metrics) -> None:
        super().__init__(metrics=metrics)
        self.calls: "list[list]" = []
        self.entered = threading.Event()
        self.gate = threading.Event()
        self.gate.set()
        self.fail = False

    def on_attestations_bulk(self, attestations):
        attestations = list(attestations)
        self.calls.append(attestations)
        self.entered.set()
        assert self.gate.wait(10), "the test never opened the gate"
        if self.fail:
            self.fail = False
            raise RuntimeError("slasher storage is gone")
        return super().on_attestations_bulk(attestations)


def att(indices, source, target, tag):
    """An attestation of `indices` as the feed sees it: (attestation,
    ValidAttestation), its data root fixed by (source, target, tag)."""
    root = bytes([tag]) * 32
    a = NS.Attestation(
        aggregation_bits=np.ones(len(indices), dtype=bool),
        data=NS.AttestationData(
            slot=target * CFG.preset.SLOTS_PER_EPOCH, index=0,
            beacon_block_root=root,
            source=NS.Checkpoint(epoch=source, root=root),
            target=NS.Checkpoint(epoch=target, root=root),
        ),
        signature=b"\x00" * 96,
    )
    return a, ValidAttestation(list(indices), target, root, 0)


def prepared(batch):
    """A batch as `_feedback` takes it: `_prevalidate`'s tuple, of which
    the feed reads the ValidAttestation (3) and the attestation (4)."""
    return [(None, None, None, valid, a) for a, valid in batch]


def verifier_for(genesis, pipeline_depth=2, metrics=None, tracer=None):
    metrics = metrics or Metrics()
    pool = OperationPool(CFG)
    v = AttestationVerifier(
        Node(genesis), use_device=False, max_active=1,
        pipeline_depth=pipeline_depth, slasher=GatedSlasher(metrics),
        operation_pool=pool, metrics=metrics, tracer=tracer,
    )
    return v, pool


def fed_indices(slasher):
    return [tuple(ix) for call in slasher.calls for ix, _s, _t, _r in call]


def batches_of(seed: int) -> "list[list]":
    """Six batches of three attestations over validators 0-15 drawn from
    `seed`, then a double vote and a surround that span two batches:
    validators 20 and 21 vote (2, 5) in one batch, and in the next 20
    votes (2, 5) for another root and 21 votes (1, 6)."""
    rng = random.Random(seed)
    out = []
    for _ in range(6):
        batch = []
        for _ in range(3):
            s = rng.randrange(4)
            batch.append(att(rng.sample(range(16), 3), s,
                             s + 1 + rng.randrange(3), rng.randrange(2)))
        out.append(batch)
    out.append([att([20, 21], 2, 5, 0x20)])
    out.append([att([20], 2, 5, 0x21), att([21], 1, 6, 0x20)])
    return out


def test_batches_are_fed_in_delivery_order(genesis):
    v, _pool = verifier_for(genesis)
    try:
        batches = batches_of(7)
        for batch in batches:
            v._feedback(prepared(batch))
        v.flush(timeout=10)
        delivered = [tuple(valid.indices)
                     for group in v.controller.delivered for valid in group]
        want = [tuple(valid.indices) for b in batches for _a, valid in b]
        assert delivered == want
        assert fed_indices(v.slasher) == want
        assert v.stats.get("slasher_errors", 0) == 0
    finally:
        v.stop()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_queued_batches_are_one_call_that_finds_what_one_call_each_finds(
        genesis, seed):
    batches = batches_of(seed)
    roots = [bytes(a.data.hash_tree_root()) for b in batches for a, _ in b]
    # the evidence store keeps four aggregates a data root: a sequence
    # that keeps fewer is judged the same whichever call sees it first
    assert max(roots.count(r) for r in set(roots)) <= 4
    # one slasher call a batch, synchronously: what the feed did before
    ref, ref_pool = verifier_for(genesis)
    try:
        for batch in batches:
            ref._feed_slasher([(a, valid) for a, valid in batch])
    finally:
        ref.stop()
    kinds = {hit.kind for hit in ref.slasher.detected}
    assert {"double_vote", "surround_vote"} <= kinds, kinds

    metrics, tracer = Metrics(), Tracer()
    v, pool = verifier_for(genesis, pipeline_depth=len(batches) - 1,
                           metrics=metrics, tracer=tracer)
    try:
        roots = [tracer.span("verify_batch") for _ in batches]
        slasher = v.slasher
        slasher.gate.clear()
        with tracer.attach(roots[0]):
            v._feedback(prepared(batches[0]))
        assert slasher.entered.wait(10)
        for root, batch in zip(roots[1:], batches[1:]):
            with tracer.attach(root):
                v._feedback(prepared(batch))
        slasher.gate.set()
        v.flush(timeout=10)
        # the first batch alone, then every batch queued behind it in
        # ONE bulk call and ONE storage transaction
        assert [len(call) for call in slasher.calls] == [
            len(batches[0]), sum(len(b) for b in batches[1:])]
        assert metrics.slasher_storage_commits.value == 2
        assert metrics.att_slasher_feed_calls.value == 2
        assert metrics.att_slasher_feed_batches.value == len(batches)
        assert metrics.att_slasher_feed_blocked.value == 0
        # what they found, and the ops built from it, are the same
        assert [(h.kind, h.validator_index, h.evidence)
                for h in slasher.detected] == [
            (h.kind, h.validator_index, h.evidence)
            for h in ref.slasher.detected]
        got = [bytes(s.hash_tree_root())
               for s in pool.contents()["attester_slashings"]]
        want = [bytes(s.hash_tree_root())
                for s in ref_pool.contents()["attester_slashings"]]
        assert got == want and got
        assert v.stats["slashings_emitted"] == ref.stats["slashings_emitted"]
        # the feed's stage: ms a batch summed over calls, its span under
        # the first batch of its call, naming how many it fed
        feeds = [s for s in tracer.finished_spans()
                 if s.name == "feedback" and s.attrs["op"] == "slasher_feed"]
        assert [(s.parent_id, s.attrs["batches"]) for s in feeds] == [
            (roots[0].span_id, 1), (roots[1].span_id, len(batches) - 1)]
        stage = metrics.verify_stage_seconds.children()[
            ("feedback", "attestation", "slasher_feed")]
        assert stage.count == 2
    finally:
        v.stop()


def test_a_hand_over_beyond_the_bound_blocks_and_is_counted(genesis):
    v, _pool = verifier_for(genesis, pipeline_depth=2)
    try:
        batches = batches_of(11)[:4]
        slasher = v.slasher
        slasher.gate.clear()
        v._feedback(prepared(batches[0]))
        assert slasher.entered.wait(10)
        # pipeline_depth batches wait for the feeder without blocking
        for batch in batches[1:3]:
            v._feedback(prepared(batch))
        assert v.metrics.att_slasher_feed_blocked.value == 0
        # the pipeline_depth + 1-th waits for room
        late = threading.Thread(target=v._feedback,
                                args=(prepared(batches[3]),))
        late.start()
        late.join(0.3)
        assert late.is_alive()
        assert v.metrics.att_slasher_feed_blocked.value == 1
        # delivery came first all the same
        assert len(v.controller.delivered) == 4
        slasher.gate.set()
        late.join(10)
        assert not late.is_alive()
        v.flush(timeout=10)
        assert fed_indices(slasher) == [
            tuple(valid.indices) for b in batches for _a, valid in b]
        assert v.metrics.att_slasher_feed_blocked.value == 1
    finally:
        v.stop()


@pytest.mark.parametrize("barrier", ["flush", "stop"])
def test_flush_and_stop_return_only_once_the_last_batch_is_fed(
        genesis, barrier):
    v, _pool = verifier_for(genesis, pipeline_depth=2)
    batches = batches_of(5)[:3]
    slasher = v.slasher
    slasher.gate.clear()
    v._feedback(prepared(batches[0]))
    assert slasher.entered.wait(10)
    for batch in batches[1:]:
        v._feedback(prepared(batch))
    waiter = threading.Thread(target=getattr(v, barrier))
    waiter.start()
    waiter.join(0.3)
    assert waiter.is_alive(), f"{barrier}() returned with batches unfed"
    slasher.gate.set()
    waiter.join(15)
    assert not waiter.is_alive()
    assert fed_indices(slasher) == [
        tuple(valid.indices) for b in batches for _a, valid in b]
    if barrier == "flush":
        v.stop()
    assert not v._feeder.is_alive()


def test_a_slasher_that_raises_is_counted_and_the_feeder_goes_on(genesis):
    v, _pool = verifier_for(genesis)
    try:
        first, second = batches_of(13)[-2:]
        v.slasher.fail = True
        v._feedback(prepared(first))
        v.flush(timeout=10)
        assert v.stats["slasher_errors"] == 1
        v._feedback(prepared(second))
        v.flush(timeout=10)
        assert v.stats["slasher_errors"] == 1
        assert len(v.slasher.calls) == 2
        assert v._feeder.is_alive()
        # the second call went through: its records are the slasher's
        assert v.slasher.record_for(21, 6) is not None
        assert v.metrics.att_slasher_feed_calls.value == 2
    finally:
        v.stop()


def test_no_slasher_no_feeder(genesis):
    node = Node(genesis)
    v = AttestationVerifier(node, use_device=False, max_active=1)
    try:
        assert v._feeder is None
        v._feedback(prepared(batches_of(3)[0]))
        assert len(node.delivered) == 1
        v.flush(timeout=1)
    finally:
        v.stop()


def test_many_delivering_threads_feed_every_batch_once(genesis):
    """Eight delivering threads (the host path's pool threads) under a
    10 us switch interval: every batch fed exactly once, each thread's
    in its own order, and the count `flush` waits on back at zero."""
    import sys

    v, _pool = verifier_for(genesis, pipeline_depth=2)
    threads, per = 8, 25
    batches = {t: [[att([1000 * t + k], 0, 1, 0x30)] for k in range(per)]
               for t in range(threads)}

    def deliver(t):
        for batch in batches[t]:
            v._feedback(prepared(batch))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=deliver, args=(t,))
                   for t in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(30)
        assert not any(w.is_alive() for w in workers)
        v.flush(timeout=30)
    finally:
        sys.setswitchinterval(interval)
        v.stop()
    fed = [ix[0] for ix in fed_indices(v.slasher)]
    assert sorted(fed) == sorted(1000 * t + k for t in range(threads)
                                 for k in range(per))
    for t in range(threads):
        mine = [i for i in fed if i // 1000 == t]
        assert mine == sorted(mine)
    assert v.metrics.att_slasher_feed_batches.value == threads * per
    assert v._unfed == 0
