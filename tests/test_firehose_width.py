"""One width bucket a node: `AttestationVerifier` keeps a width floor (the
widest committee of any device call it has built, kept where it reaches a
higher member bucket) and names it beside the batch bucket in every call,
so a node that gets single votes AND aggregates into its one queue runs
one executable: the aggregates', from the first aggregate on.

Two parts, with the generator and the stubs of
tests/test_firehose_isolation.py and tests/test_firehose_padding.py:
(a) over the recording stub of the device seam, on a 4,160-validator
    minimal-preset chain (8 slots x 4 committees of exactly 130, the
    mainnet committee's width): what first passes and probes name as
    `bucket_floor` before and after the first aggregate, the flight row's
    fields, the root span's attributes, the five series, eight submitter
    threads under a 10 us switch interval;
(b) over the REAL kernel on the CPU, through the served entry (`submit`
    -> delivery), at the smallest width pair the minimal preset gives
    (168 validators: committees of 5-6, member bucket 8, beside the
    votes' 4; batches of 4, so the executable is the 4 x 8 that
    tests/benchmark_harness/test_mixed_rehearsal.py compiles too): a
    mixed batch of votes and aggregates, honest and with one forged of
    each kind, and a batch of votes alone AFTER it, against the program's
    host anchor and the benchmark's plain reference item for item.
"""

import contextlib
import dataclasses
import sys
import threading
from types import SimpleNamespace

import pytest
from test_firehose_isolation import (
    CFG,
    IDX,
    RANDAO_MIX,
    SHAPES,
    SLOT,
    anchor_verdict,
    reference_says,
    wire,
)
from test_firehose_padding import FloorBackend

from benchmark.generators.attestations import AttestationTraffic, ChainIdentity
from benchmark.generators.keys import MessageSigner, ProgressionKeys
from benchmark.reference import bls as ref
from grandine_tpu.consensus import accessors
from grandine_tpu.consensus.verifier import NullVerifier
from grandine_tpu.fork_choice.store import Tick, TickKind
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime import AttestationVerifier, Controller
from grandine_tpu.runtime.attestation_verifier import _width_bucket
from grandine_tpu.runtime.flight import BATCH
from grandine_tpu.tpu import compile_scope
from grandine_tpu.tpu.bls import _bucket
from grandine_tpu.tracing import Tracer
from grandine_tpu.transition.fork_upgrade import state_phase
from grandine_tpu.transition.genesis import interop_genesis_state
from grandine_tpu.types.containers import spec_types

kernel = pytest.mark.kernel
SEED = 32
AGGREGATES = {"members": "aggregate", "aggregators_per_committee": 2,
              "missing_members_max": 1}


def make_chain(n: int, votes_of: int):
    """Genesis over the generator's keys; of slot SLOT the aggregates (two
    a committee, each missing 0 or 1 member) and the single votes of its
    first `votes_of` committees."""
    keys = ProgressionKeys(n, SEED)
    genesis = interop_genesis_state(n, CFG, eth1_block_hash=RANDAO_MIX,
                                    pubkeys=keys.pubkey_bytes())
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier)
    try:
        head = ctrl.snapshot()
        state = head.head_state
        ident = ChainIdentity(
            genesis_validators_root=bytes(state.genesis_validators_root),
            fork_version=bytes(state.fork.current_version),
            anchor_root=bytes(head.head_root), randao_mix=RANDAO_MIX,
        )
    finally:
        ctrl.stop()
    traffic = AttestationTraffic(AGGREGATES, SHAPES, keys, ident, SEED)
    aggregates = traffic.slot_items(SLOT)
    votes = []
    for agg in aggregates:
        if agg.index >= votes_of or any(v.index == agg.index for v in votes):
            continue
        committee = traffic.committees.committee(SLOT, agg.index)
        signer = MessageSigner(keys, agg.message)
        for pos, v in enumerate(committee[:40]):
            bits = [False] * len(committee)
            bits[pos] = True
            votes.append(dataclasses.replace(
                agg, bits=bits, members=[v],
                signature=ref.g2_to_bytes(signer.single(v))))
    return keys, genesis, votes, aggregates


@pytest.fixture(scope="module")
def wide_chain():
    """4,160 validators: every committee has exactly 130 members."""
    keys, genesis, votes, aggregates = make_chain(4160, votes_of=2)
    assert {len(a.members) for a in aggregates} == {129, 130}
    assert len(votes) == 80
    return keys, genesis, votes, aggregates


@contextlib.contextmanager
def node(genesis, backend=None, **sizes):
    """A verifier over `backend` (None: the real kernel), registry in
    sync, at the items' slot. `send(items)` is ONE `submit_many` call and
    waits for its verdicts; `delivered` fills with each verdict's
    attesting indices in delivery order."""
    metrics, tracer = Metrics(), Tracer()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics, tracer=tracer)
    verifier = AttestationVerifier(ctrl, backend=backend, **sizes)
    delivered = []
    inner = ctrl.on_valid_attestation_batch

    def deliver(valids):
        delivered.extend(tuple(int(i) for i in v.indices) for v in valids)
        return inner(valids)

    ctrl.on_valid_attestation_batch = deliver

    def rows():
        ctrl.wait()
        return [r.as_dict()
                for r in verifier.flight.snapshot(lane="attestation")
                if r.kind == BATCH]

    try:
        state = ctrl.snapshot().head_state
        assert verifier.registry.ensure(
            accessors.registry_columns(state).pubkeys)
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()

        def send(items):
            verifier.submit_many([wire(ns, it) for it in items])
            verifier.flush(timeout=600.0)

        yield SimpleNamespace(
            verifier=verifier, metrics=metrics, tracer=tracer,
            delivered=delivered, rows=rows, send=send, ns=ns)
    finally:
        verifier.stop()
        ctrl.stop()


def all_valid():
    return FloorBackend(lambda message, sig_bytes, indices: True)


# -- (a) what reaches the seam --------------------------------------------

def test_the_width_bucket_is_the_kernels_own():
    assert [_width_bucket(w) for w in range(0, 2049)] == [
        _bucket(w, lo=4) for w in range(0, 2049)]


def test_votes_alone_name_no_width_and_stay_there(wide_chain):
    """A verifier that has only ever seen single votes: every first pass
    names (64, 0), the votes' own member bucket 4, as before the floor."""
    _keys, genesis, votes, _aggregates = wide_chain
    backend = all_valid()
    with node(genesis, backend) as n:
        n.send(votes[:70])
        n.send(votes[70:71])
        n.send(votes[71:76])
        assert backend.floors == [(64, 0)] * 4
        assert {(k, s) for k, s, _n in backend.calls} == {(IDX, (64, 4))}
        assert (n.verifier.width_floor, n.verifier.width_bucket) == (0, 4)
        m = n.metrics
        assert m.att_width_floor_raised.value == 0
        assert m.att_width_bucket.value == 4
        assert m.att_mixed_batches.value == 0
        assert m.att_first_pass_members.value == 76
        assert m.att_first_pass_member_slots.value == 4 * 64 * 4
        assert [(r["width"], r["width_min"], r["width_bucket"])
                for r in n.rows()] == [(1, 1, 4)] * 4
        assert len(n.delivered) == 76


def test_one_aggregate_moves_every_later_call_to_its_bucket(wide_chain):
    """After one 130-member item every later first pass, a batch of ONE
    vote included, names (64, 130): member bucket 256, one executable. A
    later aggregate of the same bucket leaves the floor where it is."""
    _keys, genesis, votes, aggregates = wide_chain
    full = next(a for a in aggregates if len(a.members) == 130)
    short = next(a for a in aggregates if len(a.members) == 129)
    backend = all_valid()
    with node(genesis, backend) as n:
        n.send(votes[:1])
        assert backend.floors == [(64, 0)]
        n.send([full])
        assert backend.floors[-1] == (64, 130)
        assert (n.verifier.width_floor, n.verifier.width_bucket) == (130, 256)
        for later in (votes[1:2], votes[2:7], [short], votes[7:71]):
            n.send(later)
            assert backend.floors[-1] == (64, 130)
            assert backend.calls[-1][:2] == (IDX, (64, 256))
        assert len(backend.calls) == 6
        assert n.verifier.width_floor == 130
        m = n.metrics
        assert m.att_width_floor_raised.value == 1
        assert m.att_width_bucket.value == 256
        assert m.att_mixed_batches.value == 0
        rows = n.rows()
        assert [(r["items"], r["width"], r["width_min"], r["width_bucket"])
                for r in rows[:5]] == [
            (1, 1, 1, 4), (1, 130, 130, 256), (1, 1, 1, 256),
            (5, 1, 1, 256), (1, 129, 129, 256)]
        assert {r["bucket"] for r in rows} == {64}
        members = 1 + 130 + 1 + 5 + 129 + 64
        assert m.att_first_pass_members.value == members
        assert m.att_first_pass_member_slots.value == 64 * 4 + 5 * 64 * 256
        assert len(n.delivered) == 1 + 1 + 1 + 5 + 1 + 64


def test_a_mixed_batch_is_nothing_special(wide_chain):
    """Votes beside aggregates in one batch: one call in the aggregates'
    bucket, counted as mixed, every item its own verdict; the row and the
    batch's root span say how wide it was."""
    _keys, genesis, votes, aggregates = wide_chain
    batch = [votes[0], aggregates[0], votes[1], aggregates[1], votes[2]]
    backend = all_valid()
    with node(genesis, backend) as n:
        n.send(batch)
        assert backend.calls == [(IDX, (64, 256), 5)]
        widest = max(len(it.members) for it in batch)
        assert backend.floors == [(64, widest)]
        assert n.delivered == [tuple(it.members) for it in batch]
        m = n.metrics
        assert m.att_mixed_batches.value == 1
        assert m.att_width_floor_raised.value == 1
        (row,) = n.rows()
        assert (row["width"], row["width_min"], row["width_bucket"]) == (
            widest, 1, 256)
        (root,) = [s for s in n.tracer.finished_spans()
                   if s.name == "verify_batch"]
        assert (root.attrs["width"], root.attrs["width_min"],
                root.attrs["width_bucket"]) == (widest, 1, 256)
        assert root.trace_id == row["trace_id"]
        text = m.expose()
        for line in ("attestation_first_pass_members_total "
                     f"{float(sum(len(it.members) for it in batch))}",
                     f"attestation_first_pass_member_slots_total "
                     f"{float(64 * 256)}",
                     "attestation_width_floor_raised_total 1.0",
                     "attestation_width_bucket 256.0",
                     "attestation_mixed_batches_total 1.0"):
            assert line in text, line


def test_a_probe_names_the_floor_or_its_parent_whichever_is_wider(
        wide_chain):
    """A probe of a failed batch names max(the floor, its parent's widest
    committee): a failed batch of votes after an aggregate is probed in
    the aggregates' bucket (the floor), and a failed batch that holds a
    130-member aggregate on a floor of 129 names 130 (its parent)."""
    _keys, genesis, votes, aggregates = wide_chain
    full = next(a for a in aggregates if len(a.members) == 130)
    short = next(a for a in aggregates if len(a.members) == 129)
    bad = {votes[3].signature, full.signature}
    backend = FloorBackend(
        lambda message, sig_bytes, indices: sig_bytes not in bad)
    with node(genesis, backend) as n:
        n.send([short])
        assert backend.floors == [(64, 129)]
        n.send(votes[:5])
        first, probes = backend.floors[1], backend.floors[2:]
        assert first == (64, 129)
        assert probes and set(probes) == {(64, 129)}
        seen = len(backend.floors)
        n.send([votes[5], full, votes[6]])
        # 130 lies in 129's bucket: the floor stays, the first pass names
        # it, the probes their parent
        assert backend.floors[seen] == (64, 129)
        assert n.verifier.width_floor == 129
        probes = backend.floors[seen + 1:]
        assert probes and set(probes) == {(64, 130)}
        assert {s for _k, s, _n in backend.calls} == {(64, 256)}
        assert n.metrics.att_width_floor_raised.value == 1
        assert sorted(n.delivered) == sorted(
            tuple(it.members) for it in
            [short] + votes[:3] + votes[4:7])


def test_the_host_path_records_the_widths_and_no_bucket(wide_chain):
    _keys, genesis, votes, aggregates = wide_chain
    metrics = Metrics()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics)
    verifier = AttestationVerifier(ctrl, use_device=False)
    verifier._batch_check = lambda prepared, parent=None: True
    try:
        state = ctrl.snapshot().head_state
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()
        verifier.submit_many([wire(ns, it)
                              for it in (votes[0], aggregates[0])])
        verifier.flush(timeout=120.0)
        (row,) = [r.as_dict()
                  for r in verifier.flight.snapshot(lane="attestation")
                  if r.kind == BATCH]
        assert (row["width"], row["width_min"], row["width_bucket"]) == (
            len(aggregates[0].members), 1, 0)
        assert verifier.width_floor == 0
        assert metrics.att_mixed_batches.value == 1
        assert metrics.att_first_pass_member_slots.value == 0
    finally:
        verifier.stop()
        ctrl.stop()


def test_eight_submitters_leave_the_floor_monotone(wide_chain):
    """Eight threads submit votes and aggregates one by one under a 10 us
    switch interval, batches of 4 on several pool threads: the floor never
    falls, its bucket moves once, every item gets one verdict."""
    _keys, genesis, votes, aggregates = wide_chain
    items = votes[:56] + aggregates
    shares = [items[k::8] for k in range(8)]
    backend = all_valid()
    seen, stop = [], threading.Event()
    old = sys.getswitchinterval()
    with node(genesis, backend, max_batch=4, deadline_s=0.002) as n:
        def watch():
            while not stop.is_set():
                floor = n.verifier.width_floor
                if not seen or seen[-1] != floor:
                    seen.append(floor)

        def submit(share):
            for it in share:
                n.verifier.submit(wire(n.ns, it))

        watcher = threading.Thread(target=watch)
        threads = [threading.Thread(target=submit, args=(s,)) for s in shares]
        sys.setswitchinterval(1e-5)
        try:
            watcher.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            n.verifier.flush(timeout=120.0)
        finally:
            stop.set()
            watcher.join()
            sys.setswitchinterval(old)
        assert seen == sorted(seen) and seen[-1] in (129, 130)
        assert set(seen) <= {0, 129, 130}
        assert n.metrics.att_width_floor_raised.value == 1
        assert n.metrics.att_width_bucket.value == 256
        # a call names the floor as it stood when the call was built
        assert {f for _b, f in backend.floors} <= {0, 129, 130}
        assert all(b == 4 for b, _f in backend.floors)
        assert sorted(n.delivered) == sorted(
            tuple(it.members) for it in items)
        rows = n.rows()
        assert sum(r["items"] for r in rows) == len(items)
        assert all(r["width_bucket"] in (4, 256) for r in rows)
        assert n.verifier.stats["accepted"] == len(items)


# -- (b) the real kernel, through the served entry -------------------------

@pytest.fixture(scope="module")
def small_chain():
    """168 validators: committees of 5 or 6 (member bucket 8)."""
    keys, genesis, votes, aggregates = make_chain(168, votes_of=4)
    assert {len(a.members) for a in aggregates} <= {4, 5, 6}
    assert len(votes) == 21
    return keys, genesis, votes, aggregates


def forge(keys, item):
    """`item`'s signers over another root: it decompresses, lies in G2,
    passes prevalidation, and only the pairing refuses it."""
    other = bytes(a ^ 0xFF for a in item.message)
    point = MessageSigner(keys, other).aggregate(item.members)
    return dataclasses.replace(item, signature=ref.g2_to_bytes(point))


@kernel
def test_votes_and_aggregates_in_one_batch_get_the_anchors_verdicts(
        small_chain):
    """Through `submit` -> delivery in the 4 x 8 executable (its first use
    compiles it: ~1.5 min on the CPU, then the persistent cache has it):
    a batch of two votes and two aggregates; three votes ALONE after it,
    which the floor keeps in the same executable (nothing compiles); a
    mixed batch with one forged vote and one forged aggregate, isolated in
    that executable too. Delivered = exactly what the host anchor and the
    benchmark's plain reference accept, item for item."""
    keys, genesis, votes, aggregates = small_chain
    wide = [a for a in aggregates if len(a.members) >= 5]
    honest = [votes[0], wide[0], votes[1], wide[1]]
    alone = votes[2:5]
    hostile = [forge(keys, votes[5]), wide[2], votes[6],
               forge(keys, wide[3])]
    anchor = anchor_verdict(keys)
    for batch in (honest, alone, hostile):
        for it in batch:
            says = anchor(it.message, it.signature, tuple(it.members))
            assert says == reference_says(keys, it)
            assert says == (it is not hostile[0] and it is not hostile[3])
    # a settle deadline no loaded CPU worker misses: a watchdog expiry
    # would send the batch to the host twin and prove nothing
    with node(genesis, max_batch=4, settle_timeout_s=300.0) as n:
        n.send(honest)
        assert n.delivered == [tuple(it.members) for it in honest]
        assert (n.verifier.width_bucket, n.verifier.batch_bucket) == (8, 4)
        compiled = compile_scope.totals()[1]
        n.send(alone)
        n.send(hostile)
        assert compile_scope.totals()[1] == compiled
        assert n.delivered[4:7] == [tuple(it.members) for it in alone]
        assert n.delivered[7:] == [tuple(it.members)
                                   for it in (hostile[1], hostile[2])]
        stats = n.verifier.stats
        assert (stats["accepted"], stats["rejected"]) == (9, 2)
        assert stats["fallbacks"] == 1 and stats["retries"] == 0
        assert stats.get("settle_errors", 0) == 0
        rows = n.rows()
        assert [(r["items"], r["width_min"], r["width_bucket"], r["verdict"])
                for r in rows] == [(4, 1, 8, True), (3, 1, 8, True),
                                   (4, 1, 8, False)]
        assert rows[1]["width"] == 1 and rows[0]["width"] >= 5
        assert all(r["host_s"] == 0 and r["fault"] is None for r in rows)
        m = n.metrics
        assert m.att_mixed_batches.value == 2
        assert m.att_width_floor_raised.value == 1
        assert m.att_first_pass_member_slots.value == 3 * 4 * 8
        # every device call was the indexed kernel: first passes + probes
        assert m.device_kernel_calls.value("agg_fast_verify_msm_idx") == (
            3 + rows[2]["probes"])
        assert rows[2]["probes"] > 0
