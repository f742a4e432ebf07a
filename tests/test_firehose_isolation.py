"""The firehose's descent over a failed batch (`AttestationVerifier.
_isolate`): one probe a level inside the batch's own padded bucket and
executable, the halves it passes over cleared in one call.

Single votes of one slot of a 512-validator minimal-preset chain (64 a
slot), made by the benchmark's generator and forged as the hostile cell
forges them (the named validator's own signature over another root: it
decompresses, lies in G2, passes prevalidation, and only the pairing
refuses it). The backend is a recording stub of the device seam: it
answers from a per-item verdict (the program's host anchor for the small
batches that are held against the benchmark's plain reference, the forged
labels for the batch of 64) and writes down every call's kernel and
padded shape. `run_batch` also writes down every probe as the descent
asked for it (positions in the batch, verdict), and every case is held to
the schedule's two invariants: a delivered item lay in a probe that
verified, a rejected item was refused by a probe of that item alone.
"""

import dataclasses
import random

import numpy as np
import pytest

from benchmark.generators.attestations import (
    AttestationTraffic,
    ChainIdentity,
    reference_verdict,
)
from benchmark.generators.keys import MessageSigner, ProgressionKeys
from benchmark.reference import bls as ref
from grandine_tpu.consensus.verifier import NullVerifier
from grandine_tpu.crypto import bls as A
from grandine_tpu.fork_choice.store import Tick, TickKind
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime import AttestationVerifier, Controller
from grandine_tpu.runtime.flight import BATCH
from grandine_tpu.tpu import compile_scope
from grandine_tpu.tpu.bls import _bucket as bucket
from grandine_tpu.tracing import Tracer
from grandine_tpu.transition.genesis import interop_genesis_state
from grandine_tpu.types.config import Config

CFG = Config.minimal()
SHAPES = {"SLOTS_PER_EPOCH": 8, "TARGET_COMMITTEE_SIZE": 4,
          "MAX_COMMITTEES_PER_SLOT": 4, "SHUFFLE_ROUND_COUNT": 10}
N, SEED, SLOT = 512, 26, 1
RANDAO_MIX = b"\x42" * 32
IDX, UPLOAD = "agg_fast_verify_msm_idx", "agg_fast_verify_msm"


class RecordingBackend:
    """The device seam, fused, with no kernel: a call is valid when every
    item in it is (`verdict(message, signature bytes, member indices)`).
    `calls` holds (kernel, (batch bucket, member bucket), items) per call,
    the shape as tpu/bls.py `_aggregate_bucket` pads it. The upload entry
    answers only where `index_of` (compressed key -> validator index) is
    given: a verifier with no registry."""

    fuse_subgroup = True

    def __init__(self, verdict, index_of=None) -> None:
        self.verdict, self.calls = verdict, []
        self.index_of = index_of

    def g2_subgroup_check_batch_async(self, points):
        raise AssertionError("fused: never called")

    def fast_aggregate_verify_batch(self, *a, **kw):
        raise AssertionError("the non-indexed synchronous entry is off "
                             "the verifier's path")

    def _call(self, kernel, messages, sigs, widths, floor):
        fm, fk = floor or (0, 0)
        shape = (bucket(max(len(messages), fm)),
                 bucket(max(max(widths), fk)))
        self.calls.append((kernel, shape, len(messages)))

    def fast_aggregate_verify_batch_indexed_async(
            self, messages, sigs, indices, registry, bucket_floor=None):
        self._call(IDX, messages, sigs, [len(ix) for ix in indices],
                   bucket_floor)
        ok = all(self.verdict(m, A.g2_to_bytes(s.point), tuple(ix))
                 for m, s, ix in zip(messages, sigs, indices))
        return lambda: ok

    def fast_aggregate_verify_batch_async(self, messages, sigs, members,
                                          bucket_floor=None):
        self._call(UPLOAD, messages, sigs, [len(ks) for ks in members],
                   bucket_floor)
        if self.index_of is None:
            raise AssertionError("the registry is in sync: the upload "
                                 "entry is not taken")
        ok = all(self.verdict(m, A.g2_to_bytes(s.point),
                              tuple(self.index_of[k.to_bytes()] for k in ks))
                 for m, s, ks in zip(messages, sigs, members))
        return lambda: ok


@pytest.fixture(scope="module")
def chain():
    """Genesis over the generator's keys, one slot's 64 single votes, and
    a forger of any of them."""
    keys = ProgressionKeys(N, SEED)
    genesis = interop_genesis_state(N, CFG, eth1_block_hash=RANDAO_MIX,
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
    traffic = AttestationTraffic({"members": "single"}, SHAPES, keys, ident,
                                 SEED)
    items = traffic.slot_items(SLOT)
    assert len(items) == 64
    return keys, genesis, items


def forge(keys, item):
    """`item`'s validator signing another root."""
    other = bytes(a ^ 0xFF for a in item.message)
    point = MessageSigner(keys, other).single(item.members[0])
    return dataclasses.replace(item, signature=ref.g2_to_bytes(point))


def wire(ns, item):
    return ns.Attestation(
        aggregation_bits=np.asarray(item.bits, dtype=bool),
        data=ns.AttestationData(
            slot=item.slot, index=item.index,
            beacon_block_root=item.beacon_block_root,
            source=ns.Checkpoint(epoch=item.source[0], root=item.source[1]),
            target=ns.Checkpoint(epoch=item.target[0], root=item.target[1]),
        ),
        signature=item.signature,
    )


def held(items, delivered, probes, unkeyed=()) -> None:
    """The two invariants, on the probes as the descent made them, and
    the batch's own order in what was delivered. `unkeyed`: positions a
    check off the registry path refused for a key that does not
    decompress, rejected on their own without a probe."""
    place = {it.members[0]: i for i, it in enumerate(items)}
    got = [place[v] for v in delivered]
    assert got == sorted(set(got))
    assert not set(got) & set(unkeyed)
    if not probes:  # the first pass verified: no descent
        assert len(got) + len(unkeyed) == len(items)
        return
    verified = {i for part, ok in probes if ok for i in part}
    assert set(got) <= verified
    for i in set(range(len(items))) - set(got) - set(unkeyed):
        assert ((i,), False) in probes
        assert i not in verified


def todays_probes(n, bad) -> int:
    """Probes of the schedule before PR 27 (both halves at every level),
    which the new one may exceed by at most one probe a bad item."""
    def descend(lo, hi):
        mid = lo + (hi - lo) // 2
        return sum(
            1 + (descend(a, b) if b - a > 1 and any(a <= i < b for i in bad)
                 else 0)
            for a, b in ((lo, mid), (mid, hi)))
    return 1 if n == 1 else descend(0, n)


def run_batch(genesis, items, verdict, path="registry", unkeyed=()):
    """`items` as ONE batch through a verifier over the recording backend:
    by `path` with the registry in sync ("registry"), with no registry
    ("upload") or with the breaker OPEN ("host": the host anchor, real
    crypto). Returns what was delivered (validator index), the verifier's
    stats, the backend, the metrics, spans and flight rows, the descent's
    probes as (positions, verdict), the growth of the compile scope's
    count and of the process-wide key cache over the batch. Asserts the
    invariants (`held`, with `unkeyed` its positions refused for a key)."""
    from grandine_tpu.consensus import accessors
    from grandine_tpu.consensus import keys as key_cache
    from grandine_tpu.runtime import health as _health
    from grandine_tpu.transition.fork_upgrade import state_phase
    from grandine_tpu.types.containers import spec_types

    metrics, tracer = Metrics(), Tracer()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics, tracer=tracer)
    index_of = None
    if path == "upload":
        index_of = {bytes(v.pubkey): i
                    for i, v in enumerate(genesis.validators)}
    backend = RecordingBackend(verdict, index_of)
    health = _health.BackendHealthSupervisor(
        metrics=metrics, backoff_initial_s=3600.0, backoff_max_s=3600.0)
    if path == "host":
        for _ in range(health.breaker.fault_threshold):
            health.record_fault("dispatch")
        assert health.state == _health.OPEN
    verifier = AttestationVerifier(
        ctrl, backend=backend, use_device=True, max_batch=len(items),
        deadline_s=0.5, use_registry=path != "upload", health=health,
    )
    delivered = []
    inner = ctrl.on_valid_attestation_batch

    def deliver(valids):
        delivered.extend(int(v.indices[0]) for v in valids)
        return inner(valids)

    ctrl.on_valid_attestation_batch = deliver
    probes = []
    where = {it.signature: i for i, it in enumerate(items)}
    check = verifier._batch_check

    def recorded_check(prepared, parent=None):
        ok = False  # a ValueError inside is a refusal (`_probe`)
        try:
            ok = check(prepared, parent)
        finally:
            if parent is not None:
                probes.append((tuple(where[p[1]] for p in prepared), ok))
        return ok

    verifier._batch_check = recorded_check
    try:
        state = ctrl.snapshot().head_state
        if verifier.registry is not None:
            assert verifier.registry.ensure(
                accessors.registry_columns(state).pubkeys)
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()
        compiles0 = compile_scope.totals()[1]
        cached0 = len(key_cache._CACHE)
        verifier.submit_many([wire(ns, it) for it in items])
        verifier.flush(timeout=120.0)
        ctrl.wait()
        compiled = compile_scope.totals()[1] - compiles0
        rows = [r.as_dict()
                for r in verifier.flight.snapshot(lane="attestation")
                if r.kind == BATCH]
        held(items, delivered, probes, unkeyed)
        return {"delivered": delivered, "stats": dict(verifier.stats),
                "backend": backend, "metrics": metrics, "probes": probes,
                "spans": tracer.finished_spans(), "rows": rows,
                "compiled": compiled, "breaker": verifier.health.state,
                "keys_cached": len(key_cache._CACHE) - cached0}
    finally:
        verifier.stop()
        ctrl.stop()


# -- small batches, held against the plain reference ----------------------

_ANCHOR: dict = {}
_REFERENCE: dict = {}


def anchor_verdict(keys):
    """Per-item verdict by the program's host anchor (what the device is
    differential-tested against), computed once per distinct item."""
    def verdict(message, sig_bytes, indices):
        key = (message, sig_bytes, indices)
        if key not in _ANCHOR:
            members = [A.PublicKey.from_bytes(keys.pubkey_bytes()[i])
                       for i in indices]
            _ANCHOR[key] = bool(A.Signature.from_bytes(sig_bytes)
                                .fast_aggregate_verify(message, members))
        return _ANCHOR[key]
    return verdict


def reference_says(keys, item) -> bool:
    key = (item.message, item.signature, tuple(item.members))
    if key not in _REFERENCE:
        _REFERENCE[key] = reference_verdict(keys, item)
    return _REFERENCE[key]


@pytest.mark.parametrize("positions", [
    (), (0,), (7,), (3,), "two drawn", tuple(range(8)),
], ids=["none", "first", "last", "middle", "two", "all"])
def test_delivered_and_rejected_are_the_plain_references(chain, positions):
    """The forged vote first (every level defers, the union clears), last
    (every level infers, the confirming probe rejects) and in the middle
    (both): real crypto on both sides."""
    keys, genesis, items = chain
    if positions == "two drawn":
        rng = random.Random(f"forged|{SEED}|2")
        positions = tuple(sorted(rng.sample(range(8), 2)))
    forged = len(positions)
    batch = list(items[:8])
    for pos in positions:
        batch[pos] = forge(keys, batch[pos])
    out = run_batch(genesis, batch, anchor_verdict(keys))
    want = [it.members[0] for it in batch if reference_says(keys, it)]
    # the forged ones are exactly those the reference refuses
    assert sorted(set(range(8)) - set(positions)) == [
        i for i, it in enumerate(batch) if reference_says(keys, it)]
    assert out["delivered"] == want
    assert out["stats"]["accepted"] == 8 - forged
    assert out["stats"]["rejected"] == forged
    assert out["stats"]["fallbacks"] == (1 if forged else 0)
    assert out["stats"]["retries"] == 0
    assert out["stats"].get("settle_errors", 0) == 0
    assert out["breaker"] == "closed"
    # one kernel, one shape: the batch's own, first pass and every probe
    assert {(k, s) for k, s, _n in out["backend"].calls} == {(IDX, (8, 4))}
    assert out["compiled"] == 0
    isolated = out["metrics"].att_isolated_batches.value
    assert isolated == (1 if forged else 0)
    (row,) = out["rows"]
    assert row["probes"] == len(out["backend"].calls) - 1
    assert row["probes"] <= todays_probes(8, positions) + forged
    if forged == 1:
        (pos,) = positions
        assert row["probes"] == 3 + (pos & 1) + (pos != 7)
    assert row["verdict"] is (forged == 0)


# -- one forged vote in 64 ------------------------------------------------

def stub_batch(chain, n, positions):
    """The first `n` votes of the slot with those at `positions` forged,
    through a backend that refuses exactly the forged signatures."""
    keys, genesis, items = chain
    batch = list(items[:n])
    for pos in positions:
        batch[pos] = forge(keys, batch[pos])
    bad = {batch[pos].signature for pos in positions}
    out = run_batch(genesis, batch,
                    lambda message, sig_bytes, indices: sig_bytes not in bad)
    return batch, out


def one_forged_costs(pos: int, levels: int) -> int:
    """One probe a level, the confirming probe where the last level was
    inferred, the union probe where any level deferred."""
    return levels + (pos & 1) + (pos != 2 ** levels - 1)


@pytest.fixture(scope="module")
def one_in_64(chain):
    pos = random.Random(f"one-in-64|{SEED}").randrange(64)
    batch, out = stub_batch(chain, 64, [pos])
    return batch, pos, out


def test_one_forged_in_64_makes_a_probe_a_level(one_in_64):
    """First halves of 32, 16, 8, 4, 2, 1 items, whatever the position;
    the confirming probe of the forged vote where its last level was
    inferred, one probe of everything behind it where a level deferred."""
    batch, pos, out = one_in_64
    calls = out["backend"].calls
    assert len(calls) == 1 + one_forged_costs(pos, 6)
    assert calls[0][2] == 64
    rest = [1] * (pos & 1) + [63 - pos] * (pos != 63)
    assert sorted(n for _k, _s, n in calls[1:]) == sorted(
        [32, 16, 8, 4, 2, 1] + rest)
    assert out["delivered"] == [
        it.members[0] for i, it in enumerate(batch) if i != pos]
    assert out["stats"]["accepted"] == 63 and out["stats"]["rejected"] == 1


def test_the_descent_stays_in_the_batchs_own_executable(one_in_64):
    """With the registry in sync no call reaches the upload entry, the
    non-indexed synchronous entry or another bucket than the parent
    batch's; nothing enters the compile scope."""
    _batch, _pos, out = one_in_64
    assert {(k, s) for k, s, _n in out["backend"].calls} == {(IDX, (64, 4))}
    assert out["compiled"] == 0


def test_the_descents_counters_and_flight_row(one_in_64):
    _batch, pos, out = one_in_64
    m = out["metrics"]
    probes = one_forged_costs(pos, 6)
    items = 63 + (pos & 1) + (63 - pos)
    assert m.att_isolation_probes.value == probes
    assert m.att_isolation_probe_items.value == items
    assert m.att_isolation_probe_slots.value == probes * 64
    assert m.att_isolated_batches.value == 1
    assert m.att_fallbacks.value == 1
    # a level whose first half verified is entered by inference: the set
    # bits of the position
    assert m.att_isolation_inferred.value == bin(pos).count("1")
    assert m.att_isolation_union_probes.value("ok") == (pos != 63)
    assert m.att_isolation_union_probes.value("refused") == 0
    text = m.expose()
    for name in (f"attestation_isolation_probes_total {probes}",
                 f"attestation_isolation_probe_items_total {items}",
                 f"attestation_isolation_probe_slots_total {probes * 64}",
                 f"attestation_isolation_inferred_total {bin(pos).count('1')}",
                 "attestation_isolated_batches_total 1"):
        assert name in text
    if pos != 63:
        assert ('attestation_isolation_union_probes_total{verdict="ok"} 1'
                in text)
    (row,) = out["rows"]
    assert row["probes"] == probes and row["bisect_s"] > 0
    assert row["bisect_depth"] == 7
    assert row["verdict"] is False and row["items"] == 64


def test_the_descents_probe_spans(one_in_64):
    """One `probe` span a probe, all children of the batch's `fallback`
    stage, each saying `why` it was made; the stage's seconds are observed
    once (the probes are plain spans), so
    `verify_stage_seconds_sum{stage="fallback"}` is the whole descent and
    nothing twice."""
    _batch, pos, out = one_in_64
    spans = out["spans"]
    (fallback,) = [s for s in spans if s.name == "fallback"]
    probes = [s for s in spans if s.name == "probe"]
    assert len(probes) == one_forged_costs(pos, 6)
    assert all(s.parent_id == fallback.span_id for s in probes)
    assert all(s.attrs["op"] == "probe" and s.attrs["bucket"] == 64
               for s in probes)
    want = [("first_half", d, 64 >> d) for d in range(1, 7)]
    if pos & 1:
        want.append(("confirm", 6, 1))
    if pos != 63:
        # the union's depth is that of its largest piece: the first level
        # that deferred, the position's highest clear bit
        first_clear = next(d for d in range(1, 7) if not pos >> (6 - d) & 1)
        want.append(("union", first_clear, 63 - pos))
    assert [(s.attrs["why"], s.attrs["depth"], s.attrs["items"])
            for s in sorted(probes, key=lambda s: s.start)] == want
    assert all(fallback.start <= s.start and s.end <= fallback.end
               for s in probes)
    family = out["metrics"].verify_stage_seconds
    observed = [labels for labels in family.children()
                if labels[0] == "fallback"]
    assert observed == [("fallback", "attestation", "")]


@pytest.mark.parametrize("pos", range(64))
def test_every_position_of_the_forged_vote(chain, pos):
    """6 probes (one a level) + 1 if the last bit is set (the confirming
    probe) + 1 if any bit is clear (the union probe): 7 or 8."""
    batch, out = stub_batch(chain, 64, [pos])
    assert len(out["probes"]) == one_forged_costs(pos, 6)
    assert 7 <= len(out["probes"]) <= 8
    assert out["delivered"] == [
        it.members[0] for i, it in enumerate(batch) if i != pos]
    assert out["stats"]["accepted"] == 63 and out["stats"]["rejected"] == 1
    (row,) = out["rows"]
    assert row["probes"] == len(out["backend"].calls) - 1 == len(
        out["probes"])
    assert {(k, s) for k, s, _n in out["backend"].calls} == {(IDX, (64, 4))}


@pytest.mark.parametrize("k", [2, 3, 8, 64])
def test_several_forged_votes(chain, k):
    """The verdicts of the former schedule, in at most its probes + k."""
    positions = sorted(
        random.Random(f"several|{SEED}|{k}").sample(range(64), k))
    batch, out = stub_batch(chain, 64, positions)
    assert out["delivered"] == [
        it.members[0] for i, it in enumerate(batch) if i not in positions]
    assert out["stats"]["accepted"] == 64 - k
    assert out["stats"]["rejected"] == k
    assert len(out["probes"]) <= todays_probes(64, positions) + k
    (row,) = out["rows"]
    assert row["probes"] == len(out["probes"])
    assert out["metrics"].att_isolation_probes.value == len(out["probes"])
    assert {(k_, s) for k_, s, _n in out["backend"].calls} == {(IDX, (64, 4))}
    assert out["compiled"] == 0
    assert out["breaker"] == "closed"


@pytest.mark.parametrize("n", [1, 2, 3, 5, 63])
def test_batches_of_other_sizes(chain, n):
    """Sizes that are no power of two (the first half is the smaller) and
    the smallest: one forged vote at a seed-drawn place, then two."""
    for k in {1, min(2, n)}:
        positions = sorted(
            random.Random(f"sizes|{SEED}|{n}|{k}").sample(range(n), k))
        batch, out = stub_batch(chain, n, positions)
        assert out["delivered"] == [
            it.members[0] for i, it in enumerate(batch)
            if i not in positions]
        assert out["stats"]["rejected"] == k
        assert len(out["probes"]) <= todays_probes(n, positions) + k
        assert {(k_, s) for k_, s, _n in out["backend"].calls} == {
            (IDX, (bucket(n), 4))}
        (row,) = out["rows"]
        assert row["probes"] == len(out["probes"])


def test_a_device_that_refuses_the_batch_and_then_clears_all_of_it(chain):
    """Every first half verifies, so every level is entered by inference,
    down to one item: nothing is rejected on inference, the confirming
    probe clears it, all 64 are delivered and the `verdict` fault is
    filed."""
    keys, genesis, items = chain
    # the first item asked about sinks the first pass; nothing after it
    answers = iter([False])
    out = run_batch(genesis, list(items), lambda *a: next(answers, True))
    assert [n for _k, _s, n in out["backend"].calls] == [
        64, 32, 16, 8, 4, 2, 1, 1]
    assert [ok for _part, ok in out["probes"]] == [True] * 7
    assert out["probes"][-1][0] == (63,)
    whys = [s.attrs["why"] for s in sorted(
        (s for s in out["spans"] if s.name == "probe"),
        key=lambda s: s.start)]
    assert whys == ["first_half"] * 6 + ["confirm"]
    assert out["delivered"] == [it.members[0] for it in items]
    assert out["stats"]["accepted"] == 64 and out["stats"]["rejected"] == 0
    assert out["metrics"].att_isolated_batches.value == 0
    assert out["metrics"].att_isolation_inferred.value == 6
    (row,) = out["rows"]
    assert row["fault"] == "verdict" and row["probes"] == 7


def test_a_malformed_signature_inside_a_half(chain):
    """A signature that does not decompress refuses every probe that holds
    it before any device call (`ValueError` inside a probe = refused), and
    is rejected by the probe of itself alone; the other seven are
    delivered."""
    keys, genesis, items = chain
    batch = list(items[:8])
    batch[5] = dataclasses.replace(
        batch[5], signature=b"\x9f" + b"\xff" * 95)
    with pytest.raises(A.BlsError):
        A.g2_from_bytes(batch[5].signature, subgroup_check=False)
    out = run_batch(genesis, batch, lambda *a: True)
    assert out["delivered"] == [
        it.members[0] for i, it in enumerate(batch) if i != 5]
    assert out["stats"]["accepted"] == 7 and out["stats"]["rejected"] == 1
    # 0-3 verify; 4-5 refused, 6-7 deferred; 4 verifies; 5 confirmed bad;
    # the union 6-7 verifies
    assert out["probes"] == [
        ((0, 1, 2, 3), True), ((4, 5), False), ((4,), True), ((5,), False),
        ((6, 7), True)]
    # the refused ones never reached the device
    assert [n for _k, _s, n in out["backend"].calls] == [4, 1, 2]
    assert out["metrics"].att_isolation_probes.value == 3
    assert out["breaker"] == "closed"
    (row,) = out["rows"]
    assert row["probes"] == 5 and row["fault"] is None


@pytest.mark.parametrize("first_pass", [True, False],
                         ids=["device-says-invalid", "device-says-valid"])
def test_a_batch_of_one_is_rechecked_once(chain, first_pass):
    """A batch of one has no halves: one re-check stands in for the
    descent. A device that called a sound item invalid and then clears it
    files the `verdict` fault, as before."""
    keys, genesis, items = chain
    answers = iter([not first_pass, True])
    out = run_batch(genesis, items[:1], lambda *a: next(answers))
    assert [n for _k, _s, n in out["backend"].calls] == (
        [1, 1] if first_pass else [1])
    assert out["stats"]["accepted"] == 1 and out["stats"]["rejected"] == 0
    (row,) = out["rows"]
    assert row["fault"] == ("verdict" if first_pass else None)
    assert row["probes"] == (1 if first_pass else 0)


# -- member keys: decompressed only off the registry path ------------------

PATHS = ["registry", "upload", "host"]


def off_curve_key() -> bytes:
    """A compressed G1 key whose x has no point on the curve: wire-well
    formed (the registry takes it and its device decompressor zeroes the
    row), refused by the host's decompression."""
    for x in range(1, 64):
        key = bytes([0x80]) + x.to_bytes(47, "big")
        try:
            A.g1_from_bytes(key, subgroup_check=False)
        except A.BlsError:
            return key
    raise AssertionError("no x off the curve below 64")


@pytest.fixture(scope="module")
def unkeyed_chain(chain):
    """The chain's validators with ONE of the slot's first four voters'
    key replaced by `off_curve_key()` (the first whose genesis builds: its
    sync committee must not hold the key), and the slot's votes made
    anew over that genesis (committees do not depend on the keys).
    Returns (genesis, the first four votes, the unkeyed voter's
    position)."""
    keys, _genesis, items = chain
    for pos, item in enumerate(items[:4]):
        pubkeys = list(keys.pubkey_bytes())
        pubkeys[item.members[0]] = off_curve_key()
        try:
            genesis = interop_genesis_state(
                N, CFG, eth1_block_hash=RANDAO_MIX, pubkeys=pubkeys)
            break
        except A.BlsError:
            continue
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
    votes = AttestationTraffic({"members": "single"}, SHAPES, keys, ident,
                               SEED).slot_items(SLOT)[:4]
    assert [it.members for it in votes] == [it.members for it in items[:4]]
    return genesis, votes, pos


@pytest.mark.parametrize("path", PATHS)
def test_member_keys_are_decompressed_only_off_the_registry_path(
        chain, path):
    """Prevalidation decompresses no member key. Over the resident
    registry nothing does: the counter stays at 0 and the process-wide
    key cache does not grow. With no registry (the upload entry) and
    with the breaker OPEN (the host anchor, real crypto) the check
    decompresses its own items' keys, counted under its path, and the
    valid batch is delivered whole."""
    from grandine_tpu.consensus import keys as key_cache

    keys, genesis, items = chain
    batch = list(items[:4])
    for it in batch:  # cold, as a validator's first vote finds its key
        key_cache._CACHE.pop(keys.pubkey_bytes()[it.members[0]], None)
    out = run_batch(genesis, batch, anchor_verdict(keys), path=path)
    assert out["delivered"] == [it.members[0] for it in batch]
    assert out["stats"]["accepted"] == 4 and out["stats"]["rejected"] == 0
    counter = out["metrics"].att_member_keys_decompressed
    members = sum(len(it.members) for it in batch)
    for label in ("upload", "host"):
        assert counter.value(label) == (members if label == path else 0)
    kernels = {k for k, _s, _n in out["backend"].calls}
    if path == "registry":
        assert out["keys_cached"] == 0
        assert kernels == {IDX}
    else:
        assert kernels == ({UPLOAD} if path == "upload" else set())
    assert out["probes"] == []


@pytest.mark.parametrize("path", PATHS)
def test_a_key_that_does_not_decompress_rejects_its_vote_alone(
        unkeyed_chain, path):
    """A vote naming a key the host cannot decompress is rejected, and its
    batch-mates are delivered, on every path. Over the registry the
    device's validity mask fails the row closed (the recording backend
    refuses any call that names it): the batch fails and the descent
    names the vote by a probe of itself alone. Off it the check leaves
    the vote out and it is rejected on its own: no descent."""
    genesis, batch, pos = unkeyed_chain
    bad = batch[pos].members[0]
    sound = {it.signature for it in batch}

    def verdict(message, sig_bytes, indices):
        return sig_bytes in sound and bad not in indices

    out = run_batch(genesis, batch, verdict, path=path,
                    unkeyed=() if path == "registry" else (pos,))
    assert out["delivered"] == [
        it.members[0] for i, it in enumerate(batch) if i != pos]
    assert out["stats"]["accepted"] == 3 and out["stats"]["rejected"] == 1
    (row,) = out["rows"]
    counter = out["metrics"].att_member_keys_decompressed
    if path == "registry":
        assert ((pos,), False) in out["probes"]
        assert row["probes"] == len(out["probes"]) > 0
        assert counter.value("upload") == counter.value("host") == 0
    else:
        assert out["probes"] == [] and row["fault"] is None
        assert counter.value(path) == len(batch)
        assert out["breaker"] == ("open" if path == "host" else "closed")
    if path == "upload":
        assert [n for _k, _s, n in out["backend"].calls] == [3]


def test_a_batch_of_64_votes_computes_the_active_sets_digest_once(chain):
    """A batch's prevalidation looks each vote's committee up through the
    epoch's active set memoised on the registry view: over 64 votes on a
    view no lookup has seen, ONE digest is computed and the other 63
    lookups find it (`accessors.active_set_totals`)."""
    from grandine_tpu.consensus import accessors
    from grandine_tpu.transition.fork_upgrade import state_phase
    from grandine_tpu.types.containers import spec_types

    _keys, genesis, items = chain
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier)
    verifier = AttestationVerifier(ctrl, use_device=False)
    try:
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()
        state = ctrl.snapshot().head_state
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        # the same registry under a new tuple: a view no lookup has seen
        view = state.replace(validators=list(state.validators))
        assert (accessors.registry_columns(view)
                is not accessors.registry_columns(state))
        digests0, hits0 = accessors.active_set_totals()
        prepared = [verifier._prevalidate(view, wire(ns, it))
                    for it in items]
        digests, hits = accessors.active_set_totals()
        assert len(prepared) == 64
        assert (digests - digests0, hits - hits0) == (1, 63)
    finally:
        verifier.stop()
        ctrl.stop()
