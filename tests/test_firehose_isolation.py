"""The firehose's descent over a failed batch (`AttestationVerifier.
_isolate`): bisection inside the batch's own padded bucket and executable.

Single votes of one slot of a 512-validator minimal-preset chain (64 a
slot), made by the benchmark's generator and forged as the hostile cell
forges them (the named validator's own signature over another root: it
decompresses, lies in G2, passes prevalidation, and only the pairing
refuses it). The backend is a recording stub of the device seam: it
answers from a per-item verdict (the program's host anchor for the small
batches that are held against the benchmark's plain reference, the forged
labels for the batch of 64) and writes down every call's kernel and
padded shape.
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
    the shape as tpu/bls.py `_aggregate_bucket` pads it."""

    fuse_subgroup = True

    def __init__(self, verdict) -> None:
        self.verdict, self.calls = verdict, []

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
        raise AssertionError("the registry is in sync: the upload entry "
                             "is not taken")


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


def run_batch(genesis, items, verdict):
    """`items` as ONE batch through a verifier over the recording backend,
    registry in sync. Returns what was delivered (validator index), the
    verifier's stats, the backend, the metrics, spans and flight rows, and
    the growth of the compile scope's count over the batch."""
    from grandine_tpu.consensus import accessors
    from grandine_tpu.transition.fork_upgrade import state_phase
    from grandine_tpu.types.containers import spec_types

    metrics, tracer = Metrics(), Tracer()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics, tracer=tracer)
    backend = RecordingBackend(verdict)
    verifier = AttestationVerifier(
        ctrl, backend=backend, use_device=True, max_batch=len(items),
        deadline_s=0.5,
    )
    delivered = []
    inner = ctrl.on_valid_attestation_batch

    def deliver(valids):
        delivered.extend(int(v.indices[0]) for v in valids)
        return inner(valids)

    ctrl.on_valid_attestation_batch = deliver
    try:
        state = ctrl.snapshot().head_state
        assert verifier.registry.ensure(
            accessors.registry_columns(state).pubkeys)
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()
        compiles0 = compile_scope.totals()[1]
        verifier.submit_many([wire(ns, it) for it in items])
        verifier.flush(timeout=120.0)
        ctrl.wait()
        compiled = compile_scope.totals()[1] - compiles0
        rows = [r.as_dict()
                for r in verifier.flight.snapshot(lane="attestation")
                if r.kind == BATCH]
        return {"delivered": delivered, "stats": dict(verifier.stats),
                "backend": backend, "metrics": metrics,
                "spans": tracer.finished_spans(), "rows": rows,
                "compiled": compiled, "breaker": verifier.health.state}
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


@pytest.mark.parametrize("forged", [0, 1, 2, 8])
def test_delivered_and_rejected_are_the_plain_references(chain, forged):
    keys, genesis, items = chain
    rng = random.Random(f"forged|{SEED}|{forged}")
    batch = list(items[:8])
    positions = sorted(rng.sample(range(8), forged))
    for pos in positions:
        batch[pos] = forge(keys, batch[pos])
    out = run_batch(genesis, batch, anchor_verdict(keys))
    want = [it.members[0] for it in batch if reference_says(keys, it)]
    # the forged ones are exactly those the reference refuses
    assert sorted(set(range(8)) - set(positions)) == [
        i for i, it in enumerate(batch) if reference_says(keys, it)]
    assert sorted(out["delivered"]) == sorted(want)
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
    assert row["verdict"] is (forged == 0)


# -- one forged vote in 64 ------------------------------------------------

@pytest.fixture(scope="module")
def one_in_64(chain):
    keys, genesis, items = chain
    pos = random.Random(f"one-in-64|{SEED}").randrange(64)
    batch = list(items)
    batch[pos] = forge(keys, batch[pos])
    bad = batch[pos].signature
    out = run_batch(genesis, batch,
                    lambda message, sig_bytes, indices: sig_bytes != bad)
    return batch, pos, out


def test_one_forged_in_64_makes_twelve_probes(one_in_64):
    """Both halves at each of six levels (32, 16, 8, 4, 2, 1), whatever
    the position; the item a probe of one has just refused is not checked
    a second time."""
    batch, pos, out = one_in_64
    calls = out["backend"].calls
    assert len(calls) == 1 + 12
    assert calls[0][2] == 64
    assert sorted(n for _k, _s, n in calls[1:]) == sorted(
        n for n in (32, 16, 8, 4, 2, 1) for _ in range(2))
    assert sorted(out["delivered"]) == sorted(
        it.members[0] for i, it in enumerate(batch) if i != pos)
    assert out["stats"]["accepted"] == 63 and out["stats"]["rejected"] == 1


def test_the_descent_stays_in_the_batchs_own_executable(one_in_64):
    """With the registry in sync no call reaches the upload entry, the
    non-indexed synchronous entry or another bucket than the parent
    batch's; nothing enters the compile scope."""
    _batch, _pos, out = one_in_64
    assert {(k, s) for k, s, _n in out["backend"].calls} == {(IDX, (64, 4))}
    assert out["compiled"] == 0


def test_the_descents_counters_and_flight_row(one_in_64):
    _batch, _pos, out = one_in_64
    m = out["metrics"]
    assert m.att_isolation_probes.value == 12
    assert m.att_isolation_probe_items.value == 2 * (32 + 16 + 8 + 4 + 2 + 1)
    assert m.att_isolation_probe_slots.value == 12 * 64
    assert m.att_isolated_batches.value == 1
    assert m.att_fallbacks.value == 1
    text = m.expose()
    for name in ("attestation_isolation_probes_total 12",
                 "attestation_isolation_probe_items_total 126",
                 "attestation_isolation_probe_slots_total 768",
                 "attestation_isolated_batches_total 1"):
        assert name in text
    (row,) = out["rows"]
    assert row["probes"] == 12 and row["bisect_s"] > 0
    assert row["verdict"] is False and row["items"] == 64


def test_the_descents_probe_spans(one_in_64):
    """One `probe` span a probe, all children of the batch's `fallback`
    stage; the stage's seconds are observed once (the probes are plain
    spans), so `verify_stage_seconds_sum{stage="fallback"}` is the whole
    descent and nothing twice."""
    _batch, _pos, out = one_in_64
    spans = out["spans"]
    (fallback,) = [s for s in spans if s.name == "fallback"]
    probes = [s for s in spans if s.name == "probe"]
    assert len(probes) == 12
    assert all(s.parent_id == fallback.span_id for s in probes)
    assert all(s.attrs["op"] == "probe" and s.attrs["bucket"] == 64
               for s in probes)
    assert sorted((s.attrs["depth"], s.attrs["items"]) for s in probes) == [
        (d, 64 >> d) for d in range(1, 7) for _ in range(2)]
    assert all(fallback.start <= s.start and s.end <= fallback.end
               for s in probes)
    family = out["metrics"].verify_stage_seconds
    observed = [labels for labels in family.children()
                if labels[0] == "fallback"]
    assert observed == [("fallback", "attestation", "")]


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
