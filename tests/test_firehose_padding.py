"""One batch bucket: a gossip batch of any size 1..max_batch is dispatched
padded into `AttestationVerifier.batch_bucket`, so a node on live gossip
(batches closed by the collector's deadline, short of the bound) runs ONE
executable per committee width.

Three parts, on the chain of tests/test_firehose_isolation.py (one slot's
64 single votes of a 512-validator minimal-preset chain):
(a) over the recording stub of the device seam: what a first pass and a
    probe name as `bucket_floor`, the counters and the flight row;
(b) how the collector says a batch closed: by its deadline, by the batch
    bound, at stop;
(c) over the REAL kernel on the CPU, through the served entry (`submit` ->
    delivery): partial batches of 1, 5 and 33 votes, all honest and with
    one forged, against the program's host anchor item for item (and the
    forged votes against the benchmark's plain reference): padding slots
    carry no verdict and change none.
"""

import time

import pytest
from test_firehose_isolation import (
    CFG,
    IDX,
    SEED,
    SLOT,
    RecordingBackend,
    anchor_verdict,
    chain,  # noqa: F401  (a fixture)
    forge,
    reference_says,
    wire,
)

from grandine_tpu.consensus import accessors
from grandine_tpu.consensus.verifier import NullVerifier
from grandine_tpu.fork_choice.store import Tick, TickKind
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime import AttestationVerifier, Controller
from grandine_tpu.runtime.flight import BATCH
from grandine_tpu.tracing import Tracer
from grandine_tpu.transition.fork_upgrade import state_phase
from grandine_tpu.types.containers import spec_types

kernel = pytest.mark.kernel


class FloorBackend(RecordingBackend):
    """The recording stub, which also writes down each call's
    `bucket_floor` as the verifier named it."""

    def __init__(self, verdict) -> None:
        super().__init__(verdict)
        self.floors = []

    def _call(self, kernel, messages, sigs, widths, floor):
        self.floors.append(floor)
        super()._call(kernel, messages, sigs, widths, floor)


def serve(genesis, items, backend=None, then=None, **sizes):
    """`items` in ONE `submit_many` call through a verifier built with
    `sizes` (default: the node's own, max_batch 64, deadline 50 ms) over
    `backend` (None: the real kernel), registry in sync. Returns what was
    delivered (validator index, in delivery order), the stats, metrics,
    spans and the batches' flight rows."""
    metrics, tracer = Metrics(), Tracer()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics, tracer=tracer)
    verifier = AttestationVerifier(ctrl, backend=backend, **sizes)
    delivered = []
    inner = ctrl.on_valid_attestation_batch

    def deliver(valids):
        delivered.extend(int(v.indices[0]) for v in valids)
        return inner(valids)

    ctrl.on_valid_attestation_batch = deliver
    try:
        state = ctrl.snapshot().head_state
        if verifier.registry is not None:
            assert verifier.registry.ensure(
                accessors.registry_columns(state).pubkeys)
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()
        verifier.submit_many([wire(ns, it) for it in items])
        if then is not None:
            then(verifier)
        else:
            verifier.flush(timeout=600.0)
        ctrl.wait()
        rows = [r.as_dict()
                for r in verifier.flight.snapshot(lane="attestation")
                if r.kind == BATCH]
        return {"delivered": delivered, "stats": dict(verifier.stats),
                "metrics": metrics, "spans": tracer.finished_spans(),
                "rows": rows, "bucket": verifier.batch_bucket}
    finally:
        verifier.stop()
        ctrl.stop()


# -- (a) what reaches the seam --------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 33, 64])
def test_a_first_pass_of_any_size_names_the_one_batch_bucket(chain, n):
    _keys, genesis, items = chain
    backend = FloorBackend(lambda message, sig_bytes, indices: True)
    out = serve(genesis, items[:n], backend)
    assert out["bucket"] == 64
    # one call, the indexed kernel, floor = (the batch bucket, no width):
    # 64 slots x the votes' own width bucket
    assert backend.floors == [(64, 0)]
    assert backend.calls == [(IDX, (64, 4), n)]
    assert out["delivered"] == [it.members[0] for it in items[:n]]
    m = out["metrics"]
    assert m.att_first_pass_items.value == n
    assert m.att_first_pass_slots.value == 64
    assert m.att_isolation_probes.value == 0
    text = m.expose()
    assert f"attestation_first_pass_items_total {float(n)}" in text
    assert "attestation_first_pass_slots_total 64.0" in text
    # the flight row records the bucket DISPATCHED, not the pow-2 of n
    (row,) = out["rows"]
    assert (row["items"], row["bucket"]) == (n, 64)
    assert row["fill"] == pytest.approx(n / 64, abs=1e-4)
    assert row["closed_by"] == ("full" if n == 64 else "deadline")


def test_a_probe_still_names_its_parent(chain):
    """A failed batch of 5 in the bucket of 64: every probe of its descent
    names the batch bucket and the PARENT's widest committee, so it runs
    the parent's executable; probe slots are counted at that bucket."""
    keys, genesis, items = chain
    batch = list(items[:5])
    batch[3] = forge(keys, batch[3])
    bad = batch[3].signature
    backend = FloorBackend(
        lambda message, sig_bytes, indices: sig_bytes != bad)
    out = serve(genesis, batch, backend)
    assert backend.floors[0] == (64, 0)
    probes = backend.floors[1:]
    assert probes and set(probes) == {(64, 1)}
    assert {(k, s) for k, s, _n in backend.calls} == {(IDX, (64, 4))}
    assert out["delivered"] == [
        it.members[0] for i, it in enumerate(batch) if i != 3]
    m = out["metrics"]
    assert m.att_first_pass_items.value == 5
    assert m.att_first_pass_slots.value == 64
    assert m.att_isolation_probes.value == len(probes)
    assert m.att_isolation_probe_slots.value == 64 * len(probes)
    spans = [s for s in out["spans"] if s.name == "probe"]
    assert len(spans) == len(probes)
    assert all(s.attrs["bucket"] == 64 for s in spans)
    (row,) = out["rows"]
    assert (row["items"], row["bucket"], row["probes"]) == (
        5, 64, len(probes))


def test_the_batch_bucket_follows_max_batch(chain):
    """`batch_bucket` is read-only and is the pow-2 bucket of the batch
    bound, whatever the bound."""
    _keys, genesis, items = chain
    backend = FloorBackend(lambda message, sig_bytes, indices: True)
    out = serve(genesis, items[:3], backend, max_batch=6)
    assert out["bucket"] == 8 and backend.floors == [(8, 0)]
    assert backend.calls == [(IDX, (8, 4), 3)]
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier)
    verifier = AttestationVerifier(ctrl, use_device=False)
    try:
        with pytest.raises(AttributeError):
            verifier.batch_bucket = 4
    finally:
        verifier.stop()
        ctrl.stop()


# -- (b) how a batch closed -----------------------------------------------

def closed(out) -> dict:
    family = out["metrics"].att_batches_closed
    return {by: family.value(by) for by in ("full", "deadline", "stop")}


def collect_waits(out):
    return sorted(
        ((s.attrs["closed_by"], s.attrs["items"])
         for s in out["spans"] if s.name == "collect_wait"),
        key=lambda pair: -pair[1])


def test_the_collector_closes_by_size_and_by_deadline(chain):
    """70 votes in one call: a batch of 64 leaves at once ("full"), the
    six left leave when their deadline passes ("deadline"); counter, span
    and flight row say so."""
    _keys, genesis, items = chain
    votes = list(items) + list(items[:6])
    backend = FloorBackend(lambda message, sig_bytes, indices: True)
    out = serve(genesis, votes, backend)
    assert closed(out) == {"full": 1, "deadline": 1, "stop": 0}
    assert collect_waits(out) == [("full", 64), ("deadline", 6)]
    assert sorted((r["closed_by"], r["items"], r["bucket"])
                  for r in out["rows"]) == [("deadline", 6, 64),
                                            ("full", 64, 64)]
    text = out["metrics"].expose()
    assert 'attestation_batches_closed_total{by="full"} 1.0' in text
    assert 'attestation_batches_closed_total{by="deadline"} 1.0' in text
    assert backend.floors == [(64, 0), (64, 0)]
    assert out["metrics"].att_first_pass_items.value == 70
    assert out["metrics"].att_first_pass_slots.value == 128


def test_the_collector_closes_at_stop(chain):
    """Two votes under a deadline that never comes: `stop()` closes the
    batch, and it is still verified (host path: the batch resolves on its
    pool thread, so nothing races the completion thread's shutdown)."""
    _keys, genesis, items = chain

    def stop_and_wait(verifier):
        t0 = time.monotonic()
        verifier.stop()
        assert time.monotonic() - t0 < 10.0  # not the 60 s deadline
        end = time.monotonic() + 120.0
        while verifier.stats["batches"] < 1 and time.monotonic() < end:
            time.sleep(0.01)

    out = serve(genesis, items[:2], then=stop_and_wait, use_device=False,
                deadline_s=60.0)
    assert closed(out) == {"full": 0, "deadline": 0, "stop": 1}
    assert collect_waits(out) == [("stop", 2)]
    (row,) = out["rows"]
    # no device bucket on the host path: the pow-2 of the items, as before
    assert (row["closed_by"], row["items"], row["bucket"]) == ("stop", 2, 2)
    assert out["stats"]["accepted"] == 2
    assert out["delivered"] == [it.members[0] for it in items[:2]]


# -- (c) the real kernel, through the served entry -------------------------

@kernel
@pytest.mark.parametrize("forged_at", [None, "drawn"],
                         ids=["honest", "one_forged"])
@pytest.mark.parametrize("n", [1, 5, 33])
def test_a_partial_batch_gets_the_anchors_verdicts_item_for_item(
        chain, n, forged_at):
    """`n` real votes, closed by the deadline, padded into the 64 x 4
    executable (its first use compiles it: ~2 min on the CPU, then the
    persistent cache has it). Delivered = exactly the votes the host
    anchor accepts, in the batch's order; a forged vote is refused by its
    own probe, in the same executable; the counters add up."""
    import random

    keys, genesis, items = chain
    batch = list(items[:n])
    pos = None
    if forged_at is not None:
        pos = random.Random(f"padded|{SEED}|{n}").randrange(n)
        batch[pos] = forge(keys, batch[pos])
        assert reference_says(keys, batch[pos]) is False
    anchor = anchor_verdict(keys)
    want = [it.members[0] for it in batch
            if anchor(it.message, it.signature, tuple(it.members))]
    assert len(want) == n - (pos is not None)
    # a settle deadline no loaded CPU worker misses: a watchdog expiry
    # would send the batch to the host twin and prove nothing
    out = serve(genesis, batch, settle_timeout_s=300.0)
    assert out["delivered"] == want
    stats = out["stats"]
    assert stats["accepted"] == len(want)
    assert stats["rejected"] == n - len(want)
    assert stats["fallbacks"] == (1 if pos is not None else 0)
    assert stats["retries"] == 0 and stats.get("settle_errors", 0) == 0
    (row,) = out["rows"]
    assert (row["items"], row["bucket"], row["closed_by"]) == (
        n, 64, "deadline")
    assert row["host_s"] == 0 and row["fault"] is None
    assert row["verdict"] is (pos is None)
    m = out["metrics"]
    assert m.att_first_pass_items.value == n
    assert m.att_first_pass_slots.value == 64
    probes = m.att_isolation_probes.value
    assert row["probes"] == probes
    assert m.att_isolation_probe_slots.value == 64 * probes
    assert (probes > 0) == (pos is not None)
    # every device call was the indexed kernel: first pass + probes
    assert m.device_kernel_calls.value("agg_fast_verify_msm_idx") == (
        1 + probes)
