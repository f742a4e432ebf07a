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
    carry no verdict and change none;
(d) when the collector lets a batch go: a full one whenever a pool thread
    is free, one that met its deadline short only while fewer than
    `pipeline_depth + 1` batches are outstanding (over a stub whose
    settles, or a controller whose deliveries, wait at a gate the test
    opens, so the pipeline's occupancy is in the test's hand).
"""

import contextlib
import random
import sys
import threading
import time
from types import SimpleNamespace

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


@contextlib.contextmanager
def node(genesis, backend=None, deliver_gate=None, **sizes):
    """A verifier built with `sizes` (default: the node's own, max_batch
    64, deadline 50 ms) over `backend` (None: the real kernel), registry in
    sync, at the votes' slot. `submit(items)` is ONE `submit_many` call;
    `delivered` fills with validator indices in delivery order, each
    delivery first taking `deliver_gate` where one is given; `seen()`
    gives the stats, metrics, spans and the batches' flight rows."""
    metrics, tracer = Metrics(), Tracer()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics, tracer=tracer)
    verifier = AttestationVerifier(ctrl, backend=backend, **sizes)
    delivered = []
    inner = ctrl.on_valid_attestation_batch

    def deliver(valids):
        if deliver_gate is not None:
            assert deliver_gate.acquire(timeout=WAIT_S)
        delivered.extend(int(v.indices[0]) for v in valids)
        return inner(valids)

    ctrl.on_valid_attestation_batch = deliver

    def seen():
        ctrl.wait()
        rows = [r.as_dict()
                for r in verifier.flight.snapshot(lane="attestation")
                if r.kind == BATCH]
        return {"delivered": delivered, "stats": dict(verifier.stats),
                "metrics": metrics, "spans": tracer.finished_spans(),
                "rows": rows, "bucket": verifier.batch_bucket}

    try:
        state = ctrl.snapshot().head_state
        if verifier.registry is not None:
            assert verifier.registry.ensure(
                accessors.registry_columns(state).pubkeys)
        ns = getattr(spec_types(CFG.preset), state_phase(state, CFG).key)
        ctrl.on_tick(Tick(SLOT, TickKind.ATTEST))
        ctrl.wait()
        yield SimpleNamespace(
            verifier=verifier, metrics=metrics, delivered=delivered,
            seen=seen,
            submit=lambda items: verifier.submit_many(
                [wire(ns, it) for it in items]),
        )
    finally:
        verifier.stop()
        ctrl.stop()


def serve(genesis, items, backend=None, then=None, **sizes):
    """`items` in ONE `submit_many` call through `node(...)`, then a flush
    (or `then(verifier)`). Returns what `seen()` gives."""
    with node(genesis, backend, **sizes) as n:
        n.submit(items)
        if then is not None:
            then(n.verifier)
        else:
            n.verifier.flush(timeout=600.0)
        return n.seen()


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

def closed_now(metrics) -> tuple:
    """Batches closed so far: (full, by the deadline, at stop)."""
    family = metrics.att_batches_closed
    return tuple(family.value(by) for by in ("full", "deadline", "stop"))


def closed(out) -> dict:
    return dict(zip(("full", "deadline", "stop"), closed_now(out["metrics"])))


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


# -- (d) when the collector lets a batch go --------------------------------

#: what a gate or a wait gives up after (never reached by a passing test)
WAIT_S = 120.0
#: batch bound and collector deadline of this part's verifiers: votes
#: "one by one past the deadline" are `PAST_S` apart
BOUND, DEADLINE_S, PAST_S = 4, 0.01, 0.03


class GatedBackend(FloorBackend):
    """The recording stub, every item valid, whose settles wait at a gate:
    `open(n)` lets n of them through. A call is recorded when the pool
    thread LAUNCHES it, so `calls` says which batches left the collector,
    and a batch stays outstanding until the test opens the gate for it."""

    def __init__(self) -> None:
        super().__init__(lambda message, sig_bytes, indices: True)
        self.gate = threading.Semaphore(0)

    def fast_aggregate_verify_batch_indexed_async(self, *a, **kw):
        settle = super().fast_aggregate_verify_batch_indexed_async(*a, **kw)

        def gated() -> bool:
            assert self.gate.acquire(timeout=WAIT_S)
            return settle()

        return gated

    def open(self, n: int) -> None:
        for _ in range(n):
            self.gate.release()


def wait_for(what) -> None:
    end = time.monotonic() + WAIT_S
    while not what():
        assert time.monotonic() < end, "waited in vain"
        time.sleep(0.005)


def gated(genesis, backend, depth, **sizes):
    """The node over the gated stub: batches of 4, a 10 ms deadline, a
    settle watchdog that outlasts every gate."""
    return node(genesis, backend, max_batch=BOUND, deadline_s=DEADLINE_S,
                pipeline_depth=depth, settle_timeout_s=2 * WAIT_S, **sizes)


def fill_the_pipeline(n, backend, items, depth) -> int:
    """`depth + 1` whole batches in one call: `depth` get through the
    dispatch semaphore and wait at the gate, one has launched its call and
    stands at the semaphore. Returns how many items that took."""
    n.submit(items[: BOUND * (depth + 1)])
    wait_for(lambda: len(backend.calls) == depth + 1)
    assert closed_now(n.metrics) == (depth + 1, 0, 0)
    return BOUND * (depth + 1)


def one_by_one(n, items) -> None:
    for it in items:
        n.submit([it])
        time.sleep(PAST_S)


def exactly_once(out, items) -> bool:
    """Every item one verdict (batches that left together reach the
    pipeline in any order, so the deliveries' order is not held)."""
    return sorted(out["delivered"]) == sorted(it.members[0] for it in items)


def held_spans(out):
    return [(s.attrs["closed_by"], s.attrs["items"])
            for s in out["spans"]
            if s.name == "collect_wait" and s.attrs["held_s"] > 0]


def held_rows(out):
    for r in out["rows"]:
        assert 0.0 <= r["held_s"] <= r["collect_wait_s"]
    return [(r["closed_by"], r["items"]) for r in out["rows"]
            if r["held_s"] > 0]


@pytest.mark.parametrize("depth", [1, 2])
def test_a_short_batch_waits_for_a_slot_of_the_pipeline(chain, depth):
    """With `depth + 1` batches outstanding, votes that come one by one
    past the deadline are NOT popped; when one settle is let through they
    leave as ONE batch, closed by its deadline, and span, flight row and
    counter say that it was held."""
    _keys, genesis, items = chain
    backend = GatedBackend()
    with gated(genesis, backend, depth) as n:
        used = fill_the_pipeline(n, backend, items, depth)
        one_by_one(n, items[used: used + 3])
        time.sleep(10 * DEADLINE_S)
        assert len(backend.calls) == depth + 1
        assert closed_now(n.metrics) == (depth + 1, 0, 0)
        assert n.metrics.att_batches_held.value == 1
        assert n.delivered == []
        backend.open(1)
        wait_for(lambda: len(backend.calls) == depth + 2)
        assert backend.calls[-1] == (IDX, (BOUND, 4), 3)
        assert closed_now(n.metrics) == (depth + 1, 1, 0)
        backend.open(depth + 1)
        n.verifier.flush(timeout=WAIT_S)
        out = n.seen()
    assert exactly_once(out, items[: used + 3])
    assert held_spans(out) == [("deadline", 3)]
    assert held_rows(out) == [("deadline", 3)]
    assert out["metrics"].att_batches_held.value == 1
    assert "attestation_batches_held_total 1.0" in out["metrics"].expose()


@pytest.mark.parametrize("depth", [1, 2])
def test_a_held_batch_that_fills_leaves_at_once_as_full(chain, depth):
    """The same, but the queue reaches the batch bound while the batch is
    held: it leaves then, as "full", no settle let through."""
    _keys, genesis, items = chain
    backend = GatedBackend()
    with gated(genesis, backend, depth) as n:
        used = fill_the_pipeline(n, backend, items, depth)
        one_by_one(n, items[used: used + BOUND - 1])
        time.sleep(10 * DEADLINE_S)
        assert len(backend.calls) == depth + 1
        assert n.metrics.att_batches_held.value == 1
        n.submit([items[used + BOUND - 1]])
        wait_for(lambda: len(backend.calls) == depth + 2)
        assert backend.calls[-1] == (IDX, (BOUND, 4), BOUND)
        assert closed_now(n.metrics) == (depth + 2, 0, 0)
        assert n.delivered == []
        backend.open(depth + 2)
        n.verifier.flush(timeout=WAIT_S)
        out = n.seen()
    assert exactly_once(out, items[: used + BOUND])
    assert held_spans(out) == [("full", BOUND)]
    assert held_rows(out) == [("full", BOUND)]
    assert out["metrics"].att_batches_held.value == 1


@pytest.mark.parametrize("depth", [1, 2])
def test_a_short_batch_leaves_at_its_deadline_while_there_is_room(
        chain, depth):
    """From an idle pipeline up to `depth` batches outstanding, a lone vote
    leaves when its deadline passes: not held, counter unchanged."""
    _keys, genesis, items = chain
    backend = GatedBackend()
    with gated(genesis, backend, depth) as n:
        for k in range(depth + 1):
            t0 = time.monotonic()
            n.submit([items[k]])
            wait_for(lambda: len(backend.calls) == k + 1)
            assert time.monotonic() - t0 < 50 * DEADLINE_S
        assert closed_now(n.metrics) == (0, depth + 1, 0)
        assert n.metrics.att_batches_held.value == 0
        backend.open(depth + 1)
        n.verifier.flush(timeout=WAIT_S)
        out = n.seen()
    assert exactly_once(out, items[: depth + 1])
    assert held_spans(out) == [] and held_rows(out) == []
    assert [r["held_s"] for r in out["rows"]] == [0.0] * (depth + 1)
    assert out["metrics"].att_batches_held.value == 0


def test_whole_batches_are_admitted_up_to_max_active_as_before(chain):
    """Five whole batches in one call, two pool threads allowed: two calls
    go into the pipeline, two more launch and stand at the semaphore (four
    outstanding, more than a short batch may find), the fifth waits for an
    active slot. Nothing is held."""
    _keys, genesis, items = chain
    backend = GatedBackend()
    with gated(genesis, backend, 2, max_active=2) as n:
        n.submit(items[: 5 * BOUND])
        wait_for(lambda: len(backend.calls) == 4)
        time.sleep(10 * DEADLINE_S)
        assert len(backend.calls) == 4
        assert closed_now(n.metrics) == (4, 0, 0)
        backend.open(5)
        n.verifier.flush(timeout=WAIT_S)
        out = n.seen()
    assert closed(out) == {"full": 5, "deadline": 0, "stop": 0}
    assert exactly_once(out, items[: 5 * BOUND])
    assert held_spans(out) == [] and held_rows(out) == []
    assert out["metrics"].att_batches_held.value == 0


def test_flush_drains_a_held_batch(chain):
    """`flush()` with a batch held: it returns once the settles come
    through, every item with its one verdict."""
    _keys, genesis, items = chain
    backend = GatedBackend()
    with gated(genesis, backend, 2) as n:
        used = fill_the_pipeline(n, backend, items, 2)
        one_by_one(n, items[used: used + 2])
        assert n.metrics.att_batches_held.value == 1
        with pytest.raises(TimeoutError):
            n.verifier.flush(timeout=10 * DEADLINE_S)
        assert len(backend.calls) == 3  # still held: flush frees no slot
        opener = threading.Timer(0.1, backend.open, args=(4,))
        opener.start()
        try:
            n.verifier.flush(timeout=WAIT_S)
        finally:
            opener.join()
        out = n.seen()
    assert exactly_once(out, items[: used + 2])
    assert held_rows(out) == [("deadline", 2)]
    assert closed(out) == {"full": 3, "deadline": 1, "stop": 0}


def test_outstanding_batches_balance_under_many_submitters(chain):
    """Eight threads hand the slot's votes over in bursts of 1-6 while
    batches of 4 form, hold, leave and resolve on three other threads,
    the interpreter switching every 10 us: a lost update of the
    outstanding count would wedge the collector (too high: flush never
    returns) or switch the hold off (too low). After the flush nothing
    is outstanding and every vote has its one verdict."""
    _keys, genesis, items = chain
    backend = GatedBackend()
    backend.open(10_000)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with gated(genesis, backend, 2) as n:
            def hand_over(k: int) -> None:
                rng, i = random.Random(f"submitters|{SEED}|{k}"), 0
                while i < len(items):
                    j = i + rng.randint(1, 6)
                    n.submit(items[i:j])
                    i = j
                    time.sleep(rng.random() * DEADLINE_S)

            threads = [threading.Thread(target=hand_over, args=(k,))
                       for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=WAIT_S)
                assert not t.is_alive()
            n.verifier.flush(timeout=WAIT_S)
            v = n.verifier
            assert (v._outstanding, v._active, v._inflight) == (0, 0, 0)
            out = n.seen()
    finally:
        sys.setswitchinterval(interval)
    assert exactly_once(out, list(items) * 8)
    assert sum(r["items"] for r in out["rows"]) == 8 * len(items)
    assert len(held_rows(out)) == out["metrics"].att_batches_held.value
    by = closed(out)
    assert by["stop"] == 0 and by["full"] + by["deadline"] == len(out["rows"])


def host_path_with_three_outstanding(n, items) -> None:
    """Three lone votes, each a batch of its own that the host anchor has
    verified and whose delivery waits at the node's gate: outstanding on
    their pool threads, `pipeline_depth + 1` of them."""
    for k in range(3):
        n.submit([items[k]])
        wait_for(lambda: closed_now(n.metrics) == (0, k + 1, 0))
    wait_for(lambda: n.verifier.stats["accepted"] == 3)
    assert n.delivered == []


def test_the_host_path_counts_its_outstanding_batches_and_frees_them(chain):
    """`use_device=False`: a batch is outstanding until its pool thread has
    delivered it. Three stand at the delivery gate, so two more votes are
    held; one delivery through and they leave as one batch; with all
    delivered the next lone vote finds room again."""
    _keys, genesis, items = chain
    gate = threading.Semaphore(0)
    with node(genesis, deliver_gate=gate, use_device=False,
              max_batch=BOUND, deadline_s=DEADLINE_S) as n:
        host_path_with_three_outstanding(n, items)
        one_by_one(n, items[3:5])
        time.sleep(10 * DEADLINE_S)
        assert closed_now(n.metrics) == (0, 3, 0)
        assert n.metrics.att_batches_held.value == 1
        gate.release()
        wait_for(lambda: closed_now(n.metrics) == (0, 4, 0))
        for _ in range(3):
            gate.release()
        n.verifier.flush(timeout=WAIT_S)
        assert sorted(n.delivered) == sorted(
            it.members[0] for it in items[:5])
        gate.release()
        n.submit([items[5]])
        n.verifier.flush(timeout=WAIT_S)
        out = n.seen()
    assert held_rows(out) == [("deadline", 2)]
    assert held_spans(out) == [("deadline", 2)]
    assert out["metrics"].att_batches_held.value == 1
    assert out["stats"]["accepted"] == 6
    assert exactly_once(out, items[:6])


def test_stop_closes_a_held_batch_and_it_is_still_verified(chain):
    """`stop()` with a batch held (host path, as in
    `test_the_collector_closes_at_stop`): no wedge, the held votes leave
    closed by "stop", every item gets its one verdict."""
    _keys, genesis, items = chain
    gate = threading.Semaphore(0)
    with node(genesis, deliver_gate=gate, use_device=False,
              max_batch=BOUND, deadline_s=DEADLINE_S) as n:
        host_path_with_three_outstanding(n, items)
        one_by_one(n, items[3:5])
        assert n.metrics.att_batches_held.value == 1
        t0 = time.monotonic()
        n.verifier.stop()
        assert time.monotonic() - t0 < 10.0
        assert closed_now(n.metrics) == (0, 3, 1)
        for _ in range(4):
            gate.release()
        wait_for(lambda: n.verifier.stats["batches"] == 4)
        out = n.seen()
    assert exactly_once(out, items[:5])
    assert out["stats"]["accepted"] == 5
    assert held_rows(out) == [("stop", 2)]
