"""Differential tests: the batched slasher paths against the
per-validator reference loop.

`on_attestation_reference` is the oracle (the original scalar walk,
byte-for-byte the reference semantics). Every test here drives two
fresh Slasher instances over the same input and requires:

* identical detections — kind, validator, evidence dict, in order;
* identical final database state, byte for byte, across every
  slasher prefix (span chunks, records, prune indexes).

The byte-identity check is the strong one: it proves the vectorized
range updates stop at exactly the chunk the scalar early exit would
have stopped at (a lazier walk would write extra chunks; an eager exit
would miss writes).
"""

import random

import numpy as np
import pytest

from grandine_tpu.metrics import Metrics
from grandine_tpu.slasher import (
    CHUNK_EPOCHS,
    VALIDATORS_PER_CHUNK,
    Slasher,
)
from grandine_tpu.storage.database import Database


def _dump(db):
    """Full slasher keyspace as sorted (key, value) bytes."""
    return [(bytes(k), bytes(v)) for k, v in db.iterate_prefix(b"sl:")]


def _hits_key(hits):
    return [(h.kind, h.validator_index, h.evidence) for h in hits]


def _assert_same(ref, new, ref_hits, new_hits):
    assert _hits_key(new_hits) == _hits_key(ref_hits)
    assert _dump(new.db) == _dump(ref.db)


def _random_aggregates(seed, n_aggs, max_validator=1024, max_epoch=200,
                       unique_within=True):
    """A randomized mix that exercises every detection kind: a few data
    roots (collisions → double votes), random (s, t) spans (nesting →
    surround / surrounded), random index subsets."""
    rng = random.Random(seed)
    roots = [bytes([r]) * 32 for r in (0xAA, 0xBB, 0xCC)]
    aggs = []
    for _ in range(n_aggs):
        k = rng.randint(1, 48)
        if unique_within:
            ids = rng.sample(range(max_validator), k)
        else:
            ids = [rng.randrange(max_validator) for _ in range(k)]
        s = rng.randint(0, max_epoch - 1)
        t = rng.randint(s + 1, min(s + 40, max_epoch))
        aggs.append((ids, s, t, rng.choice(roots)))
    return aggs


# ------------------------------------------------- per-aggregate batched


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_batched_matches_reference_randomized(seed):
    ref, new = Slasher(), Slasher()
    ref_hits, new_hits = [], []
    for ids, s, t, root in _random_aggregates(seed, 40):
        ref_hits.extend(ref.on_attestation_reference(ids, s, t, root))
        new_hits.extend(new.on_attestation(ids, s, t, root))
    _assert_same(ref, new, ref_hits, new_hits)
    assert _hits_key(new.drain()) == _hits_key(ref.drain())


def test_batched_directed_kinds():
    """One directed aggregate per detection kind through the batched
    path, with the evidence dict checked explicitly."""
    sl = Slasher()
    base = list(range(0, 300))  # spans two vchunks
    assert sl.on_attestation(base, 10, 20, b"\xaa" * 32) == []

    # surround: (5, 30) surrounds the recorded (10, 20)
    hits = sl.on_attestation([7, 290], 5, 30, b"\xbb" * 32)
    assert [(h.kind, h.validator_index) for h in hits] == [
        ("surround_vote", 7), ("surround_vote", 290),
    ]
    assert hits[0].evidence == {"existing": [10, 20], "new": [5, 30],
                                "roots": [(b"\xaa" * 32).hex(),
                                          (b"\xbb" * 32).hex()]}

    # surrounded: (12, 15) is surrounded by the recorded (10, 20)
    hits = sl.on_attestation([8], 12, 15, b"\xcc" * 32)
    assert [(h.kind, h.validator_index) for h in hits] == [
        ("surrounded_vote", 8),
    ]
    assert hits[0].evidence == {"existing": [10, 20], "new": [12, 15],
                                "roots": [(b"\xaa" * 32).hex(),
                                          (b"\xcc" * 32).hex()]}

    # double vote: same target, different root
    hits = sl.on_attestation([9, 11], 11, 20, b"\xdd" * 32)
    assert [(h.kind, h.validator_index) for h in hits] == [
        ("double_vote", 9), ("double_vote", 11),
    ]
    assert hits[0].evidence["target_epoch"] == 20
    assert hits[0].evidence["roots"] == [
        (b"\xaa" * 32).hex(), (b"\xdd" * 32).hex(),
    ]

    # clean: disjoint validators, fresh span
    assert sl.on_attestation([500, 501], 10, 20, b"\xaa" * 32) == []


def test_duplicate_indices_fall_back_to_sequential():
    """A repeated index inside one aggregate is order-dependent; the
    batched entry point must produce reference semantics (first
    occurrence records, second sees it)."""
    ref, new = Slasher(), Slasher()
    aggs = [
        ([3, 4, 3], 1, 5, b"\xaa" * 32),
        ([4, 4], 2, 5, b"\xbb" * 32),
    ]
    ref_hits, new_hits = [], []
    for ids, s, t, root in aggs:
        ref_hits.extend(ref.on_attestation_reference(ids, s, t, root))
        new_hits.extend(new.on_attestation(ids, s, t, root))
    _assert_same(ref, new, ref_hits, new_hits)


@pytest.mark.parametrize("history", [8, 24])
def test_batched_small_history_floor(history):
    """Tiny history windows put the floor inside (or above) the walk's
    first chunk — the vectorized walk must clamp exactly like the
    scalar one."""
    ref = Slasher(history_epochs=history)
    new = Slasher(history_epochs=history)
    ref_hits, new_hits = [], []
    for ids, s, t, root in _random_aggregates(7, 30, max_epoch=64):
        ref_hits.extend(ref.on_attestation_reference(ids, s, t, root))
        new_hits.extend(new.on_attestation(ids, s, t, root))
    _assert_same(ref, new, ref_hits, new_hits)


def test_batched_deep_history_walk():
    """Deep fresh-history ingest (the bench diagnostic's shape): the
    min walk crosses hundreds of chunks; every touched chunk must match
    the scalar walk byte for byte."""
    ref, new = Slasher(), Slasher()
    ids = list(range(300))
    ref_hits = ref.on_attestation_reference(ids, 4000, 4001, b"\xaa" * 32)
    new_hits = new.on_attestation(ids, 4000, 4001, b"\xaa" * 32)
    _assert_same(ref, new, ref_hits, new_hits)
    # second aggregate one epoch up: the monotone early exit now stops
    # the walk almost immediately — still byte-identical
    ref_hits = ref.on_attestation_reference(ids, 4001, 4002, b"\xbb" * 32)
    new_hits = new.on_attestation(ids, 4001, 4002, b"\xbb" * 32)
    _assert_same(ref, new, ref_hits, new_hits)


# ------------------------------------------------------ bulk-replay feed


@pytest.mark.parametrize("seed", [11, 12])
def test_bulk_matches_sequential_reference(seed):
    """A replay window through `on_attestations_bulk` (solo validators
    ride the merged epoch grid, repeats take the scalar path) against
    aggregate-at-a-time reference ingestion."""
    aggs = _random_aggregates(seed, 25, max_validator=768,
                              unique_within=False)
    ref = Slasher()
    ref_out = [
        ref.on_attestation_reference(ids, s, t, root)
        for ids, s, t, root in aggs
    ]
    new = Slasher()
    new_out = new.on_attestations_bulk(aggs)
    assert [_hits_key(h) for h in new_out] == [_hits_key(h) for h in ref_out]
    assert _dump(new.db) == _dump(ref.db)


def test_bulk_grid_vs_span_plane():
    """The same window with and without the device SpanPlane wired —
    `tpu.spans.grid_merge_host` is the kernel's numpy twin, so the final
    state must be identical (and match the reference)."""
    from grandine_tpu.tpu.spans import SpanPlane

    aggs = _random_aggregates(21, 12, max_validator=512, max_epoch=120,
                              unique_within=False)
    host = Slasher()
    host_out = host.on_attestations_bulk(aggs)
    dev = Slasher(span_plane=SpanPlane())
    dev_out = dev.on_attestations_bulk(aggs)
    ref = Slasher()
    ref_out = [
        ref.on_attestation_reference(ids, s, t, root)
        for ids, s, t, root in aggs
    ]
    assert [_hits_key(h) for h in host_out] == [_hits_key(h) for h in ref_out]
    assert [_hits_key(h) for h in dev_out] == [_hits_key(h) for h in ref_out]
    assert _dump(host.db) == _dump(ref.db)
    assert _dump(dev.db) == _dump(ref.db)


def test_bulk_fallback_rows_off_grid():
    """Rows whose update range doesn't fit the device grid (history
    floor above the grid base) must take the host walk and still match
    the reference exactly."""
    aggs = [
        (list(range(64)), 4000, 4001, b"\xaa" * 32),   # deep: grid row
        (list(range(64, 96)), 2, 4001, b"\xbb" * 32),  # source below grid
    ]
    ref = Slasher(history_epochs=64)
    ref_out = [
        ref.on_attestation_reference(ids, s, t, root)
        for ids, s, t, root in aggs
    ]
    new = Slasher(history_epochs=64)
    new_out = new.on_attestations_bulk(aggs)
    assert [_hits_key(h) for h in new_out] == [_hits_key(h) for h in ref_out]
    assert _dump(new.db) == _dump(ref.db)


# ------------------------------------------------------- prune coherence


def test_prune_after_batched_matches_reference():
    """Pruning after batched ingest drops exactly the rows the
    reference-path slasher would drop."""
    ref, new = Slasher(history_epochs=64), Slasher(history_epochs=64)
    aggs = _random_aggregates(31, 20, max_validator=512, max_epoch=150)
    for ids, s, t, root in aggs:
        ref.on_attestation_reference(ids, s, t, root)
        new.on_attestation(ids, s, t, root)
    assert new.prune(150) == ref.prune(150)
    assert _dump(new.db) == _dump(ref.db)


# ------------------------------------- one storage transaction per call

DBS = ["memory", "sqlite"]


def _make_db(kind, tmp_path, name="chain.sqlite"):
    if kind == "memory":
        return Database.in_memory()
    return Database.persistent(str(tmp_path / name))


def _firehose_slot(seed, committees=12, aggregators=16, size=130,
                   source=2, target=3):
    """One slot's aggregates as the gossip firehose sees them: every
    committee's members in `aggregators` aggregates, each missing 0 or 1
    member, shuffled — so in any 64 of them nearly every validator appears
    several times. Two aggregators of committee 0 vote another root (a
    double vote per member) and one of committee 1 votes a span that
    surrounds the slot's (source, target)."""
    rng = random.Random(seed)
    aggs = []
    for c in range(committees):
        members = list(range(c * (size + 1), c * (size + 1) + size + c % 2))
        for a in range(aggregators):
            ids = list(members)
            if rng.random() < 0.5:
                ids.pop(rng.randrange(len(ids)))
            rng.shuffle(ids)
            s, t, root = source, target, bytes([c + 1]) * 32
            if c == 0 and a in (3, 11):
                root = b"\xee" * 32
            if c == 1 and a == 7:
                s, t, root = source - 1, target + 1, b"\xdd" * 32
            aggs.append((ids, s, t, root))
    rng.shuffle(aggs)
    return aggs


def _commits(metrics):
    return metrics.slasher_storage_commits.value


@pytest.mark.parametrize("db_kind", DBS)
def test_firehose_shape_matches_reference(db_kind, tmp_path):
    """(a) three calls of 64 over one slot's 192 aggregates: every index
    of every call is a collision; hits and the database's whole `sl:`
    keyspace equal the reference applied aggregate by aggregate."""
    aggs = _firehose_slot(5)
    assert len(aggs) == 192
    ref = Slasher(_make_db(db_kind, tmp_path, "ref.sqlite"))
    ref_out = [
        ref.on_attestation_reference(ids, s, t, root)
        for ids, s, t, root in aggs
    ]
    metrics = Metrics()
    new = Slasher(_make_db(db_kind, tmp_path), metrics=metrics)
    new_out = []
    for k in range(0, 192, 64):
        new_out.extend(new.on_attestations_bulk(aggs[k:k + 64]))
    assert [_hits_key(h) for h in new_out] == [_hits_key(h) for h in ref_out]
    kinds = {h.kind for hits in new_out for h in hits}
    assert {"double_vote", "surround_vote"} <= kinds
    assert _dump(new.db) == _dump(ref.db)
    assert _hits_key(new.drain()) == _hits_key(ref.drain())
    assert _commits(metrics) == 3
    # a validator's later lookups in a call are served by its write set
    reads = metrics.slasher_record_reads
    assert reads.value("write_set") > 3 * reads.value("db") > 0
    assert new.prune(4200) == ref.prune(4200)
    assert _dump(new.db) == _dump(ref.db)


@pytest.mark.parametrize("db_kind", DBS)
def test_offences_inside_one_call_are_found(db_kind, tmp_path):
    """(b) both votes of a double vote in ONE call, and a surround whose
    surrounded record was written earlier in the SAME call: found, with
    the evidence read through the call's write set."""
    sl = Slasher(_make_db(db_kind, tmp_path))
    out = sl.on_attestations_bulk([
        ([7, 8], 10, 20, b"\xaa" * 32),
        ([7], 11, 20, b"\xbb" * 32),      # double vote of 7
        ([8, 9], 5, 30, b"\xcc" * 32),    # surrounds 8's (10, 20)
        ([9], 6, 29, b"\xdd" * 32),       # surrounded by 9's (5, 30)
    ])
    assert [len(lst) for lst in out] == [0, 1, 1, 1]
    hits = [h for lst in out for h in lst]
    assert [(h.kind, h.validator_index) for h in hits] == [
        ("double_vote", 7), ("surround_vote", 8), ("surrounded_vote", 9),
    ]
    assert hits[0].evidence == {
        "target_epoch": 20,
        "roots": [(b"\xaa" * 32).hex(), (b"\xbb" * 32).hex()],
    }
    # a surround names the vote it found, root and all: 8's (10, 20)
    assert hits[1].evidence == {"existing": [10, 20], "new": [5, 30],
                                "roots": [(b"\xaa" * 32).hex(),
                                          (b"\xcc" * 32).hex()]}
    assert hits[2].evidence == {"existing": [5, 30], "new": [6, 29],
                                "roots": [(b"\xcc" * 32).hex(),
                                          (b"\xdd" * 32).hex()]}
    assert sl.record_for(7, 20) == (11, b"\xbb" * 32)  # last write wins


def test_a_surround_names_the_vote_it_found_not_a_later_one():
    """A later vote of the same call at the surrounded target replaces
    the record, not the root the surround hit names."""
    sl = Slasher()
    out = sl.on_attestations_bulk([
        ([8], 10, 20, b"\xaa" * 32),
        ([8], 5, 30, b"\xcc" * 32),       # surrounds (10, 20) of root aa
        ([8], 11, 20, b"\xbb" * 32),      # double vote at 20
    ])
    surround, double = out[1][0], out[2][0]
    assert surround.kind == "surround_vote"
    assert surround.evidence["roots"][0] == (b"\xaa" * 32).hex()
    assert double.kind == "double_vote"
    assert sl.record_for(8, 20) == (11, b"\xbb" * 32)


@pytest.mark.parametrize("db_kind", DBS)
@pytest.mark.parametrize("call", [
    "bulk_collisions", "bulk_solo", "aggregate", "aggregate_repeated",
    "reference", "block",
])
def test_one_commit_per_call(db_kind, call, tmp_path):
    """(c) `slasher_storage_commits_total` grows by exactly 1 per
    mutating call, collisions or not."""
    metrics = Metrics()
    sl = Slasher(_make_db(db_kind, tmp_path), metrics=metrics)
    sl.on_attestation(list(range(600)), 3, 4, b"\x11" * 32)
    before = _commits(metrics)
    assert before == 1
    if call == "bulk_collisions":
        sl.on_attestations_bulk(_firehose_slot(9)[:64])
    elif call == "bulk_solo":
        sl.on_attestations_bulk(
            [([i], 40, 41, b"\x22" * 32) for i in range(64)])
    elif call == "aggregate":
        sl.on_attestation(list(range(300)), 40, 41, b"\x22" * 32)
    elif call == "aggregate_repeated":
        sl.on_attestation([5, 6, 5, 300, 6], 40, 41, b"\x22" * 32)
    elif call == "reference":
        sl.on_attestation_reference(list(range(300)), 40, 41, b"\x22" * 32)
    else:
        assert sl.on_block(3, 9, b"\x33" * 32) is None
    assert _commits(metrics) == before + 1
    assert not sl._write_set and not sl._dirty


@pytest.mark.parametrize("entry", ["bulk", "aggregate"])
def test_call_is_committed_when_it_returns(entry, tmp_path):
    """(d) the call's durability point is its return: a second connection
    to the sqlite file (the first still open, as after a crash) finds
    every record and chunk of the call."""
    aggs = _firehose_slot(13, committees=3)[:40]
    sl = Slasher(_make_db("sqlite", tmp_path))
    if entry == "bulk":
        sl.on_attestations_bulk(aggs)
    else:
        for ids, s, t, root in aggs:
            sl.on_attestation(ids, s, t, root)
    ref = Slasher()
    for ids, s, t, root in aggs:
        ref.on_attestation_reference(ids, s, t, root)
    reopened = Database.persistent(str(tmp_path / "chain.sqlite"))
    try:
        rows = _dump(reopened)
        assert rows == _dump(ref.db)
        prefixes = {k[:5] for k, _ in rows}
        assert prefixes == {b"sl:r:", b"sl:t:", b"sl:m:", b"sl:x:", b"sl:e:"}
        # and a slasher built on the reopened file detects against them
        again = Slasher(reopened)
        hits = again.on_attestation([aggs[0][0][0]], 2, 3, b"\x99" * 32)
        assert [h.kind for h in hits] == ["double_vote"]
    finally:
        reopened.close()


@pytest.mark.parametrize("db_kind", DBS)
@pytest.mark.parametrize("fault", ["in_the_body", "in_the_flush"])
def test_call_that_raises_is_rolled_back(db_kind, fault, tmp_path,
                                         monkeypatch):
    """(e) a call that raises midway leaves no write set (and no dirty
    chunk) for a later call to write: the database and every later call
    are as if the failed call had never been made."""
    good1 = _firehose_slot(17, committees=2)[:20]
    bad = _firehose_slot(18, committees=2, source=6, target=9)[:20]
    good2 = _firehose_slot(19, committees=2, source=4, target=5)[:20]
    metrics = Metrics()
    sl = Slasher(_make_db(db_kind, tmp_path), metrics=metrics)
    sl.on_attestations_bulk(good1)
    state = _dump(sl.db)

    class Fault(Exception):
        pass

    with monkeypatch.context() as patch:
        if fault == "in_the_body":
            real, seen = sl._update_spans, []

            def failing(i, s, t):
                seen.append(i)
                if len(seen) > 700:
                    raise Fault()
                real(i, s, t)

            patch.setattr(sl, "_update_spans", failing)
        else:
            def refusing(rows):
                raise Fault()

            patch.setattr(sl.db, "put_batch", refusing)
        with pytest.raises(Fault):
            sl.on_attestations_bulk(bad)
    assert not sl._write_set and not sl._dirty
    assert _dump(sl.db) == state
    assert sl.record_for(bad[0][0][0], 9) is None
    assert _commits(metrics) == 1
    out = sl.on_attestations_bulk(good2)
    assert _commits(metrics) == 2

    ref = Slasher()
    ref.on_attestations_bulk(good1)
    ref_out = ref.on_attestations_bulk(good2)
    assert [_hits_key(h) for h in out] == [_hits_key(h) for h in ref_out]
    assert _dump(sl.db) == _dump(ref.db)
    assert _hits_key(sl.detected) == _hits_key(ref.detected)
