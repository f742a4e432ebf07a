"""helper_functions-layer tests: misc/domain math, accessors, predicates,
mutators, and the Verifier seam.

Reference test parity: helper_functions/src/verifier.rs:438-470
(MultiVerifier edge cases) and the accessor/misc unit tests.
"""

import numpy as np
import pytest

from grandine_tpu.consensus import accessors, keys, misc, mutators, predicates
from grandine_tpu.consensus.mutators import StateDraft
from grandine_tpu.consensus.verifier import (
    MultiVerifier,
    NullVerifier,
    SignatureInvalid,
    SingleVerifier,
    Triple,
)
from grandine_tpu.crypto import bls as A
from grandine_tpu.transition.genesis import interop_genesis_state, interop_secret_key
from grandine_tpu.types.config import Config
from grandine_tpu.types.primitives import (
    DOMAIN_BEACON_ATTESTER,
    DOMAIN_BEACON_PROPOSER,
    FAR_FUTURE_EPOCH,
)

CFG = Config.minimal()
P = CFG.preset


@pytest.fixture(scope="module")
def state():
    return interop_genesis_state(32, CFG)


# ----------------------------------------------------------------- misc


def test_domain_structure():
    domain = misc.compute_domain(DOMAIN_BEACON_PROPOSER, b"\x01\x00\x00\x00", b"\x11" * 32)
    assert domain[:4] == DOMAIN_BEACON_PROPOSER
    assert (
        domain[4:]
        == misc.compute_fork_data_root(b"\x01\x00\x00\x00", b"\x11" * 32)[:28]
    )


def test_signing_root_matches_manual(state):
    from grandine_tpu.core import hashing

    domain = b"\x07" * 32
    obj_root = state.fork.hash_tree_root()
    root = misc.compute_signing_root(state.fork, domain)
    assert root == hashing.hash_pair(obj_root, domain)
    # bytes input path: treated as an already-computed root
    assert misc.compute_signing_root(obj_root, domain) == root


def test_epoch_slot_math():
    assert misc.compute_epoch_at_slot(17, P) == 2
    assert misc.compute_start_slot_at_epoch(2, P) == 16
    assert misc.compute_activation_exit_epoch(3, P) == 3 + 1 + P.MAX_SEED_LOOKAHEAD


# ------------------------------------------------------------- accessors


def test_committee_partition_covers_all_active(state):
    epoch = 0
    count = accessors.get_committee_count_per_slot(state, epoch, P)
    seen = []
    for slot in range(P.SLOTS_PER_EPOCH):
        for index in range(count):
            seen.extend(
                int(v) for v in accessors.get_beacon_committee(state, slot, index, P)
            )
    active = accessors.get_active_validator_indices(state, epoch)
    assert sorted(seen) == sorted(int(v) for v in active)


def test_proposer_is_active_and_deterministic(state):
    prop1 = accessors.get_beacon_proposer_index(state, P)
    prop2 = accessors.get_beacon_proposer_index(state, P)
    assert prop1 == prop2
    active = set(int(v) for v in accessors.get_active_validator_indices(state, 0))
    assert prop1 in active


def test_registry_columns_match_containers(state):
    cols = accessors.registry_columns(state)
    for i, v in enumerate(state.validators):
        assert cols.pubkeys[i] == bytes(v.pubkey)
        assert int(cols.effective_balance[i]) == int(v.effective_balance)
        assert int(cols.exit_epoch[i]) == int(v.exit_epoch)
    # cached: same object for the same registry
    assert accessors.registry_columns(state) is cols


def test_total_active_balance(state):
    total = accessors.get_total_active_balance(state, P)
    assert total == 32 * P.MAX_EFFECTIVE_BALANCE


def test_block_root_window(state):
    from grandine_tpu.transition.slots import process_slots

    s2 = process_slots(state, 3, CFG)
    root = accessors.get_block_root_at_slot(s2, 0, P)
    assert root == bytes(s2.block_roots[0])
    with pytest.raises(ValueError):
        accessors.get_block_root_at_slot(s2, 3, P)  # slot == state slot


# ------------------------------------------------------------- predicates


def test_active_and_slashable_predicates(state):
    v = state.validators[0]
    assert predicates.is_active_validator(v, 0)
    assert predicates.is_slashable_validator(v, 0)
    exited = v.replace(exit_epoch=5)
    assert not predicates.is_active_validator(exited, 7)
    slashed = v.replace(slashed=True)
    assert not predicates.is_slashable_validator(slashed, 0)


def test_slashable_attestation_data(state):
    from grandine_tpu.types.containers import spec_types

    ns = spec_types(P).phase0
    cp = lambda e: ns.Checkpoint(epoch=e, root=b"\x01" * 32)  # noqa: E731
    d1 = ns.AttestationData(slot=8, index=0, source=cp(0), target=cp(1))
    d2 = ns.AttestationData(slot=9, index=1, source=cp(0), target=cp(1))
    assert predicates.is_slashable_attestation_data(d1, d2)  # double vote
    d3 = ns.AttestationData(slot=8, index=0, source=cp(1), target=cp(4))
    d4 = ns.AttestationData(slot=9, index=0, source=cp(2), target=cp(3))
    assert predicates.is_slashable_attestation_data(d3, d4)  # surround
    assert not predicates.is_slashable_attestation_data(d1, d1)


# --------------------------------------------------------------- mutators


def test_balance_mutators(state):
    draft = StateDraft(state, CFG)
    mutators.increase_balance(draft, 0, 1000)
    mutators.decrease_balance(draft, 1, 10**18)  # saturates
    post = draft.commit()
    assert int(post.balances[0]) == int(state.balances[0]) + 1000
    assert int(post.balances[1]) == 0
    assert int(post.balances[2]) == int(state.balances[2])


def test_initiate_validator_exit_churn(state):
    draft = StateDraft(state, CFG)
    for i in range(6):
        mutators.initiate_validator_exit(draft, i)
    post = draft.commit()
    exit_epochs = [int(post.validators[i].exit_epoch) for i in range(6)]
    floor = misc.compute_activation_exit_epoch(0, P)
    churn = misc.get_validator_churn_limit(32, CFG)
    assert min(exit_epochs) == floor
    # churn-limited: at most `churn` exits per queue epoch
    for e in set(exit_epochs):
        assert exit_epochs.count(e) <= churn
    # idempotent
    draft2 = StateDraft(post, CFG)
    mutators.initiate_validator_exit(draft2, 0)
    assert int(draft2.validator(0).exit_epoch) == int(post.validators[0].exit_epoch)


def test_slash_validator(state):
    from grandine_tpu.types.primitives import Phase

    draft = StateDraft(state, CFG)
    mutators.slash_validator(draft, 5, Phase.DENEB)
    post = draft.commit()
    v = post.validators[5]
    assert bool(v.slashed)
    assert int(v.exit_epoch) != FAR_FUTURE_EPOCH
    assert int(v.withdrawable_epoch) >= P.EPOCHS_PER_SLASHINGS_VECTOR
    assert int(post.balances[5]) < int(state.balances[5])
    assert int(post.slashings[0]) == int(v.effective_balance)


# ----------------------------------------------------------- verifier seam


def _triple(i: int, msg: bytes = b"\x11" * 32):
    sk = interop_secret_key(i)
    return Triple(msg, sk.sign(msg).to_bytes(), sk.public_key())


def test_null_verifier_accepts_garbage():
    v = NullVerifier()
    v.verify_singular(b"\x00" * 32, b"\x00" * 96, None)
    v.finish()
    assert v.is_null()


def test_single_verifier_eager():
    v = SingleVerifier()
    t = _triple(0)
    v.verify_singular(t.message, t.signature, t.public_key)  # ok, no raise
    bad = bytearray(t.signature)
    t2 = _triple(1)
    with pytest.raises(SignatureInvalid):
        v.verify_singular(t2.message, bytes(t.signature), t2.public_key)


def test_multi_verifier_defers_and_batches():
    v = MultiVerifier()
    triples = [_triple(i, bytes([i]) * 32) for i in range(3)]
    v.extend(triples)
    assert len(v.triples) == 3
    v.finish()  # all good
    assert not v.triples

    v2 = MultiVerifier()
    v2.extend(triples)
    v2.verify_singular(
        triples[0].message, triples[1].signature, triples[0].public_key
    )  # wrong sig for message
    with pytest.raises(SignatureInvalid):
        v2.finish()


def test_multi_verifier_aggregate_path():
    msg = b"\x33" * 32
    sks = [interop_secret_key(i) for i in range(4)]
    agg = A.Signature.aggregate([sk.sign(msg) for sk in sks])
    v = MultiVerifier()
    v.verify_aggregate(msg, agg.to_bytes(), [sk.public_key() for sk in sks])
    v.finish()
    # missing one signer -> fails
    v2 = MultiVerifier()
    partial = A.Signature.aggregate([sk.sign(msg) for sk in sks[:3]])
    v2.verify_aggregate(msg, partial.to_bytes(), [sk.public_key() for sk in sks])
    with pytest.raises(SignatureInvalid):
        v2.finish()


# ----------------------------------------------------------------- keys


def test_pubkey_cache_and_aggregate():
    pk_bytes = interop_secret_key(0).public_key().to_bytes()
    a = keys.decompress_pubkey(pk_bytes)
    assert keys.decompress_pubkey(pk_bytes) is a
    many = [interop_secret_key(i).public_key() for i in range(3)]
    agg = keys.aggregate_pubkeys([k.to_bytes() for k in many])
    assert agg == A.PublicKey.aggregate(many)
    with pytest.raises(A.BlsError):
        keys.aggregate_pubkeys([])


@pytest.mark.parametrize("first", ["minimal", "mainnet"])
def test_committee_partition_is_cached_per_preset(first):
    """One process can hold states of two presets that share the shuffle
    seed and the active set (the benchmark's tiny cells and the replay
    generator both found 64 validators on the same mix): each preset gets
    ITS partition, whichever came first, not the other's from the cache."""
    seed = bytes([0x5A, first == "minimal"]) * 16
    active = np.arange(64, dtype=np.uint64)
    presets = {"minimal": Config.minimal().preset,
               "mainnet": Config.mainnet().preset}
    order = [first] + [name for name in presets if name != first]
    for name in order:
        p = presets[name]
        got = accessors.committee_partition(seed, active, p)
        want = accessors.committee_count_per_slot(64, p) * p.SLOTS_PER_EPOCH
        assert len(got) == want, name
        assert sorted(int(i) for c in got for i in c) == list(range(64))


# ------------------------------------- the active set, once per registry view


@pytest.fixture(scope="module")
def mainnet_states():
    """Two registries of one process at the mainnet preset's shapes (32
    slots an epoch, 90 shuffle rounds), each on a minimal validator set:
    256 and 320 validators on different mixes."""
    from benchmark.generators.keys import ProgressionKeys

    cfg = Config.mainnet()
    keys_ = ProgressionKeys(320, 39).pubkey_bytes()
    a = interop_genesis_state(256, cfg, pubkeys=keys_[:256])
    b = interop_genesis_state(320, cfg, eth1_block_hash=b"\x17" * 32,
                              pubkeys=keys_)
    return cfg, a, b


def _fresh_committee(state, slot: int, index: int, p) -> np.ndarray:
    """Spec `get_beacon_committee` from the containers alone: the active
    set read validator by validator, the seed, the whole-list shuffle; no
    cache of the accessors."""
    epoch = misc.compute_epoch_at_slot(slot, p)
    active = np.array(
        [i for i, v in enumerate(state.validators)
         if int(v.activation_epoch) <= epoch < int(v.exit_epoch)],
        dtype=np.int64)
    seed = misc.get_seed(state, epoch, DOMAIN_BEACON_ATTESTER, p)
    parts = misc.compute_committee_partition(active, seed, p)
    per_slot = len(parts) // p.SLOTS_PER_EPOCH
    return parts[(slot % p.SLOTS_PER_EPOCH) * per_slot + index]


def _agree(state, epoch: int, p) -> int:
    """Every committee of `epoch` and the attesting indices of every other
    member, memoised against fresh; returns the lookups made."""
    from types import SimpleNamespace

    lookups = 0
    count = accessors.get_committee_count_per_slot(state, epoch, p)
    for slot in range(epoch * p.SLOTS_PER_EPOCH,
                      (epoch + 1) * p.SLOTS_PER_EPOCH):
        for index in range(count):
            want = _fresh_committee(state, slot, index, p)
            got = accessors.get_beacon_committee(state, slot, index, p)
            assert got.tolist() == want.tolist(), (slot, index)
            bits = np.arange(len(want)) % 2 == 0
            data = SimpleNamespace(slot=slot, index=index)
            assert accessors.get_attesting_indices(
                state, data, SimpleNamespace(array=bits), p,
            ).tolist() == want[bits].tolist()
            lookups += 2
    return lookups


@pytest.mark.parametrize("case", ["epoch_boundary", "exit", "two_registries"])
def test_memoised_committees_are_the_fresh_ones(mainnet_states, case):
    """`get_beacon_committee` / `get_attesting_indices` through the active
    set memoised on the registry view agree with a fresh computation: on
    both sides of an epoch boundary (a state processed into the next
    epoch), after an exit takes a validator out of the active set, and
    with two registries looked up in turn. One digest at most is computed
    per (registry view, epoch)."""
    from grandine_tpu.transition.slots import process_slots

    cfg, a, b = mainnet_states
    p = cfg.preset
    digests0 = accessors.active_set_totals()[0]
    if case == "epoch_boundary":
        post = process_slots(a, p.SLOTS_PER_EPOCH, cfg)
        looked = [(a, 0), (a, 1), (post, 1), (post, 2), (a, 1)]
    elif case == "exit":
        draft = StateDraft(a, cfg)
        mutators.initiate_validator_exit(draft, 7)
        post = draft.commit()
        gone = int(post.validators[7].exit_epoch)
        assert gone < FAR_FUTURE_EPOCH
        assert 7 in accessors.get_active_validator_indices(a, gone)
        assert 7 not in accessors.get_active_validator_indices(post, gone)
        looked = [(a, gone), (post, gone - 1), (post, gone), (a, gone)]
    else:
        looked = [(a, 0), (b, 0), (a, 0), (b, 1), (a, 1), (b, 0)]
        assert (accessors.get_beacon_committee(a, 0, 0, p).tolist()
                != accessors.get_beacon_committee(b, 0, 0, p).tolist())
    for state, epoch in looked:
        assert _agree(state, epoch, p) > 0
    views = {(id(accessors.registry_columns(s)), e) for s, e in looked}
    assert accessors.active_set_totals()[0] - digests0 <= len(views)
    # a memoised active set cannot be written through
    active = accessors.get_active_validator_indices(a, 0)
    with pytest.raises(ValueError):
        active[0] = 1


def test_the_active_set_memo_holds_under_threads(mainnet_states):
    """Sixteen threads look up eight epochs of one registry view (twice
    what the memo keeps, so evictions race with lookups) under a short
    switch interval: every answer is the fresh active set and its
    digest, and the memo stays within its epochs."""
    import hashlib
    import sys
    import threading

    cfg, a, _b = mainnet_states
    draft = StateDraft(a, cfg)
    mutators.initiate_validator_exit(draft, 3)
    cols = accessors.registry_columns(draft.commit())
    gone = int(cols.exit_epoch[3])
    epochs = range(gone - 4, gone + 4)
    want = {}
    for e in epochs:
        active = np.array([i for i in range(len(cols))
                           if cols.activation_epoch[i] <= e
                           < cols.exit_epoch[i]], dtype=np.int64)
        want[e] = (active.tolist(), hashlib.blake2b(
            active.tobytes(), digest_size=16).digest())
    assert want[gone][0] != want[gone - 1][0]
    wrong = []

    def look(k: int) -> None:
        for i in range(100):
            e = epochs[(k + i) % len(epochs)]
            active, digest = cols.active_set(e)
            if (active.tolist(), digest) != want[e]:
                wrong.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=look, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert wrong == []
    assert len(cols._active) <= 4
