"""Bulk replay pipeline tests: differential equivalence against the
per-block verifier path, bisection localization of a forged block,
and back-sync full re-verification.

The differential test is the load-bearing one: windowed cross-block
batch verification must produce byte-identical post-states and verdicts
to the legacy one-verifier-per-block path on the same chain.
"""

import pytest

from grandine_tpu.p2p.sync import verify_block_batch
from grandine_tpu.runtime.replay import BulkReplayPipeline, ReplayInvalidBlock
from grandine_tpu.slasher import Slasher
from grandine_tpu.transition.genesis import interop_genesis_state
from grandine_tpu.types.config import Config
from grandine_tpu.validator.duties import produce_attestations, produce_block

CFG = Config.minimal()


@pytest.fixture(scope="module")
def chain():
    """4 signature-dense blocks (proposer + randao + attestation
    aggregates) on the minimal preset."""
    genesis = interop_genesis_state(16, CFG)
    state, blocks, atts = genesis, [], []
    for slot in range(1, 5):
        blk, state = produce_block(
            state, slot, CFG, attestations=atts,
            full_sync_participation=False,
        )
        blocks.append(blk)
        atts = produce_attestations(state, CFG, slot=slot)
    return genesis, blocks


def test_bulk_replay_differential(chain):
    genesis, blocks = chain
    ref = verify_block_batch(genesis, blocks, CFG, bulk=False)
    pipe = BulkReplayPipeline(CFG, window_size=2, slasher=Slasher())
    posts = pipe.replay(genesis, blocks)
    assert len(posts) == len(ref)
    for bulk_post, ref_post in zip(posts, ref):
        assert bulk_post.hash_tree_root() == ref_post.hash_tree_root()
    assert pipe.stats["windows"] == 2  # 2+2
    assert pipe.stats["blocks"] == 4
    # cross-block batching actually happened: more signature sets than
    # blocks (block sig + randao at minimum), fed from shared windows
    assert pipe.stats["sigsets"] >= 2 * len(blocks)
    # every replayed attestation reached the slasher
    assert pipe.stats["slasher_attestations"] > 0
    assert pipe.stats["slasher_hits"] == 0


def test_forged_block_localized(chain):
    """A valid-point-wrong-message signature on block k fails the window
    batch; split-in-half re-dispatch must name exactly block k and hand
    back the verified posts of every block before it."""
    genesis, blocks = chain
    k = 2
    forged = blocks[k].replace(signature=bytes(blocks[0].signature))
    seq = blocks[:k] + [forged] + blocks[k + 1 :]
    pipe = BulkReplayPipeline(CFG, window_size=len(seq))
    with pytest.raises(ReplayInvalidBlock) as excinfo:
        pipe.replay(genesis, seq)
    err = excinfo.value
    assert err.index == k
    assert err.slot == int(blocks[k].message.slot)
    assert len(err.verified_posts) == k
    assert pipe.stats["localizations"] == 1


def test_verify_block_batch_routes_through_pipeline(chain):
    genesis, blocks = chain
    posts = verify_block_batch(genesis, blocks[:2], CFG, window_size=2)
    assert len(posts) == 2
    with pytest.raises(ReplayInvalidBlock):
        bad = blocks[1].replace(signature=bytes(blocks[0].signature))
        verify_block_batch(genesis, [blocks[0], bad], CFG)


def test_back_sync_reverifies_through_pipeline():
    """A back-synced node with a stored genesis state re-verifies every
    signature of the filled history through the pipeline."""
    from grandine_tpu.consensus.verifier import NullVerifier
    from grandine_tpu.fork_choice.store import Tick, TickKind
    from grandine_tpu.p2p import InMemoryHub
    from grandine_tpu.p2p.sync import back_sync
    from grandine_tpu.runtime import AttestationVerifier, Controller
    from grandine_tpu.storage import Database, Storage
    from grandine_tpu.storage.storage import (
        PREFIX_BLOCK,
        PREFIX_SLOT_INDEX,
        _slot_key,
    )

    genesis = interop_genesis_state(16, CFG)
    hub = InMemoryHub()
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier)
    transport_a = hub.join("alice")
    ver = AttestationVerifier(ctrl, use_device=False, deadline_s=0.01)
    from grandine_tpu.p2p import Network

    net = Network(transport_a, ctrl, CFG, attestation_verifier=ver)
    state, blocks = genesis, {}
    try:
        for slot in range(1, 4):
            blk, state = produce_block(
                state, slot, CFG, full_sync_participation=False
            )
            blocks[slot] = blk
            ctrl.on_tick(Tick(slot, TickKind.PROPOSE))
            ctrl.on_own_block(blk)
            ctrl.wait()

        storage = Storage(Database.in_memory(), CFG)
        storage.persist_anchor(genesis)  # pre-anchor state available
        anchor = blocks[3]
        root = anchor.message.hash_tree_root()
        storage.db.put(PREFIX_BLOCK + root, anchor.serialize())
        storage.db.put(_slot_key(PREFIX_SLOT_INDEX, 3), root)

        transport_b = hub.join("dave")
        stats = back_sync(storage, transport_b, CFG, anchor_slot=3)
        assert stats["stored"] == 2
        assert stats["off_chain"] == 0
        assert stats["reverified"] == 2  # full signature re-verification
    finally:
        ver.stop()
        ctrl.stop()
    assert net is not None


def test_synthesized_chain_replays_with_the_counted_signature_sets():
    """tools/replay_bench.py's generators (what a replay cell makes its
    chain with): a mainnet-preset chain of full-committee aggregates and
    full sync aggregates, signed through closed-form progression scalars,
    passes full verification on the host anchor and carries exactly the
    signature sets the synthesis counted."""
    from tools.replay_bench import (
        ApKeys,
        FastSigner,
        build_config,
        build_genesis,
        synthesize_chain,
    )

    n = 64
    cfg = build_config(n)
    ap = ApKeys(n)
    genesis = build_genesis(n, cfg, ap)
    blocks, set_counts = synthesize_chain(
        genesis, cfg, ap, FastSigner(use_device=False), 2
    )
    # proposer + randao + sync aggregate, and one aggregate a committee
    # of the slot before (one committee a slot at 64 validators)
    assert set_counts == [3, 4]
    pipe = BulkReplayPipeline(cfg, window_size=2)
    posts = pipe.replay(genesis, blocks)
    assert int(posts[-1].slot) == 2
    assert pipe.stats["sigsets"] == sum(set_counts)
