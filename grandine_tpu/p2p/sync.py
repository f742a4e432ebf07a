"""Block sync — reference: p2p/src/block_sync_service.rs + sync_manager.rs
(range/root request tracking), back_sync.rs (reverse fill to genesis with
batch verification), block_verification_pool.rs:76-129 (two-epoch block
batches verified against one head state).
"""

from __future__ import annotations

import logging
from typing import Optional

from grandine_tpu.consensus.verifier import MultiVerifier, NullVerifier
from grandine_tpu.types.combined import decode_signed_block

logger = logging.getLogger("grandine.sync")


class SyncManager:
    """Tracks peer statuses and picks sync targets
    (sync_manager.rs / range_and_root_requests.rs)."""

    def __init__(self, transport) -> None:
        self.transport = transport
        self.peer_status: "dict[str, dict]" = {}

    def refresh(self) -> None:
        for peer in self.transport.peers():
            try:
                self.peer_status[peer] = self.transport.request_status(peer)
            except ConnectionError:
                self.peer_status.pop(peer, None)

    def best_peer(self) -> "Optional[str]":
        if not self.peer_status:
            return None
        return max(
            self.peer_status, key=lambda p: self.peer_status[p]["head_slot"]
        )

    def target_slot(self) -> int:
        return max(
            (s["head_slot"] for s in self.peer_status.values()), default=0
        )


class BlockSyncService:
    """Forward range sync: while the head lags the best peer, request
    slot ranges and feed them through the controller's normal validation
    (block_sync_service shape; the controller's delayed-maps handle
    out-of-order arrival)."""

    def __init__(self, transport, controller, cfg,
                 batch_size: "Optional[int]" = None,
                 bulk_verify: bool = False,
                 replay_pipeline=None) -> None:
        self.transport = transport
        self.controller = controller
        self.cfg = cfg
        self.sync_manager = SyncManager(transport)
        # two epochs per round, like the reference's verification pool
        self.batch_size = batch_size or 2 * cfg.preset.SLOTS_PER_EPOCH
        #: bulk mode: verify a fetched range as ONE cross-block batch
        #: through the replay pipeline, then import trusted — any
        #: pipeline failure degrades to the per-block path, which stays
        #: the arbiter of validity
        self.bulk_verify = bulk_verify
        self._pipeline = replay_pipeline
        self.stats = {"requested": 0, "applied_batches": 0,
                      "root_requests": 0, "blob_requests": 0,
                      "bulk_blocks": 0, "bulk_fallbacks": 0}
        # resolve delayed-by-parent blocks via BlocksByRoot instead of
        # waiting for the next range round (p2p/src/network.rs:911-912)
        if hasattr(controller, "on_unknown_parent"):
            controller.on_unknown_parent.append(self._on_unknown_parent)

    def _on_unknown_parent(self, parent_root: bytes) -> None:
        """Mutator-thread hook: fetch the missing parent off-thread."""
        def task() -> None:
            self.sync_manager.refresh()
            peer = self.sync_manager.best_peer()
            if peer is None:
                return
            try:
                raw = self.transport.request_blocks_by_root(
                    peer, [parent_root]
                )
            except Exception:
                return  # range sync remains the fallback
            self.stats["root_requests"] += 1
            for data in raw:
                try:
                    block = decode_signed_block(data, self.cfg)
                except Exception:
                    continue
                self.controller.on_requested_block(block)

        from grandine_tpu.runtime.thread_pool import Priority

        self.controller.pool.spawn(task, Priority.LOW)

    def _fetch_blobs(self, peer: str, blocks) -> None:
        """Range-synced deneb blocks need their sidecars before the blob
        gate lets them import (BlobsByRange; p2p/src/network.rs:15)."""
        need = [
            b for b in blocks
            if getattr(b.message.body, "blob_kzg_commitments", None)
        ]
        if not need:
            return
        lo = min(int(b.message.slot) for b in need)
        hi = max(int(b.message.slot) for b in need)
        try:
            raw = self.transport.request_blobs_by_range(peer, lo, hi - lo + 1)
        except Exception:
            return
        self.stats["blob_requests"] += len(raw)
        from grandine_tpu.types.containers import spec_types

        ns = spec_types(self.cfg.preset).deneb
        for data in raw:
            try:
                sidecar = ns.BlobSidecar.deserialize(data)
            except Exception:
                continue
            self.controller.on_gossip_blob_sidecar(sidecar)

    def sync_once(self) -> bool:
        """One round: returns True when more work remains."""
        self.sync_manager.refresh()
        peer = self.sync_manager.best_peer()
        if peer is None:
            return False
        snap = self.controller.snapshot()
        head_slot = int(snap.head_state.slot)
        target = self.sync_manager.target_slot()
        if head_slot >= target:
            return False
        # walk windows upward past empty stretches (a >= batch_size gap of
        # empty slots must not stall the sync or fake completion)
        start = head_slot + 1
        blocks = []
        while start <= target:
            raw_blocks = self.transport.request_blocks_by_range(
                peer, start, self.batch_size
            )
            self.stats["requested"] += len(raw_blocks)
            blocks = [decode_signed_block(raw, self.cfg) for raw in raw_blocks]
            if blocks:
                break
            start += self.batch_size
        if blocks:
            # advance the local clock only to slots we actually RECEIVED
            # blocks for — never to a peer's unverified head_slot claim
            # (a malicious Status could fast-forward our clock arbitrarily)
            from grandine_tpu.fork_choice.store import Tick, TickKind

            max_received = max(int(b.message.slot) for b in blocks)
            self.controller.on_tick(Tick(max_received, TickKind.AGGREGATE))
            self._fetch_blobs(peer, blocks)
        if not (self.bulk_verify and self._bulk_import(snap, blocks)):
            for block in blocks:
                self.controller.on_requested_block(block)
        self.controller.wait()
        self.stats["applied_batches"] += 1
        head = int(self.controller.snapshot().head_state.slot)
        return bool(blocks) and head < target

    def _bulk_import(self, snap, blocks) -> bool:
        """Verify a fetched range as ONE cross-block pipeline batch against
        the head state, then import trusted. Returns False (per-block
        fallback) when the range is not a contiguous chain off the head,
        or when the pipeline rejects anything — the per-block path stays
        the arbiter of validity and will name the bad block."""
        if not blocks:
            return False
        ordered = sorted(blocks, key=lambda b: int(b.message.slot))
        parent = bytes(snap.head_root)
        for b in ordered:
            if bytes(b.message.parent_root) != parent:
                self.stats["bulk_fallbacks"] += 1
                return False
            parent = bytes(b.message.hash_tree_root())
        if self._pipeline is None:
            from grandine_tpu.runtime.replay import BulkReplayPipeline

            self._pipeline = BulkReplayPipeline(self.cfg)
        try:
            self._pipeline.replay(snap.head_state, ordered)
        except Exception as e:
            logger.warning("bulk range verification failed (%s); "
                           "falling back to per-block import", e)
            self.stats["bulk_fallbacks"] += 1
            return False
        for b in ordered:
            self.controller.on_verified_block(b)
        self.stats["bulk_blocks"] += len(ordered)
        return True

    def sync_to_head(self, max_rounds: int = 1000) -> None:
        for _ in range(max_rounds):
            if not self.sync_once():
                return
        raise TimeoutError("sync did not converge")


def back_sync(storage, transport, cfg, anchor_slot: int,
              peer: "Optional[str]" = None, batch_size: int = 64,
              verify: bool = True, use_device: bool = False,
              window_size: "Optional[int]" = None,
              slasher=None) -> dict:
    """Reverse-fill history below a checkpoint anchor down to genesis
    (back_sync.rs): request ranges below `anchor_slot`, check hash-chain
    linkage child->parent, persist to the finalized schema. Returns a
    stats dict: ``stored`` blocks persisted, ``off_chain`` blocks dropped
    for not being on the anchored chain, ``reverified`` blocks whose
    signatures were re-checked.

    With verify=True the linkage to the trusted anchor root guards
    integrity during the fill; once the fill reaches a stored genesis
    state the whole history is additionally replayed through the bulk
    pipeline for FULL signature re-verification (closing the reference's
    `TrustBackSyncBlocks` escape hatch). Checkpoint-sync nodes whose
    first anchor IS the checkpoint have no pre-anchor state to replay
    from; they keep linkage-only verification (logged once)."""
    from grandine_tpu.storage.storage import (
        PREFIX_BLOCK,
        PREFIX_SLOT_INDEX,
        _slot_key,
    )

    stats = {"stored": 0, "off_chain": 0, "reverified": 0}
    if peer is None:
        peers = transport.peers()
        if not peers:
            return stats
        peer = peers[0]

    # expected root of the next (lower) block comes from the anchor chain
    anchor_root = storage.finalized_root_by_slot(anchor_slot)
    expected_parent = None
    if anchor_root is not None:
        anchor_block = storage.finalized_block_by_root(anchor_root)
        if anchor_block is not None:
            expected_parent = bytes(anchor_block.message.parent_root)
    if verify and expected_parent is None:
        # without the anchor's parent root there is nothing to chain the
        # fetched history to — refusing beats storing unverified blocks
        # as finalized
        raise LookupError(
            f"no anchor block stored at slot {anchor_slot}; cannot verify "
            "back-synced history"
        )

    slot_hi = anchor_slot - 1
    while slot_hi >= 0:
        start = max(0, slot_hi - batch_size + 1)
        raws = transport.request_blocks_by_range(peer, start, slot_hi - start + 1)
        blocks = [decode_signed_block(r, cfg) for r in raws]
        blocks.sort(key=lambda b: -int(b.message.slot))  # high -> low
        items = []
        off_chain = 0
        for block in blocks:
            root = block.message.hash_tree_root()
            if verify and expected_parent is not None and root != expected_parent:
                off_chain += 1
                continue  # not on the anchored chain
            items.append((PREFIX_BLOCK + root, block.serialize()))
            items.append(
                (_slot_key(PREFIX_SLOT_INDEX, int(block.message.slot)), root)
            )
            expected_parent = bytes(block.message.parent_root)
            stats["stored"] += 1
        if off_chain:
            stats["off_chain"] += off_chain
            logger.warning(
                "back_sync: dropped %d off-anchor-chain block(s) in "
                "slots [%d, %d] from peer %s", off_chain, start, slot_hi,
                peer,
            )
        storage.db.put_batch(items)
        # an empty window just moves the cursor down (long empty stretches
        # are normal); the loop ends when the window reaches genesis
        slot_hi = start - 1
        if start == 0:
            break

    if verify and stats["stored"]:
        stats["reverified"] = _reverify_back_synced(
            storage, cfg, anchor_slot, use_device=use_device,
            window_size=window_size, slasher=slasher,
        )
    return stats


def _reverify_back_synced(storage, cfg, anchor_slot: int, *,
                          use_device: bool = False,
                          window_size: "Optional[int]" = None,
                          slasher=None) -> int:
    """Full signature re-verification of the back-synced range through
    the bulk replay pipeline, anchored at the stored genesis state.
    Raises ReplayInvalidBlock on a bad signature; returns the number of
    blocks re-verified (0 when no pre-anchor state exists to replay
    from — the checkpoint-sync case)."""
    genesis = storage.load_genesis_state()
    if genesis is None or int(genesis.slot) >= anchor_slot:
        logger.warning(
            "back_sync: no pre-anchor state available; back-synced "
            "history below slot %d keeps linkage-only verification",
            anchor_slot,
        )
        return 0
    blocks = []
    for slot in range(int(genesis.slot) + 1, anchor_slot):
        root = storage.finalized_root_by_slot(slot)
        if root is None:
            continue  # empty slot
        block = storage.finalized_block_by_root(root)
        if block is not None:
            blocks.append(block)
    if not blocks:
        return 0
    from grandine_tpu.runtime.replay import (
        DEFAULT_WINDOW_BLOCKS,
        BulkReplayPipeline,
    )

    pipeline = BulkReplayPipeline(
        cfg, use_device=use_device,
        window_size=window_size or DEFAULT_WINDOW_BLOCKS,
        slasher=slasher,
    )
    pipeline.replay(genesis, blocks)
    logger.info("back_sync: re-verified %d block(s) of back-synced "
                "history (%d signature sets)", len(blocks),
                pipeline.stats["sigsets"])
    return len(blocks)


def verify_block_batch(anchor_state, blocks, cfg, use_device: bool = False,
                       bulk: bool = True,
                       window_size: "Optional[int]" = None,
                       slasher=None):
    """Batch verification against one base state
    (block_verification_pool.rs:76-129), returning the post states and
    raising on the first invalid block.

    bulk=True (default) routes through the BulkReplayPipeline: ONE
    cross-block batch per window instead of one dispatch per block.
    bulk=False keeps the legacy shape — a fresh verifier and one RLC
    batch PER BLOCK — as the per-block baseline
    (tests/test_replay.py holds the two to the same post-states)."""
    if bulk:
        from grandine_tpu.runtime.replay import (
            DEFAULT_WINDOW_BLOCKS,
            BulkReplayPipeline,
        )

        pipeline = BulkReplayPipeline(
            cfg, use_device=use_device,
            window_size=window_size or DEFAULT_WINDOW_BLOCKS,
            slasher=slasher,
        )
        return pipeline.replay(anchor_state, blocks)
    from grandine_tpu.consensus.verifier import TpuVerifier
    from grandine_tpu.transition.combined import custom_state_transition

    state = anchor_state
    posts = []
    for block in blocks:
        verifier = TpuVerifier() if use_device else MultiVerifier()
        state = custom_state_transition(state, block, cfg, verifier)
        posts.append(state)
    return posts


__all__ = [
    "SyncManager",
    "BlockSyncService",
    "back_sync",
    "verify_block_batch",
]
