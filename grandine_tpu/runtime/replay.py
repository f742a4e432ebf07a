"""Bulk replay pipeline — cross-block batched signature verification for
back-sync, checkpoint catch-up, and historical slashing surveillance.

`verify_block_batch` historically built one fresh verifier and one RLC
device dispatch PER BLOCK (CONFIG3: 16.8 signature-sets/s), and
`back_sync` skipped signature re-verification entirely (the reference's
`TrustBackSyncBlocks` escape hatch). Replay is the one verify workload
whose batch size is NOT bounded by gossip deadlines, so the right shape
is the opposite of the firehose's: run `custom_state_transition` over a
WINDOW of N blocks with a `CollectingVerifier` (consensus/verifier.py)
that defers every signature — proposer, randao, attestation aggregates,
sync aggregates, operations — into ONE shared pow-2-bucketed RLC batch
on the device multi_verify kernel (one Miller loop per signature set
and one final exponentiation per WINDOW, vs one kernel dispatch and one
padded bucket per block in the legacy path).

Stages (two-deep dispatch overlap, mirroring attestation_verifier.py):

  transition_collect  optimistic state transition over the window; all
                      signature checks accumulate into the window sink
  dispatch            host prep + async device dispatch of the combined
                      batch (readback stays in the settle closure)
  settle              force the batch verdict; window W+1's transition
                      ran while window W's batch was on the device
  commit              feed every replayed attestation and block header
                      through the Slasher (historical surround/double-
                      vote surveillance) — only for VERIFIED blocks

A failed window batch triggers O(log n) split-in-half re-dispatch at
block granularity (the verify scheduler's bisection shape — never a
linear per-signature host walk): each probe re-dispatches half the
remaining item range as one batch, descending into the failing half
until one block remains, whose items are then checked individually to
name the offending signature.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Optional, Sequence

from grandine_tpu.consensus import accessors
from grandine_tpu.consensus.verifier import (
    CollectingVerifier,
    SignatureInvalid,
)
from grandine_tpu.crypto import bls as A
from grandine_tpu.runtime import flight as _flight
from grandine_tpu.runtime.verify_scheduler import VerifyItem, host_check_item
from grandine_tpu.tracing import NULL_TRACER, stage as _stage

logger = logging.getLogger("grandine.replay")

#: default blocks per window — two epochs of minimal preset / a quarter
#: epoch of mainnet; the sweet spot where per-dispatch overhead amortizes
#: without holding more than a few thousand signature sets per batch
DEFAULT_WINDOW_BLOCKS = 32
#: windows in flight (dispatched, not settled): the same two-deep bound
#: the firehose uses — window W+1 transitions while W is on the device
DEFAULT_PIPELINE_DEPTH = 2


class ReplayInvalidBlock(SignatureInvalid):
    """A window batch failed and bisection localized the offending block.
    `index` is the position in the replayed sequence, `verified_posts`
    the post-states of every block BEFORE it (all verified)."""

    def __init__(self, index: int, slot: int, root: bytes, reason: str,
                 verified_posts: "Sequence" = ()) -> None:
        super().__init__(
            f"replay block {index} (slot {slot}, root {root.hex()[:16]}…) "
            f"failed verification: {reason}"
        )
        self.index = index
        self.slot = slot
        self.root = bytes(root)
        self.verified_posts = list(verified_posts)


class _WindowSink:
    """CollectingVerifier sink for one window: VerifyItems in collection
    order (per-block contiguous, so a (lo, hi) slice names one block's
    signature sets)."""

    __slots__ = ("items",)

    def __init__(self) -> None:
        self.items: "list[VerifyItem]" = []

    def add(self, message, signature, public_keys=None,
            member_indices=None, pubkey_columns=None) -> None:
        self.items.append(VerifyItem(
            message, signature, public_keys=public_keys,
            member_indices=member_indices, pubkey_columns=pubkey_columns,
        ))


class _Window:
    """One window's optimistic results, held until the batch settles."""

    __slots__ = ("blocks", "posts", "items", "slices", "slasher_feed",
                 "start_index", "t0")

    def __init__(self, blocks, start_index: int) -> None:
        self.blocks = list(blocks)
        self.start_index = start_index
        self.posts: list = []
        self.items: "list[VerifyItem]" = []
        #: per-block [lo, hi) into `items`
        self.slices: "list[tuple[int, int]]" = []
        #: per-block (proposer, slot, root, [(indices, src, tgt, droot)])
        self.slasher_feed: list = []
        self.t0 = time.perf_counter()


class BulkReplayPipeline:
    """Verify a historical block sequence with cross-block device batches.

    `replay(anchor_state, blocks)` returns the post-state of every block,
    raising `ReplayInvalidBlock` (bisection-localized) on a bad signature
    or the underlying `TransitionError`/`StateRootMismatch` on a
    structurally invalid block. With `slasher` set, every verified
    block's attestations and header feed the slashing database, so
    back-fill doubles as historical surveillance.

    Thread ownership: `replay` drives everything on the CALLING thread;
    window state is single-owned and only the injected scheduler's own
    threads run concurrently behind the ticket API."""

    def __init__(
        self,
        cfg,
        *,
        use_device: bool = False,
        backend=None,
        window_size: int = DEFAULT_WINDOW_BLOCKS,
        pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
        slasher=None,
        metrics=None,
        tracer=None,
        flight=None,
        state_root_policy: str = "verify",
    ) -> None:
        self.cfg = cfg
        self.use_device = use_device
        if use_device and backend is None:
            from grandine_tpu.tpu import schemes

            backend = schemes.get("bls").make_backend(
                metrics=metrics, tracer=tracer, lane="replay"
            )
        self.backend = backend
        #: flight recorder: one record per window in the "replay" lane
        self.flight = (
            flight if flight is not None
            else _flight.FlightRecorder(metrics=metrics)
        )
        self.window_size = max(1, int(window_size))
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.slasher = slasher
        #: brownout gate: cleared by the BrownoutController at B3 to
        #: pause bulk replay between windows (live duties outrank
        #: catch-up); set again on recovery. Starts open.
        self.run_gate = threading.Event()
        self.run_gate.set()
        self.metrics = metrics
        self.tracer = tracer or NULL_TRACER
        self.state_root_policy = state_root_policy
        self.stats = {
            "windows": 0, "blocks": 0, "sigsets": 0, "localizations": 0,
            "slasher_attestations": 0, "slasher_hits": 0,
            "slasher_errors": 0,
        }

    # ------------------------------------------------------------- driver

    def replay(self, anchor_state, blocks) -> list:
        """Replay `blocks` (a parent→child chain extending `anchor_state`)
        through windowed batch verification; returns all post-states."""
        blocks = list(blocks)
        posts: list = []
        pending: "deque[tuple[_Window, object, object]]" = deque()
        state = anchor_state
        device = self.use_device and self.backend is not None
        kernel = "multi_verify" if device else "host"
        try:
            for w0 in range(0, len(blocks), self.window_size):
                # brownout B3 pauses catch-up at window granularity —
                # in-flight windows still settle, new ones wait here
                self.run_gate.wait()
                chunk = blocks[w0 : w0 + self.window_size]
                window, state = self._transition_and_collect(
                    state, chunk, w0
                )
                fl = self.flight.begin_batch(
                    "replay", kernel, len(window.items)
                )
                t0 = time.perf_counter()
                settle = self._dispatch_batch(window.items)
                (fl.note_device if device else fl.note_host)(
                    time.perf_counter() - t0
                )
                if device:
                    self.flight.device_enter()
                pending.append((window, settle, fl))
                self._note_depth(len(pending))
                while len(pending) > self.pipeline_depth:
                    self._settle_window(*pending.popleft(), posts=posts)
                    self._note_depth(len(pending))
        except Exception:
            # a bad signature in an ALREADY-DISPATCHED window outranks
            # whatever just went wrong downstream of it: settle the
            # in-flight windows first (their failure replaces this one)
            while pending:
                self._settle_window(*pending.popleft(), posts=posts)
            raise
        while pending:
            self._settle_window(*pending.popleft(), posts=posts)
            self._note_depth(len(pending))
        return posts

    def _note_depth(self, depth: int) -> None:
        if self.metrics is not None:
            self.metrics.replay_pipeline_depth.set(depth)

    def _stage(self, stage: str, **attrs):
        """The shared stage helper (tracing.stage) on lane "replay"."""
        return _stage(self.tracer, self.metrics, stage, "replay", **attrs)

    # --------------------------------------------------- transition+collect

    def _transition_and_collect(self, state, chunk, start_index: int):
        """Optimistically transition the window, deferring every signature
        into the window sink; records per-block item slices (for the
        bisection) and the slasher feed entries (committed after the
        batch verdict)."""
        from grandine_tpu.transition.combined import custom_state_transition

        sink = _WindowSink()
        verifier = CollectingVerifier(sink)
        window = _Window(chunk, start_index)
        window.items = sink.items
        with self._stage("transition_collect", blocks=len(chunk)):
            for blk in chunk:
                lo = len(sink.items)
                post = custom_state_transition(
                    state, blk, self.cfg, verifier,
                    state_root_policy=self.state_root_policy,
                )
                window.slices.append((lo, len(sink.items)))
                window.posts.append(post)
                if self.slasher is not None:
                    window.slasher_feed.append(
                        self._slasher_entries(post, blk)
                    )
                state = post
        return window, state

    def _slasher_entries(self, post, signed_block):
        """(proposer, slot, root, [(indices, source, target, data_root)])
        for one block, resolved against the post-state (its committees
        cover the attestations' current-and-previous-epoch slots)."""
        block = signed_block.message
        atts = []
        p = self.cfg.preset
        for att in block.body.attestations:
            try:
                indices = accessors.get_attesting_indices(
                    post, att.data, att.aggregation_bits, p
                )
            except Exception:
                self.stats["slasher_errors"] += 1
                continue
            atts.append((
                [int(i) for i in indices],
                int(att.data.source.epoch),
                int(att.data.target.epoch),
                bytes(att.data.hash_tree_root()),
            ))
        return (
            int(block.proposer_index),
            int(block.slot),
            bytes(block.hash_tree_root()),
            atts,
        )

    # ----------------------------------------------------------- dispatch

    def _dispatch_batch(self, items: "Sequence[VerifyItem]"):
        """Host prep + async dispatch of one cross-block batch; returns a
        zero-arg settle callable producing the batch verdict. Readback
        happens only inside the settle closures."""
        if not items:
            return lambda: True
        if self.use_device and self.backend is not None:
            settle = self._device_dispatch(items)
            if settle is not None:
                return settle
        return self._host_dispatch(items)

    def _device_dispatch(self, items: "Sequence[VerifyItem]"):
        """ONE RLC multi_verify kernel dispatch for the whole window.

        The firehose needs per-item verdicts (gossip attribution), so it
        pays the fast-aggregate kernels' two pairings per item. Replay
        does not: a window wants a single combined verdict — attribution
        comes from the bisection, not the kernel — so the RLC batch
        kernel (one Miller loop per item, one final exponentiation per
        WINDOW) is the right shape, exactly the per-block TpuVerifier
        kernel but dispatched once per window instead of once per block.
        Signatures decompress WITHOUT the per-item host subgroup
        scalar-mul; the device ψ-ladder batch check covers them."""
        backend = self.backend
        if not (
            hasattr(backend, "multi_verify_async")
            and hasattr(backend, "g2_subgroup_check_batch_async")
        ):
            return None
        try:
            points = [
                A.g2_from_bytes(it.signature, subgroup_check=False)
                for it in items
            ]
        except A.BlsError:
            return lambda: False
        if any(p.is_infinity() for p in points):
            return lambda: False
        try:
            pks = [
                resolved[0] if len(resolved) == 1
                else A.PublicKey.aggregate(resolved)
                for resolved in (it.resolve_keys() for it in items)
            ]
        except SignatureInvalid:
            return lambda: False
        sub_settle = backend.g2_subgroup_check_batch_async(points)
        sigs = [A.Signature(p) for p in points]
        if self.metrics is not None:
            self.metrics.device_batch_sigs.inc(len(sigs))
        mv_settle = backend.multi_verify_async(
            [it.message for it in items], sigs, pks
        )

        def settle() -> bool:
            if not bool(sub_settle().all()):
                return False
            return bool(mv_settle())

        return settle

    def _host_dispatch(self, items: "Sequence[VerifyItem]"):
        """MultiVerifier semantics over the whole window: aggregate each
        item's signer set host-side, one anchor RLC multi_verify. The
        work is deferred into the settle closure so the dispatch stage
        stays cheap on the host path too."""

        def settle() -> bool:
            messages, signatures, pks = [], [], []
            try:
                for it in items:
                    signatures.append(A.Signature.from_bytes(it.signature))
                    resolved = it.resolve_keys()
                    messages.append(it.message)
                    pks.append(
                        resolved[0] if len(resolved) == 1
                        else A.PublicKey.aggregate(resolved)
                    )
            except (A.BlsError, SignatureInvalid):
                return False
            return A.multi_verify(messages, signatures, pks)

        return settle

    # ------------------------------------------------------------- settle

    def _settle_window(self, window: _Window, settle, fl,
                       posts: list) -> None:
        device = self.use_device and self.backend is not None
        with self._stage("settle", blocks=len(window.blocks)):
            t0 = time.perf_counter()
            try:
                ok = bool(settle())
            finally:
                (fl.note_device if device else fl.note_host)(
                    time.perf_counter() - t0
                )
                if device:
                    self.flight.device_exit()
        if not ok:
            self.stats["localizations"] += 1
            t0 = time.perf_counter()
            k, reason = self._localize(window)
            fl.note_bisect(
                time.perf_counter() - t0,
                depth=max(1, len(window.blocks).bit_length()),
            )
            fl.finish(False)
            posts.extend(window.posts[:k])
            self._commit(window, upto=k)
            blk = window.blocks[k]
            raise ReplayInvalidBlock(
                window.start_index + k,
                int(blk.message.slot),
                blk.message.hash_tree_root(),
                reason,
                posts,
            )
        fl.finish(True)
        self._commit(window, upto=len(window.blocks))
        posts.extend(window.posts)
        self.stats["windows"] += 1
        self.stats["blocks"] += len(window.blocks)
        self.stats["sigsets"] += len(window.items)
        if self.metrics is not None:
            self.metrics.replay_blocks.inc(len(window.blocks))
            self.metrics.replay_sigsets.inc(len(window.items))
            self.metrics.replay_window_seconds.observe(
                time.perf_counter() - window.t0
            )

    def _localize(self, window: _Window) -> "tuple[int, str]":
        """First invalid block of a failed window: split-in-half
        re-dispatch (O(log n) batch probes, the scheduler's `_isolate`
        shape), then an item-level host check of the single remaining
        block to name the offending signature."""

        def batch_ok(b_lo: int, b_hi: int) -> bool:
            i_lo = window.slices[b_lo][0]
            i_hi = window.slices[b_hi - 1][1]
            half = window.items[i_lo:i_hi]
            if not half:
                return True
            return bool(self._dispatch_batch(half)())

        lo, hi = 0, len(window.blocks)
        while hi - lo > 1:
            mid = (lo + hi) // 2
            if batch_ok(lo, mid):
                # the left half verifies as a batch → the FIRST invalid
                # block is in the right half
                lo = mid
            else:
                hi = mid
        s_lo, s_hi = window.slices[lo]
        for j in range(s_lo, s_hi):
            if not host_check_item(window.items[j]):
                return lo, (
                    f"signature set {j - s_lo + 1} of {s_hi - s_lo} invalid"
                )
        # every item of the leaf passes individually: the batch verdict
        # came from a device fault/wrong verdict, not this block's data
        return lo, "window batch rejected (leaf items verify individually)"

    # ------------------------------------------------------------- commit

    def _commit(self, window: _Window, upto: int) -> None:
        """Feed the slasher the VERIFIED prefix of the window: every
        replayed attestation (surround/double-vote surveillance over
        history) and every block header (double-proposal)."""
        if self.slasher is None or upto == 0 or not window.slasher_feed:
            return
        with self._stage("commit", blocks=upto):
            # block headers stay per-block (double-proposal checks are a
            # single K-V probe each); the window's attestations feed the
            # slasher in ONE bulk call so span updates merge into a
            # handful of vectorized chunk passes — or one device grid
            # dispatch — instead of a Python walk per attesting index
            flat: "list[tuple]" = []   # (slot, att) per attestation
            for proposer, slot, root, atts in window.slasher_feed[:upto]:
                try:
                    if self.slasher.on_block(proposer, slot, root):
                        self.stats["slasher_hits"] += 1
                except Exception:
                    # surveillance is best-effort: a slasher fault must
                    # not abort an otherwise verified replay
                    self.stats["slasher_errors"] += 1
                for att in atts:
                    flat.append((slot, att))
            if not flat:
                return
            try:
                hit_lists = self.slasher.on_attestations_bulk(
                    [att for _slot, att in flat]
                )
            except Exception:
                self.stats["slasher_errors"] += 1
                return
            for (slot, att), hits in zip(flat, hit_lists):
                target = att[2]
                self.stats["slasher_attestations"] += 1
                self.stats["slasher_hits"] += len(hits)
                for hit in hits:
                    rec = self.slasher.record_for(
                        hit.validator_index, target
                    )
                    logger.warning(
                        "historical %s by validator %d at slot %d"
                        " (recorded vote: %s)", hit.kind,
                        hit.validator_index, slot,
                        rec and (rec[0], rec[1].hex()[:16]),
                    )


__all__ = [
    "BulkReplayPipeline",
    "ReplayInvalidBlock",
    "DEFAULT_WINDOW_BLOCKS",
    "DEFAULT_PIPELINE_DEPTH",
]
