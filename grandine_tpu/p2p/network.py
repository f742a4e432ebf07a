"""Gossip transport + network service — reference: p2p/src/network.rs
(`Network::run` select loop :204, gossip dispatch :1411-1445, publishes
:539-560) over the eth2_libp2p behaviours.

`Transport` is the seam a libp2p backend implements; `InMemoryHub` is the
in-process mesh used by tests and the devnet. Payloads on the wire are
ssz_snappy (the real encoding), topics carry the fork digest.
"""

from __future__ import annotations

import inspect
import threading
from collections import defaultdict
from typing import Callable, Optional

from grandine_tpu.consensus import misc
from grandine_tpu.spec_tests.snappy import frame_compress, frame_decompress


class GossipTopics:
    """Topic name construction (consensus networking spec)."""

    @staticmethod
    def fork_digest(cfg, state) -> bytes:
        return misc.compute_fork_digest(
            bytes(state.fork.current_version),
            bytes(state.genesis_validators_root),
        )

    @staticmethod
    def beacon_block(digest: bytes) -> str:
        return f"/eth2/{digest.hex()}/beacon_block/ssz_snappy"

    @staticmethod
    def beacon_attestation(digest: bytes, subnet: int) -> str:
        return f"/eth2/{digest.hex()}/beacon_attestation_{subnet}/ssz_snappy"

    @staticmethod
    def aggregate_and_proof(digest: bytes) -> str:
        return f"/eth2/{digest.hex()}/beacon_aggregate_and_proof/ssz_snappy"

    @staticmethod
    def voluntary_exit(digest: bytes) -> str:
        return f"/eth2/{digest.hex()}/voluntary_exit/ssz_snappy"

    @staticmethod
    def blob_sidecar(digest: bytes, subnet: int) -> str:
        return f"/eth2/{digest.hex()}/blob_sidecar_{subnet}/ssz_snappy"

    @staticmethod
    def sync_committee(digest: bytes, subnet: int) -> str:
        return f"/eth2/{digest.hex()}/sync_committee_{subnet}/ssz_snappy"

    @staticmethod
    def sync_committee_contribution(digest: bytes) -> str:
        return (
            f"/eth2/{digest.hex()}"
            "/sync_committee_contribution_and_proof/ssz_snappy"
        )

    @staticmethod
    def proposer_slashing(digest: bytes) -> str:
        return f"/eth2/{digest.hex()}/proposer_slashing/ssz_snappy"

    @staticmethod
    def attester_slashing(digest: bytes) -> str:
        return f"/eth2/{digest.hex()}/attester_slashing/ssz_snappy"

    @staticmethod
    def bls_to_execution_change(digest: bytes) -> str:
        return f"/eth2/{digest.hex()}/bls_to_execution_change/ssz_snappy"


class Transport:
    """What a WAN backend provides: pubsub + the req/resp protocols
    (Status, BlocksByRange/Root, BlobsByRange/Root — p2p/src/network.rs
    :13-24,911-912)."""

    def publish(self, topic: str, payload: bytes) -> None:
        raise NotImplementedError

    def subscribe(self, topic: str, handler: "Callable[[str, bytes], None]") -> None:
        """Handlers taking a third positional argument additionally
        receive the sending peer's id (failure-attribution feed for the
        flight recorder); two-argument handlers keep working unchanged."""
        raise NotImplementedError

    def peers(self) -> "list[str]":
        raise NotImplementedError

    def request_blocks_by_range(
        self, peer: str, start_slot: int, count: int
    ) -> "list[bytes]":
        raise NotImplementedError

    def request_blocks_by_root(
        self, peer: str, roots: "list[bytes]"
    ) -> "list[bytes]":
        raise NotImplementedError

    def request_blobs_by_range(
        self, peer: str, start_slot: int, count: int
    ) -> "list[bytes]":
        raise NotImplementedError

    def request_blobs_by_root(
        self, peer: str, ids: "list[tuple[bytes, int]]"
    ) -> "list[bytes]":
        raise NotImplementedError

    def request_status(self, peer: str) -> dict:
        raise NotImplementedError

    def register_provider(
        self, blocks_by_range, status,
        blocks_by_root=None, blobs_by_range=None, blobs_by_root=None,
    ) -> None:
        """Install the local node's req/resp serving callbacks."""
        raise NotImplementedError


def _handler_accepts_sender(handler) -> bool:
    """Arity probe done ONCE at subscribe time: a handler whose bound
    signature takes a third positional parameter (topic, payload, sender)
    gets the sending peer id on every publish; legacy two-argument
    handlers never see it. Unintrospectable callables (C builtins, some
    mocks) fall back to the legacy shape."""
    try:
        params = list(inspect.signature(handler).parameters.values())
    except (TypeError, ValueError):
        return False
    if any(p.kind == p.VAR_POSITIONAL for p in params):
        return True
    positional = [
        p for p in params
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
    ]
    return len(positional) >= 3


class InMemoryHub:
    """Process-local gossip mesh + req/resp: every joined transport sees
    every publish (except its own); range/status requests are served by
    peer-registered providers."""

    def __init__(self) -> None:
        self._subs: "dict[str, list[tuple[str, Callable]]]" = defaultdict(list)
        self._providers: "dict[str, dict]" = {}
        self._lock = threading.Lock()

    def join(self, peer_id: str) -> "Transport":
        return _HubTransport(self, peer_id)

    def register_provider(
        self, peer_id: str,
        blocks_by_range: "Callable[[int, int], list[bytes]]",
        status: "Callable[[], dict]",
        blocks_by_root=None, blobs_by_range=None, blobs_by_root=None,
    ) -> None:
        with self._lock:
            self._providers[peer_id] = {
                "blocks_by_range": blocks_by_range,
                "status": status,
                "blocks_by_root": blocks_by_root,
                "blobs_by_range": blobs_by_range,
                "blobs_by_root": blobs_by_root,
            }

    # -- hub internals ------------------------------------------------------

    def _publish(self, sender: str, topic: str, payload: bytes) -> None:
        with self._lock:
            handlers = list(self._subs.get(topic, ()))
        for peer_id, handler, wants_sender in handlers:
            if peer_id != sender:
                if wants_sender:
                    handler(topic, payload, sender)
                else:
                    handler(topic, payload)

    def _subscribe(self, peer_id: str, topic: str, handler) -> None:
        wants_sender = _handler_accepts_sender(handler)
        with self._lock:
            self._subs[topic].append((peer_id, handler, wants_sender))

    def _peers(self, excluding: str) -> "list[str]":
        with self._lock:
            return [p for p in self._providers if p != excluding]

    def _request(self, peer: str, what: str, *args):
        with self._lock:
            provider = self._providers.get(peer)
        if provider is None:
            raise ConnectionError(f"unknown peer {peer}")
        fn = provider.get(what)
        if fn is None:
            raise ConnectionError(f"peer {peer} does not serve {what}")
        return fn(*args)


class _HubTransport(Transport):
    def __init__(self, hub: InMemoryHub, peer_id: str) -> None:
        self.hub = hub
        self.peer_id = peer_id

    def publish(self, topic, payload):
        self.hub._publish(self.peer_id, topic, payload)

    def subscribe(self, topic, handler):
        self.hub._subscribe(self.peer_id, topic, handler)

    def peers(self):
        return self.hub._peers(self.peer_id)

    def request_blocks_by_range(self, peer, start_slot, count):
        return self.hub._request(peer, "blocks_by_range", start_slot, count)

    def request_blocks_by_root(self, peer, roots):
        return self.hub._request(peer, "blocks_by_root", roots)

    def request_blobs_by_range(self, peer, start_slot, count):
        return self.hub._request(peer, "blobs_by_range", start_slot, count)

    def request_blobs_by_root(self, peer, ids):
        return self.hub._request(peer, "blobs_by_root", ids)

    def request_status(self, peer):
        return self.hub._request(peer, "status")

    def register_provider(self, blocks_by_range, status, **extra):
        self.hub.register_provider(
            self.peer_id, blocks_by_range, status, **extra
        )


class Network:
    """The service loop glue (network.rs): gossip in → controller /
    attestation firehose; own objects → gossip out; serves BlocksByRange
    and Status to peers from the store + storage."""

    def __init__(
        self,
        transport: Transport,
        controller,
        cfg,
        attestation_verifier=None,
        storage=None,
        sync_pool=None,
        operation_pool=None,
        metrics=None,
        verify_scheduler=None,
        admission=None,
    ) -> None:
        self.transport = transport
        self.controller = controller
        self.cfg = cfg
        self.attestation_verifier = attestation_verifier
        self.storage = storage
        self.sync_pool = sync_pool
        self.operation_pool = operation_pool
        #: central verify scheduler (runtime/verify_scheduler.py): when
        #: wired, gossip handlers submit signature checks to its lanes
        #: and apply effects from the ticket callback; when None the
        #: handlers verify eagerly inline (the historical synchronous
        #: path — tests and minimal deployments)
        self.verify_scheduler = verify_scheduler
        #: per-origin fair-share admission control
        #: (runtime/isolation.AdmissionController): when wired, gossip
        #: verify submissions from an over-quota origin are shed at the
        #: door — a gossipsub "ignore", never a "reject" — before they
        #: can queue against honest traffic; None admits everything
        self.admission = admission
        #: shared Metrics struct (labeled per-topic gossip counters +
        #: per-protocol req/resp counters); defaults to the controller's
        self.metrics = (
            metrics if metrics is not None
            else getattr(controller, "metrics", None)
        )
        snap = controller.snapshot()
        self.digest = GossipTopics.fork_digest(cfg, snap.head_state)
        self.stats = defaultdict(int)
        #: None = all subnets (no SubnetService wired, the historical
        #: behavior); otherwise the active set maintained by SubnetService
        #: (attestation_subnets.rs) — gossip on other subnets is dropped
        self.active_attestation_subnets: "Optional[set[int]]" = None
        #: pubkey → committee positions for the CURRENT sync-committee
        #: period, built once per period instead of re-scanning the
        #: 512-entry committee per gossip message; invalidated on the
        #: period key AND the validator-set-change hook
        self._sync_positions: "Optional[tuple[int, dict]]" = None
        hooks = getattr(controller, "on_validator_set_change", None)
        if hooks is not None:
            hooks.append(lambda old, new: self._invalidate_sync_positions())

        transport.subscribe(
            GossipTopics.beacon_block(self.digest), self._on_gossip_block
        )
        # the GLOBAL aggregate topic is never subnet-gated — it is the
        # always-on fork-choice vote feed that makes per-subnet gating
        # safe (network.rs subscribes beacon_aggregate_and_proof
        # unconditionally)
        transport.subscribe(
            GossipTopics.aggregate_and_proof(self.digest),
            self._on_gossip_aggregate,
        )
        p = cfg.preset
        for subnet in range(min(cfg.attestation_subnet_count, 64)):
            transport.subscribe(
                GossipTopics.beacon_attestation(self.digest, subnet),
                self._on_gossip_attestation,
            )
        # deneb blob-sidecar subnets (p2p/src/network.rs:104,221-222)
        for subnet in range(cfg.blob_sidecar_subnet_count):
            transport.subscribe(
                GossipTopics.blob_sidecar(self.digest, subnet),
                self._on_gossip_blob_sidecar,
            )
        # sync-committee message/contribution + operation topics
        # (p2p/src/network.rs:42-50,233,273)
        for subnet in range(cfg.sync_committee_subnet_count):
            transport.subscribe(
                GossipTopics.sync_committee(self.digest, subnet),
                self._on_gossip_sync_committee_message,
            )
        transport.subscribe(
            GossipTopics.sync_committee_contribution(self.digest),
            self._on_gossip_sync_contribution,
        )
        transport.subscribe(
            GossipTopics.proposer_slashing(self.digest),
            self._on_gossip_proposer_slashing,
        )
        transport.subscribe(
            GossipTopics.attester_slashing(self.digest),
            self._on_gossip_attester_slashing,
        )
        transport.subscribe(
            GossipTopics.bls_to_execution_change(self.digest),
            self._on_gossip_bls_change,
        )
        transport.subscribe(
            GossipTopics.voluntary_exit(self.digest),
            self._on_gossip_voluntary_exit,
        )
        try:
            transport.register_provider(
                self._serve_blocks_by_range, self._serve_status,
                blocks_by_root=self._serve_blocks_by_root,
                blobs_by_range=self._serve_blobs_by_range,
                blobs_by_root=self._serve_blobs_by_root,
            )
        except NotImplementedError:
            pass

    # ------------------------------------------------------------ inbound

    @staticmethod
    def _topic_kind(topic: str) -> str:
        """`/eth2/<digest>/beacon_attestation_5/ssz_snappy` →
        `beacon_attestation` — the subnet number is stripped so label
        cardinality stays at the topic-kind count, not 64× it."""
        parts = topic.split("/")
        name = parts[3] if len(parts) > 3 else topic
        base, _, suffix = name.rpartition("_")
        return base if suffix.isdigit() and base else name

    def _count_gossip(self, topic: str, result: str) -> None:
        """Per-topic accept/ignore/reject accounting (the gossipsub
        MessageAcceptance triple): accept = handed to a service, ignore =
        dropped without prejudice (off-subnet / no service wired), reject
        = invalid (decode or validation failure)."""
        if self.metrics is not None:
            self.metrics.gossip_messages.labels(
                self._topic_kind(topic), result
            ).inc()

    def _count_rpc(self, protocol: str) -> None:
        if self.metrics is not None:
            self.metrics.rpc_requests.labels(protocol).inc()

    # --------------------------------------------- signature dispatching

    def _eager_verify_items(self, items) -> bool:
        """WHITELISTED eager fallback (the lint rule no-inline-gossip-verify
        audits that gossip handlers hold no other verification calls):
        SingleVerifier-equivalent per-item host checks, used when no
        verify scheduler is wired so handler semantics stay synchronous."""
        from grandine_tpu.runtime.verify_scheduler import host_check_item

        return all(host_check_item(it) for it in items)

    @staticmethod
    def _origin_of(sender: "Optional[str]") -> "Optional[str]":
        """Gossip sender → failure-attribution origin string. The
        `peer:` prefix namespaces the id so the flight recorder's top-K
        table can mix peer origins with future validator origins; the
        string NEVER becomes a Prometheus label (unbounded cardinality —
        tools/lint metrics_cardinality enforces this)."""
        return f"peer:{sender}" if sender else None

    def _dispatch_verify(
        self, lane: str, items, topic: str, reject_key: str, on_accept,
        origin: "Optional[str]" = None,
    ) -> None:
        """Route one handler's deferred signature checks: submit to the
        scheduler lane (effects run from the ticket callback) or fall
        back to the eager inline path. A job shed under overload counts
        as gossipsub "ignore" — dropped without prejudice — never as a
        validation reject."""

        def deliver(ok: bool, dropped: bool = False) -> None:
            if dropped:
                self.stats["verify_shed"] += 1
                sched = self.verify_scheduler
                if sched is not None and getattr(
                    sched, "device_degraded", lambda: False
                )():
                    # sheds while the device breaker is quarantining the
                    # backend: overload-under-degradation, not plain
                    # overload — the operator's cue that host-path
                    # throughput, not gossip volume, is the bottleneck
                    self.stats["verify_shed_degraded"] += 1
                self._count_gossip(topic, "ignore")
                return
            if not ok:
                self.stats[reject_key] += 1
                self._count_gossip(topic, "reject")
                return
            self._count_gossip(topic, "accept")
            on_accept()

        if (
            self.admission is not None
            and not self.admission.admit(origin, len(items), lane=lane)
        ):
            # over fair share: shed at the door, before the job can
            # queue against honest traffic (the controller counts
            # verify_admission_rejected_total by lane)
            self.stats["verify_admission_rejected"] += 1
            deliver(False, dropped=True)
            return
        sched = self.verify_scheduler
        if sched is not None:
            sched.submit(
                lane, items,
                callback=lambda t: deliver(t.ok, t.dropped),
                origin=origin,
            )
            return
        deliver(self._eager_verify_items(items))

    def _invalidate_sync_positions(self) -> None:
        self._sync_positions = None

    def _sync_committee_for_slot(self, state, slot: int):
        """The sync committee that signs at `slot`: the head state
        carries the CURRENT committee and (near a rotation boundary)
        the NEXT one — a message timestamped one period ahead of the
        state must resolve against next_sync_committee, not current.
        Returns (committee, period) — committee is None when the slot's
        period is outside the two the state knows."""
        from grandine_tpu.consensus import misc

        p = self.cfg.preset
        state_period = misc.sync_committee_period(int(state.slot), p)
        period = misc.sync_committee_period(int(slot), p)
        if period == state_period:
            return state.current_sync_committee, period
        if period == state_period + 1:
            return state.next_sync_committee, period
        return None, period

    def _sync_committee_positions(self, state, slot: int, pubkey: bytes):
        """Committee position(s) of `pubkey` in the sync committee of
        `slot`'s PERIOD (current vs next, resolved against the head
        state) — one table build per period (the period key catches
        rotation; the validator-set-change hook catches deposits/
        finalization) instead of an O(committee) scan per message."""
        committee, period = self._sync_committee_for_slot(state, slot)
        if committee is None:
            return ()
        cache = self._sync_positions
        if cache is None:
            cache = {}
            self._sync_positions = cache
        table = cache.get(period)
        if table is None:
            table = {}
            for pos, pk_bytes in enumerate(committee.pubkeys):
                key = bytes(pk_bytes)
                table[key] = table.get(key, ()) + (pos,)
            cache[period] = table
            # only the state's own and the next period are resolvable —
            # drop rotated-out tables instead of accreting one per period
            for stale in [k for k in cache if k not in (period, period + 1,
                                                        period - 1)]:
                del cache[stale]
        return table.get(bytes(pubkey), ())

    def _on_gossip_block(self, topic: str, payload: bytes) -> None:
        from grandine_tpu.types.combined import decode_signed_block

        self.stats["blocks_in"] += 1
        try:
            block = decode_signed_block(frame_decompress(payload), self.cfg)
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        self._count_gossip(topic, "accept")
        self.controller.on_gossip_block(block)

    def set_attestation_subnets(self, subnets: "set[int]") -> None:
        """SubnetService push: which beacon_attestation_{n} topics this
        node is currently joined to (transports without unsubscribe keep
        the topic; the gate below drops off-subnet traffic)."""
        self.active_attestation_subnets = set(subnets)

    @staticmethod
    def _subnet_of_topic(topic: str) -> "Optional[int]":
        marker = "/beacon_attestation_"
        if marker not in topic:
            return None
        try:
            return int(topic.split(marker, 1)[1].split("/", 1)[0])
        except ValueError:
            return None

    def _on_gossip_attestation(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        from grandine_tpu.types.combined import decode_attestation

        subnet = self._subnet_of_topic(topic)
        if (
            self.active_attestation_subnets is not None
            and subnet is not None
            and subnet not in self.active_attestation_subnets
        ):
            self.stats["attestations_off_subnet"] += 1
            self._count_gossip(topic, "ignore")
            return
        self.stats["attestations_in"] += 1
        if self.attestation_verifier is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            slot = self.controller.snapshot().slot
            att = decode_attestation(frame_decompress(payload), self.cfg, slot)
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        self._count_gossip(topic, "accept")
        self.attestation_verifier.submit(att, origin=self._origin_of(sender))

    def _on_gossip_aggregate(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        from grandine_tpu.types.combined import decode_signed_aggregate

        self.stats["aggregates_in"] += 1
        if self.attestation_verifier is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            slot = self.controller.snapshot().slot
            signed = decode_signed_aggregate(
                frame_decompress(payload), self.cfg, slot
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        self._count_gossip(topic, "accept")
        self.attestation_verifier.submit(
            signed.message.aggregate, origin=self._origin_of(sender)
        )

    def _deneb_ns(self):
        from grandine_tpu.types.containers import spec_types

        return spec_types(self.cfg.preset).deneb

    def _on_gossip_blob_sidecar(self, topic: str, payload: bytes) -> None:
        self.stats["blob_sidecars_in"] += 1
        try:
            sidecar = self._deneb_ns().BlobSidecar.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        self._count_gossip(topic, "accept")
        self.controller.on_gossip_blob_sidecar(sidecar)

    def _on_gossip_sync_committee_message(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        self.stats["sync_messages_in"] += 1
        if self.sync_pool is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            msg = self._deneb_ns().SyncCommitteeMessage.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        # validator_index → committee position(s) via the head state's
        # current sync committee (a validator can hold several positions)
        state = self.controller.snapshot().head_state
        vidx = int(msg.validator_index)
        if vidx >= len(state.validators):
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        pubkey = bytes(state.validators[vidx].pubkey)
        # gossip validation: the signature must verify against the
        # claimed validator's key for the message's slot/root — a forged
        # signature inserted into the pool would poison the produced
        # sync aggregate and invalidate this node's own proposals
        # (p2p gossip rules; sync_committee_agg_pool tasks.rs)
        from grandine_tpu.consensus import accessors, misc, signing
        from grandine_tpu.runtime.verify_scheduler import VerifyItem

        try:
            root = signing.sync_committee_message_signing_root(
                state, bytes(msg.beacon_block_root),
                misc.compute_epoch_at_slot(int(msg.slot), self.cfg.preset),
                self.cfg,
            )
            cols = accessors.registry_columns(state)
        except Exception:
            self.stats["sync_messages_rejected"] += 1
            self._count_gossip(topic, "reject")
            return
        slot = int(msg.slot)
        positions = self._sync_committee_positions(state, slot, pubkey)
        block_root = bytes(msg.beacon_block_root)
        signature = bytes(msg.signature)

        def insert() -> None:
            self.sync_pool.insert_message_at_positions(
                slot, block_root, positions, signature
            )

        # the index+columns form lets the scheduler's device path gather
        # the pubkey from the registry instead of uploading it
        self._dispatch_verify(
            "sync_message",
            [VerifyItem(root, signature, member_indices=(vidx,),
                        pubkey_columns=cols.pubkeys)],
            topic, "sync_messages_rejected", insert,
            origin=self._origin_of(sender),
        )

    def _on_gossip_sync_contribution(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        self.stats["sync_contributions_in"] += 1
        if self.sync_pool is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            signed = self._deneb_ns().SignedContributionAndProof.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        contribution = signed.message.contribution
        # full gossip validation before the pool: the aggregator's
        # selection proof (proves the right to aggregate this slot/
        # subcommittee), the outer SignedContributionAndProof signature,
        # and the contribution's aggregate signature against the set
        # subcommittee members — any one forged could poison the pool's
        # aggregates or let a non-aggregator flood the topic
        from grandine_tpu.consensus import accessors, misc, signing
        from grandine_tpu.crypto import bls as A
        from grandine_tpu.runtime.verify_scheduler import VerifyItem

        state = self.controller.snapshot().head_state
        p = self.cfg.preset
        try:
            sub = int(contribution.subcommittee_index)
            sub_size = p.SYNC_COMMITTEE_SIZE // self.cfg.sync_committee_subnet_count
            committee, _period = self._sync_committee_for_slot(
                state, int(contribution.slot)
            )
            if committee is None:
                raise ValueError("slot outside known sync periods")
            members = committee.pubkeys[
                sub * sub_size : (sub + 1) * sub_size
            ]
            bits = list(contribution.aggregation_bits)
            pks = [
                A.PublicKey.from_bytes(bytes(pk))
                for bit, pk in zip(bits, members)
                if bit
            ]
            if not pks:
                raise ValueError("empty contribution")
            agg_idx = int(signed.message.aggregator_index)
            if agg_idx >= len(state.validators):
                raise ValueError("aggregator index out of range")
            agg_pubkey = bytes(state.validators[agg_idx].pubkey)
            if not any(bytes(pk) == agg_pubkey for pk in members):
                raise ValueError("aggregator not in declared subcommittee")
            selection_proof = bytes(signed.message.selection_proof)
            if not misc.is_sync_committee_aggregator(
                selection_proof, p, self.cfg.sync_committee_subnet_count
            ):
                raise ValueError("selection proof does not elect aggregator")
            ns = self._deneb_ns()
            selection_root = signing.sync_selection_proof_signing_root(
                state,
                ns.SyncAggregatorSelectionData(
                    slot=contribution.slot, subcommittee_index=sub
                ),
                self.cfg,
            )
            outer_root = signing.contribution_and_proof_signing_root(
                state, signed.message, self.cfg
            )
            root = signing.sync_committee_message_signing_root(
                state, bytes(contribution.beacon_block_root),
                misc.compute_epoch_at_slot(int(contribution.slot), p),
                self.cfg,
            )
            cols = accessors.registry_columns(state)
        except Exception:
            self.stats["sync_contributions_rejected"] += 1
            self._count_gossip(topic, "reject")
            return
        # one ticket, three signatures: selection proof + outer proof
        # ride the registry's indexed path (aggregator index known);
        # the contribution aggregate carries its member keys
        self._dispatch_verify(
            "sync_contribution",
            [
                VerifyItem(selection_root, selection_proof,
                           member_indices=(agg_idx,),
                           pubkey_columns=cols.pubkeys),
                VerifyItem(outer_root, bytes(signed.signature),
                           member_indices=(agg_idx,),
                           pubkey_columns=cols.pubkeys),
                VerifyItem(root, bytes(contribution.signature),
                           public_keys=pks),
            ],
            topic, "sync_contributions_rejected",
            lambda: self.sync_pool.insert_contribution(contribution),
            origin=self._origin_of(sender),
        )

    def _on_gossip_proposer_slashing(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        self.stats["proposer_slashings_in"] += 1
        if self.operation_pool is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            slashing = self._deneb_ns().ProposerSlashing.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        # full validation BEFORE insert, mirroring the attester-slashing
        # handler: process_proposer_slashing preconditions + BOTH header
        # signatures. Without this any peer could stuff the pool with
        # junk that invalidates our own block proposals at pack time.
        from grandine_tpu.consensus import (
            accessors, misc, predicates, signing,
        )
        from grandine_tpu.runtime.verify_scheduler import VerifyItem

        h1 = slashing.signed_header_1.message
        h2 = slashing.signed_header_2.message
        state = self.controller.snapshot().head_state
        try:
            if int(h1.slot) != int(h2.slot):
                raise ValueError("headers are for different slots")
            if int(h1.proposer_index) != int(h2.proposer_index):
                raise ValueError("headers are for different proposers")
            if h1.hash_tree_root() == h2.hash_tree_root():
                raise ValueError("headers are identical")
            idx = int(h1.proposer_index)
            if idx >= len(state.validators):
                raise ValueError("proposer index out of range")
            epoch = misc.compute_epoch_at_slot(
                int(state.slot), self.cfg.preset
            )
            if not predicates.is_slashable_validator(
                state.validators[idx], epoch
            ):
                raise ValueError("proposer is not slashable")
            cols = accessors.registry_columns(state)
            items = [
                VerifyItem(
                    signing.header_signing_root(
                        state, signed.message, self.cfg
                    ),
                    bytes(signed.signature),
                    member_indices=(idx,),
                    pubkey_columns=cols.pubkeys,
                )
                for signed in (slashing.signed_header_1,
                               slashing.signed_header_2)
            ]
        except Exception:
            self.stats["proposer_slashings_rejected"] += 1
            self._count_gossip(topic, "reject")
            return
        self._dispatch_verify(
            "slashing", items, topic, "proposer_slashings_rejected",
            lambda: self.operation_pool.insert_proposer_slashing(slashing),
            origin=self._origin_of(sender),
        )

    def _on_gossip_attester_slashing(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        self.stats["attester_slashings_in"] += 1
        try:
            slashing = self._deneb_ns().AttesterSlashing.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        # full validation BEFORE any effect: slashable data + BOTH indexed
        # attestation signatures. An unvalidated slashing would let any
        # peer zero arbitrary validators' fork-choice weight and poison
        # this node's own block proposals (spec p2p gossip validation;
        # process_attester_slashing preconditions). The structural checks
        # stay inline; the signatures are COLLECTED (MultiVerifier defers
        # them as triples) and routed through the slashing lane.
        from grandine_tpu.consensus import predicates
        from grandine_tpu.consensus.verifier import MultiVerifier
        from grandine_tpu.runtime.verify_scheduler import VerifyItem

        att1, att2 = slashing.attestation_1, slashing.attestation_2
        state = self.controller.snapshot().head_state
        try:
            if not predicates.is_slashable_attestation_data(
                att1.data, att2.data
            ):
                raise ValueError("attestations are not slashable")
            collector = MultiVerifier()
            for indexed in (att1, att2):
                predicates.validate_indexed_attestation(
                    indexed, state, collector, self.cfg
                )
            items = [
                VerifyItem(t.message, t.signature,
                           public_keys=(t.public_key,))
                for t in collector.triples
            ]
        except Exception:
            self.stats["attester_slashings_rejected"] += 1
            self._count_gossip(topic, "reject")
            return

        def apply() -> None:
            if self.operation_pool is not None:
                self.operation_pool.insert_attester_slashing(slashing)
            # fork choice marks the intersection equivocating
            a = set(int(i) for i in att1.attesting_indices)
            b = set(int(i) for i in att2.attesting_indices)
            both = sorted(a & b)
            if both:
                self.controller.on_attester_slashing(both)

        self._dispatch_verify(
            "slashing", items, topic, "attester_slashings_rejected", apply,
            origin=self._origin_of(sender),
        )

    def _on_gossip_bls_change(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        self.stats["bls_changes_in"] += 1
        if self.operation_pool is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            signed = self._deneb_ns().SignedBLSToExecutionChange.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        # verify the change signature (under the genesis-fork-version
        # domain, against the claimed from_bls_pubkey) before it can
        # reach the pool. The withdrawal-credential hash binding stays in
        # OperationPool.pack, where the packing state is authoritative.
        from grandine_tpu.consensus import signing
        from grandine_tpu.consensus.verifier import MultiVerifier
        from grandine_tpu.runtime.verify_scheduler import VerifyItem

        state = self.controller.snapshot().head_state
        try:
            if int(signed.message.validator_index) >= len(state.validators):
                raise ValueError("validator index out of range")
            collector = MultiVerifier()
            signing.extend_with_bls_to_execution_change(
                collector, state, signed, self.cfg
            )
            items = [
                VerifyItem(t.message, t.signature,
                           public_keys=(t.public_key,))
                for t in collector.triples
            ]
        except Exception:
            self.stats["bls_changes_rejected"] += 1
            self._count_gossip(topic, "reject")
            return
        self._dispatch_verify(
            "bls_change", items, topic, "bls_changes_rejected",
            lambda: self.operation_pool.insert_bls_to_execution_change(
                signed
            ),
            origin=self._origin_of(sender),
        )

    def _on_gossip_voluntary_exit(
        self, topic: str, payload: bytes, sender: "Optional[str]" = None
    ) -> None:
        self.stats["voluntary_exits_in"] += 1
        if self.operation_pool is None:
            self._count_gossip(topic, "ignore")
            return
        try:
            signed = self._deneb_ns().SignedVoluntaryExit.deserialize(
                frame_decompress(payload)
            )
        except Exception:
            self.stats["decode_failures"] += 1
            self._count_gossip(topic, "reject")
            return
        # verify the exit signature (EIP-7044-aware domain) against the
        # exiting validator's key before the pool can pack it
        from grandine_tpu.consensus import signing
        from grandine_tpu.consensus.verifier import MultiVerifier
        from grandine_tpu.runtime.verify_scheduler import VerifyItem
        from grandine_tpu.types.combined import state_phase_of

        state = self.controller.snapshot().head_state
        try:
            if int(signed.message.validator_index) >= len(state.validators):
                raise ValueError("validator index out of range")
            collector = MultiVerifier()
            signing.extend_with_voluntary_exit(
                collector, state, signed, self.cfg,
                state_phase_of(state, self.cfg),
            )
            items = [
                VerifyItem(t.message, t.signature,
                           public_keys=(t.public_key,))
                for t in collector.triples
            ]
        except Exception:
            self.stats["voluntary_exits_rejected"] += 1
            self._count_gossip(topic, "reject")
            return
        self._dispatch_verify(
            "exit", items, topic, "voluntary_exits_rejected",
            lambda: self.operation_pool.insert_voluntary_exit(signed),
            origin=self._origin_of(sender),
        )

    # ----------------------------------------------------------- outbound

    def publish_aggregate(self, signed_aggregate_and_proof) -> None:
        self.stats["aggregates_out"] += 1
        self.transport.publish(
            GossipTopics.aggregate_and_proof(self.digest),
            frame_compress(signed_aggregate_and_proof.serialize()),
        )

    def publish_block(self, signed_block) -> None:
        self.stats["blocks_out"] += 1
        self.transport.publish(
            GossipTopics.beacon_block(self.digest),
            frame_compress(signed_block.serialize()),
        )

    def publish_attestation(self, attestation, subnet: int = 0) -> None:
        self.stats["attestations_out"] += 1
        self.transport.publish(
            GossipTopics.beacon_attestation(self.digest, subnet),
            frame_compress(attestation.serialize()),
        )

    def publish_blob_sidecar(self, sidecar) -> None:
        """Subnet = index % BLOB_SIDECAR_SUBNET_COUNT (spec
        compute_subnet_for_blob_sidecar)."""
        self.stats["blob_sidecars_out"] += 1
        subnet = int(sidecar.index) % self.cfg.blob_sidecar_subnet_count
        self.transport.publish(
            GossipTopics.blob_sidecar(self.digest, subnet),
            frame_compress(sidecar.serialize()),
        )

    def publish_sync_committee_message(self, msg, subnet: int = 0) -> None:
        self.stats["sync_messages_out"] += 1
        self.transport.publish(
            GossipTopics.sync_committee(self.digest, subnet),
            frame_compress(msg.serialize()),
        )

    def publish_sync_contribution(self, signed_contribution) -> None:
        self.stats["sync_contributions_out"] += 1
        self.transport.publish(
            GossipTopics.sync_committee_contribution(self.digest),
            frame_compress(signed_contribution.serialize()),
        )

    def publish_proposer_slashing(self, slashing) -> None:
        self.stats["proposer_slashings_out"] += 1
        self.transport.publish(
            GossipTopics.proposer_slashing(self.digest),
            frame_compress(slashing.serialize()),
        )

    def publish_attester_slashing(self, slashing) -> None:
        self.stats["attester_slashings_out"] += 1
        self.transport.publish(
            GossipTopics.attester_slashing(self.digest),
            frame_compress(slashing.serialize()),
        )

    def publish_bls_change(self, signed_change) -> None:
        self.stats["bls_changes_out"] += 1
        self.transport.publish(
            GossipTopics.bls_to_execution_change(self.digest),
            frame_compress(signed_change.serialize()),
        )

    def publish_voluntary_exit(self, signed_exit) -> None:
        self.stats["voluntary_exits_out"] += 1
        self.transport.publish(
            GossipTopics.voluntary_exit(self.digest),
            frame_compress(signed_exit.serialize()),
        )

    # ------------------------------------------------------------ serving

    def _serve_blocks_by_range(self, start_slot: int, count: int) -> "list[bytes]":
        self._count_rpc("beacon_blocks_by_range")
        out = []
        store = self.controller.store
        by_slot = {}
        for node in store.blocks.values():
            if hasattr(node.signed_block, "serialize"):
                by_slot[node.slot] = node.signed_block
        for slot in range(start_slot, start_slot + count):
            block = by_slot.get(slot)
            if block is None and self.storage is not None:
                root = self.storage.finalized_root_by_slot(slot)
                if root is not None:
                    block = self.storage.finalized_block_by_root(root)
            if block is not None:
                out.append(block.serialize())
        return out

    def _serve_blocks_by_root(self, roots: "list[bytes]") -> "list[bytes]":
        """BeaconBlocksByRoot (p2p/src/network.rs:911-912): resolve a
        delayed block's unknown parent without waiting for range sync."""
        self._count_rpc("beacon_blocks_by_root")
        out = []
        store = self.controller.store
        for root in roots:
            root = bytes(root)
            node = store.blocks.get(root)
            block = node.signed_block if node is not None else None
            if (
                block is None or not hasattr(block, "serialize")
            ) and self.storage is not None:
                block = self.storage.finalized_block_by_root(root)
            if block is not None and hasattr(block, "serialize"):
                out.append(block.serialize())
        return out

    def _serve_blobs_by_range(self, start_slot: int, count: int) -> "list[bytes]":
        self._count_rpc("blob_sidecars_by_range")
        out = []
        store = self.controller.store
        for node in sorted(store.blocks.values(), key=lambda n: n.slot):
            if start_slot <= node.slot < start_slot + count:
                for sc in self.controller.blob_sidecars_for(node.root):
                    out.append(sc.serialize())
        return out

    def _serve_blobs_by_root(self, ids: "list") -> "list[bytes]":
        """ids: [(block_root, index), ...] (spec BlobIdentifier)."""
        self._count_rpc("blob_sidecars_by_root")
        out = []
        for root, index in ids:
            for sc in self.controller.blob_sidecars_for(bytes(root)):
                if int(sc.index) == int(index):
                    out.append(sc.serialize())
        return out

    def _serve_status(self) -> dict:
        self._count_rpc("status")
        snap = self.controller.snapshot()
        return {
            "head_slot": int(snap.head_state.slot),
            "head_root": snap.head_root.hex(),
            "finalized_epoch": int(snap.finalized_checkpoint.epoch),
            "fork_digest": self.digest.hex(),
        }


__all__ = ["GossipTopics", "Transport", "InMemoryHub", "Network"]
