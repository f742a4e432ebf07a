"""In-process node: clock + controller + duty engine ticking through slots
on synthetic data — the round-9 "minimal runtime skeleton" everything else
plugs into (reference runtime/src/runtime.rs:49-110 wiring, minus
networking/eth1 which enter through the same seams later).

`InProcessNode.run_slot` drives one slot's three ticks:
  PROPOSE   — produce a block on the current head (validator.rs:733,1292)
              and feed it back through the controller (own-block path)
  ATTEST    — produce one aggregate attestation per committee and submit
              them to the AttestationVerifier firehose
  AGGREGATE — flush the verifier (stand-in for aggregate publication)
"""

from __future__ import annotations

from typing import Optional

from grandine_tpu.fork_choice.store import Tick, TickKind
from grandine_tpu.runtime.attestation_verifier import AttestationVerifier
from grandine_tpu.runtime.clock import SlotClock, ticks_for_slot
from grandine_tpu.runtime.controller import Controller
from grandine_tpu.validator.duties import produce_attestations, produce_block


class InProcessNode:
    def __init__(
        self,
        genesis_state,
        cfg,
        execution_engine=None,
        verifier_factory=None,
        use_device_firehose: bool = False,
        use_verify_scheduler: bool = False,
        full_sync_participation: bool = False,
        slasher=None,
        operation_pool=None,
        metrics=None,
        tracer=None,
        mesh=None,
        use_isolation: bool = True,
        use_brownout: bool = True,
        database=None,
    ) -> None:
        from grandine_tpu.consensus.verifier import MultiVerifier

        from grandine_tpu.runtime.flight import FlightRecorder
        from grandine_tpu.runtime.health import BackendHealthSupervisor
        from grandine_tpu.runtime.isolation import (
            AdmissionController,
            ReputationTable,
        )
        from grandine_tpu.tpu.mesh import mesh_or_none

        self.cfg = cfg
        self.metrics = metrics
        self.tracer = tracer
        #: injected VerifyMesh (cli --devices → VerifyMesh.build): threaded
        #: into the scheduler and the attestation firehose, which shard
        #: the registry + kernels over it; None / 1-device is single-chip
        self.mesh = mesh_or_none(mesh)
        #: ONE flight recorder for the whole verify plane: scheduler
        #: batches, firehose batches, canary probes, and breaker
        #: transitions share a single ordered timeline (the debug
        #: endpoint GET /eth/v1/debug/grandine/flight serves it)
        self.flight = FlightRecorder(metrics=metrics)
        #: ONE kernel profiler for the whole verify plane: the dispatch
        #: seams reach it via the module default, so its device timeline
        #: stamps every kernel call and capture sessions annotate every
        #: kernel (GET /eth/v1/debug/grandine/profile serves/controls it);
        #: the interpreter's collections are watched from here on, into
        #: the node's metrics and spans
        from grandine_tpu.runtime.profiler import (
            KernelProfiler,
            set_profiler,
            watch_collections,
        )

        self.profiler = set_profiler(KernelProfiler(metrics=metrics))
        watch_collections(metrics, tracer)
        #: ONE health supervisor for the whole device verify plane: a
        #: breaker fault observed by either the scheduler or the
        #: attestation firehose quarantines the device for both
        self.health = BackendHealthSupervisor(
            metrics=metrics, flight=self.flight
        )
        #: ONE reputation table + admission controller for the whole
        #: node (runtime/isolation.py): the scheduler quarantines by it,
        #: the gossip plane (p2p/network.py `admission=`) sheds by it.
        #: Persisted through the node's K-V store (when one is given) so
        #: an attacker cannot reset quarantine by waiting out a reboot.
        self.database = database
        self.reputation = ReputationTable()
        if database is not None:
            try:
                self.reputation.load(database)
            except Exception:
                pass  # a corrupt reputation row must never stop the node
        # admission keys quotas off per-origin FAILURE RATES from the
        # shared reputation table (not raw submission share): a busy
        # honest aggregator is never clamped, a high-failure origin is
        self.admission = AdmissionController(
            metrics=metrics, reputation=self.reputation
        )
        self.verify_scheduler = None
        if use_verify_scheduler:
            from grandine_tpu.runtime.verify_scheduler import VerifyScheduler

            self.verify_scheduler = VerifyScheduler(
                use_device=use_device_firehose,
                metrics=metrics,
                tracer=tracer,
                health=self.health,
                flight=self.flight,
                mesh=self.mesh,
                reputation=self.reputation,
                use_isolation=use_isolation,
            )
            if verifier_factory is None:
                # block proposer-signature batches ride the HIGH lane
                verifier_factory = self.verify_scheduler.verifier_factory(
                    "block"
                )
        self.controller = Controller(
            genesis_state,
            cfg,
            execution_engine=execution_engine,
            verifier_factory=verifier_factory or MultiVerifier,
            metrics=metrics,
            tracer=tracer,
        )
        self.controller.verify_scheduler = self.verify_scheduler
        self.attestation_verifier = AttestationVerifier(
            self.controller,
            use_device=use_device_firehose,
            slasher=slasher,
            operation_pool=operation_pool,
            metrics=metrics,
            tracer=tracer,
            health=self.health,
            flight=self.flight,
            mesh=self.mesh,
        )
        if (
            self.verify_scheduler is not None
            and self.attestation_verifier.registry is not None
        ):
            # share the device-resident pubkey registry (one device
            # mirror; the firehose already hooked its staleness to
            # on_validator_set_change)
            self.verify_scheduler.registry = (
                self.attestation_verifier.registry
            )
        #: ONE brownout controller for the whole node: watches the
        #: shared flight recorder's SLO-miss stream and the scheduler's
        #: lane depths, and walks the NORMAL→…→CRITICAL ladder across
        #: the verify plane + admission quotas (runtime/brownout.py).
        #: Only meaningful when a scheduler exists to actuate on.
        self.brownout = None
        if use_brownout and self.verify_scheduler is not None:
            from grandine_tpu.runtime.brownout import BrownoutController

            self.brownout = BrownoutController(
                self.verify_scheduler,
                flight=self.flight,
                admission=self.admission,
                metrics=metrics,
            )
            self.brownout.start()
        self.clock = SlotClock(
            int(genesis_state.genesis_time), cfg.seconds_per_slot
        )
        self.full_sync_participation = full_sync_participation
        self.produced_blocks: list = []
        #: optional BuilderApi (cli --builder-url): when set, _propose
        #: tries the blinded/builder flow before local building
        self.builder_api = None
        self.builder_stats = {"blocks": 0, "fallbacks": 0, "aborts": 0}

    # ------------------------------------------------------------- driving

    def run_slot(self, slot: int, attest: bool = True) -> None:
        for tick in ticks_for_slot(slot):
            self.controller.on_tick(tick)
            if tick.kind == TickKind.PROPOSE:
                self._propose(slot)
            elif tick.kind == TickKind.ATTEST and attest:
                self._attest(slot)
            elif tick.kind == TickKind.AGGREGATE:
                self.attestation_verifier.flush()
        self.controller.wait()

    def run_until(self, slot: int, attest: bool = True) -> None:
        start = self.controller.snapshot().slot + 1
        for s in range(start, slot + 1):
            self.run_slot(s, attest=attest)

    # -------------------------------------------------------------- duties

    def _propose(self, slot: int) -> None:
        self.controller.wait()  # head must reflect everything applied
        snapshot = self.controller.snapshot()
        signed_block = None
        if self.builder_api is not None and self.builder_api.can_use_builder(
            self.controller, slot, self.cfg.preset.SLOTS_PER_EPOCH
        ):
            aborted, signed_block = self._propose_via_builder(snapshot, slot)
            if aborted:
                self.builder_stats["aborts"] += 1
                return  # post-sign failure: never sign a second block
            if signed_block is not None:
                self.builder_stats["blocks"] += 1
            else:
                self.builder_stats["fallbacks"] += 1
        if signed_block is None:
            signed_block, _post = produce_block(
                snapshot.head_state,
                slot,
                self.cfg,
                full_sync_participation=self.full_sync_participation,
                attestations=self._pool_attestations(snapshot, slot),
            )
        self.produced_blocks.append(signed_block)
        self.controller.on_own_block(signed_block)
        self.controller.wait()

    def _propose_via_builder(self, snapshot, slot: int):
        """Builder flow with the devnet's interop proposer key; returns
        (aborted, signed_block_or_None). Pre-sign failures fall back to
        local building; post-sign failures abort the slot (the relay may
        hold the signature — equivocation risk)."""
        from grandine_tpu.consensus import accessors, signing
        from grandine_tpu.transition.slots import process_slots
        from grandine_tpu.types.combined import fork_namespace, state_phase_of
        from grandine_tpu.validator import blinded as blinded_mod
        from grandine_tpu.validator.duties import _interop_keys

        p = self.cfg.preset
        state = snapshot.head_state
        try:
            if int(state.slot) < slot:
                state = process_slots(state, slot, self.cfg)
            ns = fork_namespace(self.cfg, state_phase_of(state, self.cfg))
            proposer = accessors.get_beacon_proposer_index(state, p)
            key = _interop_keys(proposer)
            pubkey = key.public_key().to_bytes()
            bid = self.builder_api.get_execution_payload_header(
                slot,
                bytes(state.latest_execution_payload_header.block_hash),
                pubkey,
                ns=ns,
            )
            header = blinded_mod.header_from_bid(ns, bid["header"])
            reveal = key.sign(
                signing.randao_signing_root(
                    state, accessors.get_current_epoch(state, p), self.cfg
                )
            ).to_bytes()
            block, pre, _post = blinded_mod.produce_blinded_block(
                state, slot, self.cfg, header, reveal,
                attestations=self._pool_attestations(snapshot, slot),
            )
        except Exception as e:
            self.builder_stats["last_error"] = repr(e)
            return False, None  # pre-sign: local fallback is safe
        try:
            sig = key.sign(
                signing.block_signing_root(pre, block, self.cfg)
            ).to_bytes()
            signed_blinded = ns.SignedBlindedBeaconBlock(
                message=block, signature=sig
            )
            response = self.builder_api.submit_blinded_block(signed_blinded)
            raw = response["execution_payload"]
            payload = ns.ExecutionPayload.deserialize(
                bytes.fromhex(raw.removeprefix("0x"))
                if isinstance(raw, str)
                else bytes(raw)
            )
            return False, blinded_mod.unblind_signed_block(
                signed_blinded, payload, self.cfg
            )
        except Exception as e:
            self.builder_stats["last_error"] = repr(e)
            return True, None  # post-sign: abort the slot

    def _pool_attestations(self, snapshot, slot: int):
        """Previous-slot attestations for inclusion (a stand-in for the
        operation pool, built against the head state)."""
        if slot <= 1 or int(snapshot.head_state.slot) < slot - 1:
            return []
        try:
            return produce_attestations(
                snapshot.head_state, self.cfg, slot=slot - 1
            )
        except ValueError:
            return []

    def _attest(self, slot: int) -> None:
        self.controller.wait()
        snapshot = self.controller.snapshot()
        if int(snapshot.head_state.slot) < slot:
            return
        atts = produce_attestations(snapshot.head_state, self.cfg, slot=slot)
        # firehose path exercises batch verification + fallback; the
        # produced attestations also flow into the proposer's next block
        # via _pool_attestations
        self.attestation_verifier.submit_many(atts)

    # ------------------------------------------------------------- control

    def head(self):
        return self.controller.snapshot()

    def stop(self) -> None:
        if self.database is not None:
            try:
                self.reputation.save(self.database)
            except Exception:
                pass  # shutdown persistence is best-effort
        # the controller stops FIRST so it reverts every brownout
        # actuation (lane configs, admission pressure) before the
        # scheduler drains
        if self.brownout is not None:
            self.brownout.stop()
        self.attestation_verifier.stop()
        if self.verify_scheduler is not None:
            self.verify_scheduler.stop()
        self.controller.stop()

    def __enter__(self) -> "InProcessNode":
        return self

    def __exit__(self, *_) -> None:
        self.stop()


__all__ = ["InProcessNode"]
