"""The gossip-attestation firehose service — reference:
p2p/src/attestation_verifier.rs (`AttestationVerifier` :39: accumulate up
to 64 per batch :37, bounded concurrent batch tasks :44-45,68, spawn on the
low-priority executor :142-163, prevalidate + build triples :352-457, ONE
batch verification :396-417, and on batch failure fall back to per-item
verification so a single bad signature can't stall the stream :231-239,
:377-386).

TPU shape: each batch becomes ONE `fast_aggregate_verify_batch` launch
(M aggregates × K committee members — the firehose kernels' native
geometry, tpu/bls.py aggregate_fast_verify_msm_idx_kernel). The deadline keeps latency bounded when gossip is slow;
the batch bound keeps device launches dense when it's fast. A batch of any
size 1..max_batch is dispatched padded into ONE batch bucket
(`AttestationVerifier.batch_bucket`): the kernel's time is flat in its
batch axis, so a smaller native bucket buys nothing and costs an
executable (minutes to compile, ~80 s to load). The member axis has ONE
bucket a node too, chosen by what the verifier has seen: the widest
committee's, from the first call that held one on (`width_floor`), so a
queue of single votes AND aggregates runs one resident executable.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Optional, Sequence

from grandine_tpu.consensus import accessors, keys, signing
from grandine_tpu.consensus.verifier import SignatureInvalid
from grandine_tpu.crypto import bls as A
from grandine_tpu.fork_choice.store import ForkChoiceError, ValidAttestation
from grandine_tpu.runtime import flight as _flight
from grandine_tpu.runtime import health as _health
from grandine_tpu.runtime import profiler as _profiler
from grandine_tpu.runtime.thread_pool import Priority
from grandine_tpu.tracing import NULL_TRACER, stage as _stage

MAX_BATCH = 64  # attestation_verifier.rs:37


def _width_bucket(width: int) -> int:
    """The member-axis bucket a widest committee of `width` pads into
    (tpu/bls.py `_bucket(width, lo=4)`, without importing JAX)."""
    return max(4, _flight.bucket_of(width))


class GossipAttestation:
    """One attestation off the wire, pre-verification. `origin` is the
    gossip peer attribution ("peer:<id>") for the flight recorder's
    failing-origin table — never a metrics label."""

    __slots__ = ("attestation", "received_at", "origin", "arrived")

    def __init__(self, attestation, received_at: "Optional[float]" = None,
                 origin: "Optional[str]" = None,
                 arrived: "Optional[float]" = None) -> None:
        self.attestation = attestation
        self.received_at = received_at if received_at is not None else time.time()
        self.origin = origin
        #: monotonic arrival stamp (`perf_counter`, the spans' clock):
        #: every wait of the item's batch is measured from it
        self.arrived = arrived if arrived is not None else time.perf_counter()


class _BatchLife:
    """What travels with one batch from the collector to its verdict: the
    root span of its chain and the stamps its waits are measured from."""

    __slots__ = ("root", "arrived", "popped", "pool_span", "closed_by",
                 "held_s", "slot_wait_s")

    def __init__(self, tracer, batch, popped: float, closed_by: str,
                 held_s: float, slot_wait_s: float) -> None:
        #: arrival of the batch's oldest item: where its life begins
        self.arrived = min(it.arrived for it in batch)
        self.popped = popped
        #: what closed the batch: "full", "deadline" or "stop"
        self.closed_by = closed_by
        #: how long the batch stood past its deadline for a pipeline slot
        #: (inside collect_wait; 0.0: it was not held)
        self.held_s = held_s
        #: how long the formed batch waited at the collector for a slot of
        #: either bound: the hold, or `max_active` batches in flight (pool
        #: threads still waiting for the pipeline's settles); the device's
        #: idle time over it is back-pressure (runtime/profiler.py `hold`)
        self.slot_wait_s = slot_wait_s
        self.root = tracer.span(
            "verify_batch", {"batch": len(batch)}, start=self.arrived
        )
        tracer.span(
            "collect_wait",
            {"closed_by": closed_by, "items": len(batch), "held_s": held_s},
            parent=self.root, start=self.arrived,
        ).finish()
        self.pool_span = tracer.span("pool_wait", parent=self.root)


class _MemberKeys:
    """An item's committee members' public keys, decompressed on the host
    the first time a check off the resident registry's path reads them
    (`AttestationVerifier._member_keys`); the registry's path reads the
    indices alone. `refused`: one of them does not decompress."""

    __slots__ = ("points", "refused")

    def __init__(self) -> None:
        self.points: "Optional[list]" = None
        self.refused = False


class _Descent:
    """What one descent over a failed batch (`_isolate`) has found so far:
    the batch, the positions of the items named bad, the probes made."""

    __slots__ = ("parent", "bad", "probes")

    def __init__(self, parent) -> None:
        self.parent = parent
        self.bad: "set[int]" = set()
        self.probes = 0


class AttestationVerifier:
    """Accumulate → deadline/size-bound batch → device verify → feedback.

    `submit` is called from gossip (any thread); a collector thread forms
    batches; verification tasks run on the controller's LOW-priority pool;
    verified attestations flow to `controller.on_valid_attestation_batch`.
    """

    def __init__(
        self,
        controller,
        backend=None,
        max_batch: int = MAX_BATCH,
        deadline_s: float = 0.050,
        max_active: "Optional[int]" = None,
        use_device: bool = True,
        use_registry: bool = True,
        pipeline_depth: int = 2,
        slasher=None,
        operation_pool=None,
        metrics=None,
        tracer=None,
        health: "Optional[_health.BackendHealthSupervisor]" = None,
        settle_timeout_s: float = 5.0,
        flight: "Optional[_flight.FlightRecorder]" = None,
        mesh=None,
    ) -> None:
        from grandine_tpu.tpu.mesh import mesh_or_none

        self.controller = controller
        self.cfg = controller.cfg
        self.backend = backend
        self.use_device = use_device
        #: injected VerifyMesh (tpu/mesh.py) threaded into the backend and
        #: the pubkey registry; None / 1-device collapses to single-chip
        self.mesh = mesh_or_none(mesh)
        #: observability: default to whatever the controller carries so
        #: node wiring stays one assignment; NULL_TRACER keeps span calls
        #: branch-free when tracing is off
        self.metrics = (
            metrics if metrics is not None
            else getattr(controller, "metrics", None)
        )
        self.tracer = (
            tracer or getattr(controller, "tracer", None) or NULL_TRACER
        )
        self.max_batch = max_batch
        self.deadline_s = deadline_s
        self.max_active = max_active or controller.pool.n_threads
        #: optional slasher fed with every ACCEPTED attestation; detected
        #: offenses become AttesterSlashing ops in the operation pool
        #: (the reference's slasher → validator proposer pipeline)
        self.slasher = slasher
        self.operation_pool = operation_pool

        #: target_epoch -> {data_root: (attestation, indices)} for recent
        #: epochs — the evidence store that turns a slasher hit into a
        #: full AttesterSlashing op (the reference's indexed-attestation
        #: DB keyed by target+root); epoch-bucketed so pruning is one
        #: dict-pop per stale epoch, not a rebuild
        self._recent_attestations: "dict[int, dict]" = {}
        #: serializes slasher spans + the evidence store across the
        #: concurrent batch-verify pool threads
        self._slasher_lock = threading.Lock()
        #: breaker + settle watchdog + canary gating; node.py passes the
        #: scheduler's supervisor so both verify planes quarantine the
        #: device together
        #: flight recorder — always-on (a private ring when none is
        #: injected; node.py shares one across the whole verify plane)
        self.flight = (
            flight if flight is not None
            else _flight.FlightRecorder(metrics=self.metrics)
        )
        self.health = (
            health if health is not None
            else _health.BackendHealthSupervisor(
                metrics=self.metrics, settle_timeout_s=settle_timeout_s,
                flight=self.flight,
            )
        )
        if self.health.flight is None:
            # an injected supervisor without its own recorder joins this
            # pipeline's timeline
            self.health.flight = self.flight
            self.health.breaker.flight = self.flight
        self._queue: "deque[GossipAttestation]" = deque()
        self._cond = threading.Condition()
        self._active = 0
        #: batches popped from the queue and not yet resolved (delivered,
        #: refused, or dropped by a settle error), whichever thread
        #: resolves them: what the collector holds a SHORT batch against
        self._outstanding = 0
        self._stop = False
        self.stats = {
            "batches": 0, "accepted": 0, "rejected": 0, "fallbacks": 0,
            "breaker_skips": 0, "retries": 0,
        }
        #: guards every `stats` bump — concurrent pool workers, the
        #: completion thread, and the slasher feed all mutate them
        self._stats_lock = threading.Lock()
        #: guards the lazy TpuBlsBackend build (pool workers race to it)
        self._backend_lock = threading.Lock()
        #: the widest committee of any device call built so far, kept only
        #: where it reaches a higher member bucket than the floor's (0: none
        #: has left bucket 4 yet). Every call names it beside the batch
        #: bucket, so once one full-size aggregate has gone out, every
        #: later call, a single vote included, runs THAT executable. Raised
        #: under `_width_lock` where a call is built (`_device_dispatch`),
        #: never lowered; a width is bound by the head state's committees
        #: (`get_attesting_indices`), so no peer raises it past the
        #: network's own bucket
        self._width_floor = 0
        self._width_lock = threading.Lock()

        #: device-resident pubkey registry (tpu/registry.py): the verify
        #: plane's warm path gathers committee pubkeys on-device by
        #: validator index instead of re-uploading 208 B/member per batch.
        #: Kept fresh via the controller's validator-set-change hook
        #: (deposits / finalization → mark_stale → prefix re-check).
        self.use_registry = use_registry
        self.registry = None
        if use_device and use_registry:
            from grandine_tpu.tpu.registry import DevicePubkeyRegistry

            self.registry = DevicePubkeyRegistry(
                metrics=self.metrics, mesh=self.mesh
            )
            hooks = getattr(controller, "on_validator_set_change", None)
            if hooks is not None:
                hooks.append(lambda old, new: self.registry.mark_stale())

        #: two-deep dispatch pipeline: batch tasks hand their device
        #: dispatch a zero-arg settle callable and return immediately, so
        #: batch N+1's host_prep/upload overlaps batch N's device execute
        #: (JAX async dispatch). The semaphore bounds device residency;
        #: the completion thread forces results in dispatch order.
        self.pipeline_depth = max(1, int(pipeline_depth))
        self._dispatch_sem = threading.BoundedSemaphore(self.pipeline_depth)
        self._inflight = 0
        self._completion: "Optional[queue.Queue]" = None
        self._completion_thread: "Optional[threading.Thread]" = None
        if use_device:
            self._completion = queue.Queue()
            self._completion_thread = threading.Thread(
                target=self._complete, name="attestation-settle", daemon=True
            )
        #: the slasher feed runs on a thread of its own, behind a FIFO of
        #: delivered batches: the delivering thread hands the accepted
        #: pairs over and frees its pipeline slot. At most
        #: `pipeline_depth` batches wait; a hand-over beyond that blocks
        #: (`slasher_wait`), so the slasher stays within `pipeline_depth`
        #: + 1 batches of fork choice
        self._feed_queue: "Optional[queue.Queue]" = None
        self._feeder: "Optional[threading.Thread]" = None
        #: batches handed to the feeder and not yet fed (under _cond):
        #: what `flush` waits for beside the pipeline
        self._unfed = 0
        if slasher is not None:
            self._feed_queue = queue.Queue(maxsize=self.pipeline_depth)
            self._feeder = threading.Thread(
                target=self._feed, name="attestation-slasher", daemon=True
            )
        # construct every thread before starting any: a started thread
        # must never observe a half-initialized verifier
        self._collector = threading.Thread(
            target=self._collect, name="attestation-verifier", daemon=True
        )
        if self._feeder is not None:
            self._feeder.start()
        if self._completion_thread is not None:
            self._completion_thread.start()
        self._collector.start()

    @property
    def batch_bucket(self) -> int:
        """The ONE batch bucket every device call of this verifier runs
        in, whatever it holds (a first pass of 1..max_batch items, a probe
        of a failed batch): what the warm-up compiles per committee width
        and what the flight row records."""
        return _flight.bucket_of(self.max_batch)

    @property
    def width_floor(self) -> int:
        """The widest committee every later device call is padded to at
        the least (0: nothing wider than bucket 4 dispatched yet)."""
        with self._width_lock:
            return self._width_floor

    @property
    def width_bucket(self) -> int:
        """The member bucket of `width_floor`: what a call of single votes
        runs in today."""
        return _width_bucket(self.width_floor)

    # ----------------------------------------------------------- ingestion

    def submit(self, attestation, origin: "Optional[str]" = None) -> None:
        with self._cond:
            self._queue.append(GossipAttestation(attestation, origin=origin))
            self._cond.notify()

    def submit_many(self, attestations: "Sequence",
                    origin: "Optional[str]" = None) -> None:
        # one call, one arrival: a stamp for the lot, not one an item
        wall, arrived = time.time(), time.perf_counter()
        with self._cond:
            self._queue.extend(
                GossipAttestation(a, wall, origin, arrived)
                for a in attestations
            )
            self._cond.notify()

    # ----------------------------------------------------------- collector

    def _collect(self) -> None:
        """Runs ONLY on the collector thread: owns the pending queue
        (under _cond) and batch formation; pool workers run the host
        fallback, the completion thread settles device batches."""
        while True:
            # crash containment: the collector must outlive any single
            # batch-forming failure (thread-crash-containment rule) —
            # account it and keep collecting
            try:
                if self._collect_once():
                    return
            except Exception:
                self._count_daemon_failure("attestation-verifier")
                with self._cond:
                    if self._stop:
                        return
                time.sleep(0.01)

    def _collect_once(self) -> bool:
        """One accumulate→spawn round; True when the collector should
        exit (stop() with an empty queue)."""
        with self._cond:
            # wait for the first item
            while not self._stop and not self._queue:
                self._cond.wait()
            if self._stop and not self._queue:
                return True
            # accumulate: dispatch when the batch bound is reached, the
            # deadline since the first item expires, or on shutdown —
            # this is what makes device launches dense under load
            deadline = time.monotonic() + self.deadline_s
            while (
                not self._stop
                and len(self._queue) < self.max_batch
                and (remaining := deadline - time.monotonic()) > 0
            ):
                self._cond.wait(remaining)
            # respect the concurrent-batch bound before dispatching. A
            # batch that can leave full takes any active slot. One that
            # met its deadline short is HELD while the pipeline is full
            # (`pipeline_depth` calls the device holds, one batch preparing
            # beside them): its call could not start before one of those
            # ends, so it waits here, not in the device's stream, and what
            # arrives meanwhile rides in its call. Re-read on every wake
            # (a submit, a batch resolved): it leaves when a slot frees or
            # when it has filled, whichever comes first
            held_at = waited_at = None
            while not self._stop:
                held = (
                    len(self._queue) < self.max_batch
                    and self._outstanding >= self.pipeline_depth + 1
                )
                if not held and self._active < self.max_active:
                    break
                if waited_at is None:
                    waited_at = time.perf_counter()
                if held and held_at is None:
                    held_at = time.perf_counter()
                    if self.metrics is not None:
                        self.metrics.att_batches_held.inc()
                self._cond.wait()
            if self._stop and not self._queue:
                return True
            batch = [
                self._queue.popleft()
                for _ in range(min(self.max_batch, len(self._queue)))
            ]
            if not batch:
                return False
            self._active += 1
            self._outstanding += 1
            popped = time.perf_counter()
            # what bounded the batch as it leaves: a batch that met its
            # deadline short and filled while it waited for a slot leaves
            # full
            closed_by = (
                "full" if len(batch) >= self.max_batch
                else "stop" if self._stop else "deadline"
            )
        if self.metrics is not None:
            self.metrics.att_batches_closed.inc(closed_by)
        # the batch's chain of spans begins here, back-dated to the
        # arrival of its oldest item: collect_wait is over, pool_wait runs
        life = _BatchLife(
            self.tracer, batch, popped, closed_by,
            0.0 if held_at is None else popped - held_at,
            0.0 if waited_at is None else popped - waited_at,
        )
        try:
            self.controller.pool.spawn(
                lambda: self._verify_batch(batch, life), Priority.LOW
            )
        except Exception:
            # pool stopped / spawn failure: release the batch's slots so
            # the collector cannot wedge on them
            with self._cond:
                self._active -= 1
                self._outstanding -= 1
                self._cond.notify_all()
            life.root.finish()
            raise
        return False

    # ------------------------------------------------------------- verify

    #: lane label on verify_stage_seconds — the attestation firehose is
    #: the scheduler's sibling "attestation" lane
    lane = "attestation"

    def _stage(self, stage: str, **attrs):
        """One pipeline stage (tracing.stage): a child span under the
        current trace context, a `verify_stage_seconds{stage,lane,op}`
        observation and, during a profiler capture session, a host span
        on the profiler's clock."""
        return _stage(self.tracer, self.metrics, stage, self.lane, **attrs)

    def _verify_batch(self, batch: "Sequence[GossipAttestation]",
                      life: _BatchLife) -> None:
        """Pool-thread entry. Every span of the batch hangs under the
        root the collector opened (`life.root`), which ends where the
        flight record commits — here, or on the completion thread."""
        t_batch = time.perf_counter()
        life.pool_span.finish()
        handed_over = False
        try:
            with self.tracer.attach(life.root):
                handed_over = self._verify_batch_traced(batch, life, t_batch)
        except BaseException:
            life.root.finish()  # no record committed: end the chain here
            raise
        finally:
            with self._cond:
                self._active -= 1
                if not handed_over:
                    # resolved (or lost) on this thread; a batch handed
                    # over stays outstanding until `_complete` is done
                    self._outstanding -= 1
                self._cond.notify()
            with self._stats_lock:
                self.stats["batches"] += 1
            if self.metrics is not None:
                self.metrics.att_batches.inc()
                self.metrics.att_batch_times.observe(
                    time.perf_counter() - t_batch
                )

    def _verify_batch_traced(self, batch: "Sequence[GossipAttestation]",
                             life: _BatchLife, t_start: float) -> bool:
        """True when the batch was handed to the completion thread, which
        then resolves it; otherwise it is resolved on return."""
        snapshot = self.controller.snapshot()
        state = snapshot.head_state
        prepared = []
        with self._stage("host_prep", op="prevalidate", items=len(batch)):
            for item in batch:
                try:
                    prepared.append(
                        self._prevalidate(state, item.attestation)
                        + (item.origin,)
                    )
                except (ForkChoiceError, ValueError, KeyError):
                    # KeyError: raced the mutator's finalization prune (the
                    # same race the block task path catches)
                    with self._stats_lock:
                        self.stats["rejected"] += 1
        t_prevalidated = time.perf_counter()
        if not prepared:
            life.root.finish()
            return False
        # what the SLO tracker charges to the queue: the oldest item's
        # arrival to here, i.e. collect_wait + pool_wait + prevalidation
        fl = self.flight.begin_batch(
            self.lane, "", len(prepared),
            queue_wait_s=time.perf_counter() - life.arrived,
            breaker_state=self.health.state if self.use_device else "",
            devices=self.mesh.device_count if self.mesh is not None else 1,
            bucket=self.batch_bucket if self.use_device else None,
        )
        fl.trace(life.root)
        fl.record.closed_by = life.closed_by
        fl.record.collect_wait_s = life.popped - life.arrived
        fl.record.held_s = life.held_s
        fl.record.pool_wait_s = max(0.0, t_start - life.popped)
        # an item's width is its attesting indices: known from here on
        widths = [len(p[5]) for p in prepared]
        fl.record.width, fl.record.width_min = max(widths), min(widths)
        life.root.set_attr("width", fl.record.width)
        life.root.set_attr("width_min", fl.record.width_min)
        if (self.metrics is not None and _width_bucket(fl.record.width_min)
                != _width_bucket(fl.record.width)):
            self.metrics.att_mixed_batches.inc()
        skipped = False
        if self.use_device and self._completion is not None:
            if not self.health.allow_device():
                # breaker OPEN: zero device dispatch attempts — straight
                # to the host anchor below, no per-batch fault tax
                with self._stats_lock:
                    self.stats["breaker_skips"] += 1
                skipped = True
            else:
                t0 = time.perf_counter()
                # what the batch did before its call, for the device's
                # idle time ahead of it (runtime/profiler.py)
                with _profiler.dispatch_phases(
                    ("collect", life.arrived),
                    ("hold", life.popped - life.slot_wait_s),
                    ("pool_wait", life.popped),
                    ("prevalidate", t_start),
                    ("host_prep", t_prevalidated),
                ):
                    try:
                        settle = self._device_dispatch(prepared, fl=fl)
                        fl.note_device(time.perf_counter() - t0)
                    except Exception:
                        fl.note_device(time.perf_counter() - t0)
                        fl.note_fault("dispatch")
                        self.health.record_fault("dispatch")
                        # bounded transient retry: one immediate
                        # re-dispatch
                        settle = self._retry_dispatch(prepared, fl)
                if settle is not None:
                    # pipelined path: readback is deferred to the
                    # completion thread so this pool thread (and the
                    # collector behind it) can start the NEXT batch's
                    # host_prep while the device executes this one
                    fl.record.kernel = "fast_aggregate"
                    self._enqueue_settle(settle, prepared, fl)
                    return True
        t0 = time.perf_counter()
        ok = self._batch_check(prepared)
        dt = time.perf_counter() - t0
        if self.use_device and not skipped:
            fl.note_device(dt)
        else:
            fl.note_host(dt)
        self._resolve_batch(prepared, ok, fl)
        return False

    def _resolve_batch(self, prepared, ok: bool, fl=None) -> None:
        """Deliver a settled batch verdict: feedback on success, bisection
        on failure. Runs on the pool thread (sync path) or the completion
        thread (pipelined path)."""
        if fl is None:
            fl = self.flight.begin_batch(
                self.lane, "", len(prepared),
                devices=(
                    self.mesh.device_count if self.mesh is not None else 1
                ),
            )
        if ok:
            # a check off the registry path verified all but the items
            # whose keys it `refused` (`_member_keys`)
            delivered = [p for p in prepared if not p[2].refused]
            with self._stats_lock:
                self.stats["accepted"] += len(delivered)
                self.stats["rejected"] += len(prepared) - len(delivered)
            if delivered:
                self._feedback(delivered)
            fl.finish(True)
            return
        # batch failed: BISECT to the bad items with batch checks —
        # O(k·log n) verifies for k bad signatures instead of n
        # singular host pairings. The singular-per-item fallback
        # (attestation_verifier.rs:231-239) costs ~0.7 s/item on the
        # host anchor; at the adversarial operating point of ~1 bad
        # signature per batch that re-verifies EVERY item and blows
        # the 4 s deadline — this is the DoS surface of batch
        # verification, and bisection caps it.
        with self._stats_lock:
            self.stats["fallbacks"] += 1
        if self.metrics is not None:
            self.metrics.att_fallbacks.inc()
        with self._stage("fallback", items=len(prepared)):
            t0 = time.perf_counter()
            good_items, bad_count, probes = self._isolate(prepared)
            fl.note_bisect(
                time.perf_counter() - t0,
                depth=max(1, len(prepared).bit_length()),
                probes=probes,
            )
        if bad_count and self.metrics is not None:
            self.metrics.att_isolated_batches.inc()
        good_ids = {id(p) for p in good_items}
        delivered = [p for p in good_items if not p[2].refused]
        bad_count += len(good_items) - len(delivered)
        if bad_count == 0:
            # the batch verdict said "invalid" yet bisection cleared
            # every item: a wrong-verdict device — file the fault kind
            # only canary probes catch at re-promotion
            self.health.record_fault("verdict")
            fl.note_fault("verdict")
        else:
            # attribute each bisection-named bad item to its gossip
            # origin (bounded top-K — the quarantine lane's feed)
            for p in prepared:
                if id(p) not in good_ids:
                    fl.note_origin_failure(p[7])
        with self._stats_lock:
            self.stats["accepted"] += len(delivered)
            self.stats["rejected"] += bad_count
        if delivered:
            self._feedback(delivered)
        fl.finish(bad_count == 0)

    def _feedback(self, accepted) -> None:
        """The verdicts to fork choice (`feedback` / `deliver`), then the
        accepted pairs to the feeder thread (`_feed`), which runs the
        `feedback` / `slasher_feed` part: AFTER delivery, so a slasher
        problem never costs fork choice its verified votes, and off this
        thread, so a feed never holds the next verdict back. Blocks only
        while `pipeline_depth` batches already wait for the feeder."""
        with self._stage("feedback", op="deliver", items=len(accepted)):
            self.controller.on_valid_attestation_batch(
                [p[3] for p in accepted]
            )
        if self._feed_queue is None:
            return
        # the batch's root rides along: the feed's stage hangs under it
        waiting = ([(p[4], p[3]) for p in accepted], self.tracer.capture())
        with self._cond:
            self._unfed += 1
        try:
            self._feed_queue.put_nowait(waiting)
        except queue.Full:
            if self.metrics is not None:
                self.metrics.att_slasher_feed_blocked.inc()
            with self.tracer.span("slasher_wait", {"items": len(accepted)}):
                self._feed_queue.put(waiting)

    def _feed(self) -> None:
        """Feeder thread: feeds delivered batches in delivery order until
        the sentinel `stop` queues behind the last one."""
        while True:
            # crash containment: `_feed_slasher` counts the slasher's own
            # faults; anything else is accounted here and the loop goes on
            try:
                if self._feed_once():
                    return
            except Exception:
                self._count_daemon_failure("attestation-slasher")

    def _feed_once(self) -> bool:
        """Wait for a delivered batch, take it with every batch queued
        behind it at that moment, and feed them all in ONE `_feed_slasher`
        call (`Slasher.on_attestations_bulk` is calling `on_attestation`
        in order: one call detects what one call a batch would, in one
        storage transaction). The stage's seconds stay the sum over the
        window's batches, its span hangs under the first batch's root.
        True at the sentinel."""
        taken = [self._feed_queue.get()]
        for _ in range(self._feed_queue.qsize()):
            taken.append(self._feed_queue.get_nowait())
        batches = [t for t in taken if t is not None]
        try:
            if batches:
                if self.metrics is not None:
                    self.metrics.att_slasher_feed_calls.inc()
                    self.metrics.att_slasher_feed_batches.inc(len(batches))
                pairs = [pair for b, _root in batches for pair in b]
                with self.tracer.attach(batches[0][1]), self._stage(
                    "feedback", op="slasher_feed", items=len(pairs),
                    batches=len(batches),
                ):
                    self._feed_slasher(pairs)
        finally:
            with self._cond:
                self._unfed -= len(batches)
                self._cond.notify_all()
        return len(batches) < len(taken)

    # ------------------------------------------------------------ pipeline

    def _device_dispatch(self, prepared, parent=None, fl=None):
        """Host prep + async device dispatch for one prepared batch.
        Returns a zero-arg settle callable producing the batch verdict, or
        None when the backend lacks the async seam (`_batch_check` then
        answers from the host anchor). `parent` is the failed batch that
        `prepared` is a part of, when the call is a probe of its descent
        (`_isolate`), and is then counted as a probe. Every call names the
        verifier's one batch bucket and its width floor as its floor
        (`bucket_floor`), so a first pass of 1..max_batch items of any
        width seen so far and a probe of any part of it run the same
        executable; a probe also names its parent's widest committee, so
        it stays in the parent's width bucket. `fl` is the first pass's
        flight context: it is told the width bucket dispatched."""
        backend = self._ensure_backend()
        if not _health.has_async_seam(backend):
            return None
        messages = [p[0] for p in prepared]
        if self.metrics is not None:
            self.metrics.signature_decompress_items.inc(
                A.g2_batch_path(), amount=len(prepared)
            )
        try:
            # decompress WITHOUT the per-signature host subgroup
            # scalar-mul (~9 ms each — it dominated batch latency); the
            # device checks the whole batch in one ψ ladder. One call a
            # batch: native and off the GIL where the library loaded
            with self._stage("host_prep", op="g2_decompress",
                             items=len(prepared)):
                points = A.g2_from_bytes_batch(
                    [bytes(p[1]) for p in prepared]
                )
        except A.BlsError:
            return lambda: False
        if any(p.is_infinity() for p in points):
            return lambda: False
        # Fused backends fold the ψ-ladder membership check into the
        # verify kernel itself (check_subgroup static): ONE device
        # dispatch per batch. Two-pass backends stack both dispatches
        # before any readback: subgroup ladder and verify kernel queue
        # back-to-back on the device. Verifying a not-yet-subgroup-
        # checked (but on-curve) point is safe either way — if the
        # membership check fails the batch verdict is False and the
        # items fall to bisection, every probe of which comes through
        # here again and carries the same membership check.
        fused = getattr(backend, "fuse_subgroup", False)
        sub_settle = (
            None if fused else backend.g2_subgroup_check_batch_async(points)
        )
        sigs = [A.Signature(p) for p in points]
        if self.metrics is not None:
            self.metrics.device_batch_sigs.inc(len(sigs))
        # padding slots carry no verdict and change none (tpu/bls.py
        # `bucket_floor`): the width axis runs in the floor's bucket, and
        # a probe in its parent's at the least
        widest = max(len(p[5]) for p in (prepared if parent is None
                                         else parent))
        width_floor = self._raise_width_floor(widest)
        width_bucket = _width_bucket(width_floor)
        if parent is None:
            floor = (self.batch_bucket, width_floor)
            self._count_first_pass(prepared, width_bucket)
        else:
            floor = (self.batch_bucket, max(width_floor, widest))
            self._count_probe(len(prepared))
        if fl is not None:
            fl.record.width_bucket = width_bucket
            if fl.root is not None:
                fl.root.set_attr("width_bucket", width_bucket)
        registry = self._sync_registry(prepared)
        if registry is not None:
            ver_settle = backend.fast_aggregate_verify_batch_indexed_async(
                messages, sigs, [p[5] for p in prepared], registry,
                bucket_floor=floor,
            )
        else:
            keyed = self._member_keys(prepared, "upload")
            ver_settle = backend.fast_aggregate_verify_batch_async(
                [messages[i] for i in keyed], [sigs[i] for i in keyed],
                [prepared[i][2].points for i in keyed], bucket_floor=floor,
            ) if keyed else (lambda: True)

        def settle() -> bool:
            if sub_settle is not None and not bool(sub_settle().all()):
                return False
            return bool(ver_settle())

        return settle

    def _ensure_backend(self):
        """The verify backend, lazily building the real TpuBlsBackend
        (which then also answers the supervisor's canary probes;
        injected backends keep whatever probe the caller wired).
        Concurrent pool workers race to the first build: the lock keeps
        the backend a singleton (one jit cache, one canary probe)."""
        with self._backend_lock:
            backend = self.backend
            if backend is None:
                from grandine_tpu.tpu import schemes

                backend = self.backend = schemes.get("bls").make_backend(
                    metrics=self.metrics, tracer=self.tracer, mesh=self.mesh
                )
                self.health.ensure_probe(_health.make_canary_probe(
                    backend, timeout_s=self.health.settle_timeout_s
                ))
        return backend

    def _retry_dispatch(self, prepared, fl=None):
        """Bounded transient retry: ONE immediate re-dispatch after a
        dispatch fault, breaker permitting."""
        if not self.health.allow_device():
            return None
        with self._stats_lock:
            self.stats["retries"] += 1
        if self.metrics is not None:
            self.metrics.verify_retry.inc(self.lane)
        if fl is not None:
            fl.note_retry()
        t0 = time.perf_counter()
        try:
            return self._device_dispatch(prepared, fl=fl)
        except Exception:
            self.health.record_fault("dispatch")
            if fl is not None:
                fl.note_fault("dispatch")
            return None
        finally:
            if fl is not None:
                fl.note_device(time.perf_counter() - t0)

    def _count_daemon_failure(self, thread: str) -> None:
        if self.metrics is not None:
            self.metrics.daemon_loop_failures.inc(thread)

    def _sync_registry(self, prepared):
        """Bring the registry up to date with the batch's head-state
        pubkey columns (identity hit when nothing changed); None → take
        the upload path."""
        registry = self.registry
        if registry is None:
            return None
        try:
            with self._stage("host_prep", op="registry_sync",
                             items=len(prepared)):
                if registry.ensure(prepared[0][6]):
                    return registry
        except A.BlsError:
            # corrupted registry bytes: keep the upload path (and its
            # per-key validation) rather than poisoning the device mirror
            pass
        return None

    def _enqueue_settle(self, settle, prepared, fl=None) -> None:
        """Hand a dispatched batch to the completion thread. Blocks when
        `pipeline_depth` batches are already in flight — backpressure that
        bounds device residency."""
        # the slot is released on the completion thread in _complete's
        # finally, so a `with` cannot express this handoff
        t0 = time.perf_counter()
        wait = self.tracer.span("dispatch_wait")
        self._dispatch_sem.acquire()  # lint: disable=thread-affinity
        wait.finish()
        t_put = time.perf_counter()
        if fl is not None:
            fl.record.dispatch_wait_s = t_put - t0
        with self._cond:
            self._inflight += 1
            depth = self._inflight
        if self.metrics is not None:
            self.metrics.verify_pipeline_depth.set(depth)
        self.flight.device_enter()
        # the batch's root rides along (the pool thread's current span):
        # the completion thread's stages hang under it too
        self._completion.put((
            settle, prepared, self.tracer.capture(), fl,
            self.tracer.span("settle_wait"), t_put,
        ))

    def _complete(self) -> None:
        """Completion thread: force settled batch verdicts in dispatch
        order and deliver feedback. Readback happens HERE, off the
        dispatch path, so the pool threads never block on the device."""
        while True:
            item = self._completion.get()
            if item is None:
                return
            settle, prepared, span_ctx, fl, wait, t_put = item
            wait.finish()
            if fl is not None:
                fl.record.settle_wait_s = time.perf_counter() - t_put
            try:
                with self.tracer.attach(span_ctx):
                    self._settle_one(settle, prepared, fl)
            except Exception:
                # the completion thread must survive backend faults; the
                # batch is dropped (counted), not silently accepted
                with self._stats_lock:
                    self.stats["settle_errors"] = (
                        self.stats.get("settle_errors", 0) + 1
                    )
                if fl is not None:
                    fl.finish(None)
            finally:
                self.flight.device_exit()
                self._dispatch_sem.release()
                with self._cond:
                    self._inflight -= 1
                    self._outstanding -= 1
                    depth = self._inflight
                    self._cond.notify_all()
                if self.metrics is not None:
                    self.metrics.verify_pipeline_depth.set(depth)

    def _settle_one(self, settle, prepared, fl=None) -> None:
        """Force one batch verdict under the settle watchdog. A fault or
        watchdog expiry files a breaker fault and DEGRADES the batch to a
        fresh (breaker-gated device or host) re-check — honest votes are
        never dropped on a backend hiccup."""
        t0 = time.perf_counter()
        with self._stage("settle", items=len(prepared)) as span:

            def settle_traced():
                # the watchdog runs the settle on a thread of its own:
                # the backend's execute / readback stages hang under this
                # stage all the same
                with self.tracer.attach(span):
                    return settle()

            outcome = self.health.guard_settle(
                settle_traced, thread_name="attestation-settle-watchdog"
            )
        if fl is not None:
            fl.record.settle_s = time.perf_counter() - t0
            fl.note_device(fl.record.settle_s)
        if outcome.status == _health.OK:
            self.health.record_success()
            self._resolve_batch(prepared, bool(outcome.value), fl)
            return
        if outcome.status == _health.TIMEOUT:
            # abandon the hung settle (its thread is an expendable
            # daemon); the pipeline slot is released by the caller's
            # finally, so backpressure clears immediately
            if self.metrics is not None:
                self.metrics.verify_watchdog_fired.inc(self.lane)
            self.health.record_fault("watchdog")
            if fl is not None:
                fl.note_fault("watchdog")
        else:
            self.health.record_fault("settle")
            if fl is not None:
                fl.note_fault("settle")
        with self._stats_lock:
            self.stats["settle_errors"] = (
                self.stats.get("settle_errors", 0) + 1
            )
        t0 = time.perf_counter()
        ok = self._batch_check(prepared)
        if fl is not None:
            fl.note_host(time.perf_counter() - t0)
        self._resolve_batch(prepared, ok, fl)

    def _isolate(self, prepared):
        """The descent over a FAILED batch, inside the batch's own bucket:
        a probe re-checks a part of the batch as a batch padded to the
        parent's shape (`_device_dispatch(parent=)`: the executable the
        batch itself ran, over the resident registry: no second kernel, no
        second shape, nothing to compile on the settle path). A call costs
        the same whatever its width, so the schedule (`_descend`) spends
        ONE probe a level and clears what it passed over in one call: one
        bad item in 2^k costs k + 1 or k + 2 probes. Every item delivered
        lay in a probe that verified; every item rejected was refused by a
        probe of that item alone, so nothing is rejected on inference.
        Returns (good_items in the batch's order, bad_count, probes)."""
        descent = _Descent(prepared)
        self._descend(descent, 0, len(prepared), False, 0)
        bad = descent.bad
        good = [p for i, p in enumerate(prepared) if i not in bad]
        return good, len(bad), descent.probes

    def _descend(self, descent: "_Descent", lo: int, hi: int, refused: bool,
                 depth: int) -> None:
        """The schedule over the suspect set parent[lo:hi], a set known to
        hold a bad item: by a probe of exactly this set that was `refused`,
        else by inference (a set that holds no bad item always verifies,
        so a suspect set whose first half verified has its bad item in the
        second; the failed batch itself counts as inferred, so a batch of
        one is re-checked). `depth` is the halvings that gave the set.
        Names the bad items' positions into `descent.bad`.

        1. Probe the first half only. Verified: it is good, go on in the
           second half by inference. Refused: go on in the first half, the
           second is DEFERRED (verdict unknown).
        2. One item left: refused by its own probe, it is bad; reached by
           inference, it gets the confirming probe.
        3. The deferred halves lie side by side behind the item (each was
           cut off the end of the set), so ONE probe clears their union.
           If that is refused: a single deferred half is the next suspect
           set (this was its probe); several get a probe each, smallest
           first (the batch's order), the last by inference if the others
           all verified, and the schedule again inside each refused one."""
        deferred = []  # (lo, hi, depth), the largest first
        while hi - lo > 1:
            mid = lo + (hi - lo) // 2
            depth += 1
            if self._probe(descent, lo, mid, depth, "first_half"):
                lo, refused = mid, False
                self._count_inferred()
            else:
                deferred.append((mid, hi, depth))
                hi, refused = mid, True
        if refused or not self._probe(descent, lo, hi, depth, "confirm"):
            descent.bad.add(lo)
        if not deferred:
            return
        _, top, shallowest = deferred[0]
        cleared = self._probe(descent, hi, top, shallowest, "union")
        if self.metrics is not None:
            self.metrics.att_isolation_union_probes.inc(
                "ok" if cleared else "refused"
            )
        if cleared:
            return
        if len(deferred) == 1:
            self._descend(descent, hi, top, True, shallowest)
            return
        others_verified = True
        for a, b, d in reversed(deferred):
            if b == top and others_verified:
                self._count_inferred()
                self._descend(descent, a, b, False, d)
            elif not self._probe(descent, a, b, d, "piece"):
                others_verified = False
                self._descend(descent, a, b, True, d)

    def _probe(self, descent: "_Descent", lo: int, hi: int, depth: int,
               why: str) -> bool:
        """One re-check of parent[lo:hi], a part of a failed batch: a
        `probe` span under the `fallback` stage (a plain span: the stage's
        seconds stay the whole descent's, counted once). Non-crypto errors
        (device/runtime faults) PROPAGATE: honest votes must not be
        silently rejected on a backend hiccup; the pool's task catch
        surfaces the failure like the old fallback."""
        parent = descent.parent
        descent.probes += 1
        with self.tracer.span("probe", {
            "op": "probe", "items": hi - lo,
            "bucket": self.batch_bucket, "depth": depth,
            "why": why,
        }), _profiler.dispatch_phases(("descent", float("-inf"))):
            try:
                return bool(self._batch_check(parent[lo:hi], parent))
            except ValueError:
                return False  # a malformed signature inside: refused

    def _count_inferred(self) -> None:
        if self.metrics is not None:
            self.metrics.att_isolation_inferred.inc()

    def _count_probe(self, items: int) -> None:
        if self.metrics is not None:
            self.metrics.att_isolation_probes.inc()
            self.metrics.att_isolation_probe_items.inc(items)
            self.metrics.att_isolation_probe_slots.inc(self.batch_bucket)

    def _count_first_pass(self, prepared, width_bucket: int) -> None:
        if self.metrics is not None:
            self.metrics.att_first_pass_items.inc(len(prepared))
            self.metrics.att_first_pass_slots.inc(self.batch_bucket)
            self.metrics.att_first_pass_members.inc(
                sum(len(p[5]) for p in prepared))
            self.metrics.att_first_pass_member_slots.inc(
                self.batch_bucket * width_bucket)

    def _raise_width_floor(self, widest: int) -> int:
        """The width floor for a call whose widest committee is `widest`:
        raised to it first where that reaches a higher member bucket."""
        with self._width_lock:
            raised = _width_bucket(widest) > _width_bucket(self._width_floor)
            if raised:
                self._width_floor = widest
            floor = self._width_floor
        if self.metrics is not None:
            if raised:
                self.metrics.att_width_floor_raised.inc()
            self.metrics.att_width_bucket.set(_width_bucket(floor))
        return floor

    def _prevalidate(self, state, attestation):
        """Committee lookup + fork-choice windows; returns
        (signing_root, signature_bytes, member_keys, ValidAttestation,
        attestation, member_indices, state_pubkey_columns). No key is
        decompressed here: the registry path gathers the members on the
        device by index, and a check off it decompresses them from the
        state's compressed-pubkey tuple into `member_keys`, a
        `_MemberKeys` (`_member_keys`)."""
        p = self.cfg.preset
        data = attestation.data
        indices = accessors.get_attesting_indices(
            state, data, attestation.aggregation_bits, p
        )
        if len(indices) == 0:
            raise ValueError("empty attestation")
        idx_list = [int(i) for i in indices]
        valid = self.controller.store.validate_attestation(
            int(data.slot),
            int(data.index),
            int(data.target.epoch),
            bytes(data.beacon_block_root),
            bytes(data.target.root),
            idx_list,
        )
        root = signing.attestation_signing_root(state, data, self.cfg)
        return (
            root, bytes(attestation.signature), _MemberKeys(), valid,
            attestation, idx_list, accessors.registry_columns(state).pubkeys,
        )

    def _member_keys(self, prepared, path: str) -> "list[int]":
        """The positions in `prepared` a check off the registry path
        verifies, their members' keys decompressed into `p[2]` through the
        process-wide key cache where no check has yet (`path`: "upload" or
        "host", the counter's label). An item one of whose keys does not
        decompress is left out: it is `refused`, and `_resolve_batch`
        rejects it on its own while its batch-mates are judged without
        it."""
        decompressed = 0
        for p in prepared:
            held = p[2]
            if held.points is None and not held.refused:
                decompressed += len(p[5])
                try:
                    held.points = [
                        keys.decompress_pubkey(p[6][i], trusted=True)
                        for i in p[5]
                    ]
                except A.BlsError:
                    held.refused = True
        if decompressed and self.metrics is not None:
            self.metrics.att_member_keys_decompressed.inc(
                path, amount=decompressed)
        return [i for i, p in enumerate(prepared) if not p[2].refused]

    #: evidence retention window (epochs) for building slashing ops
    SLASHER_EVIDENCE_EPOCHS = 64

    def _feed_slasher(self, accepted_pairs) -> None:
        """Run every ACCEPTED attestation through the slasher; a hit is
        turned into a full AttesterSlashing op for the proposer pipeline
        when the conflicting attestation is still in the evidence window
        (slasher.rs → validator slashing forwarding). The feeder thread's
        body (`_feed_once`), synchronous; serialized by _slasher_lock (the
        slasher's span chunks are not thread-safe) and exception-isolated
        — detection must never break verification."""
        if self.slasher is None:
            return
        try:
            with self._slasher_lock:
                # pass 1: evidence-window bookkeeping + normalization
                batch = []  # (attestation, indices, source, target, root)
                for attestation, valid in accepted_pairs:
                    data = attestation.data
                    source = int(data.source.epoch)
                    target = int(data.target.epoch)
                    data_root = bytes(data.hash_tree_root())
                    indices = [int(i) for i in valid.indices]
                    bucket = self._recent_attestations.get(target)
                    if bucket is None:
                        bucket = self._recent_attestations[target] = {}
                        # a NEW epoch appeared: drop stale epoch buckets
                        # (one pop per epoch, not a rebuild per item)
                        floor = target - self.SLASHER_EVIDENCE_EPOCHS
                        for e in [
                            e
                            for e in self._recent_attestations
                            if e < floor
                        ]:
                            del self._recent_attestations[e]
                    # keep up to a few aggregates per data root: a later
                    # NARROWER aggregate must not evict the one holding
                    # the offender (each op's signature must match its
                    # own indices, so entries cannot be union-merged)
                    entries = bucket.setdefault(data_root, [])
                    idx_set = set(indices)
                    if not any(idx_set <= set(i) for _a, i in entries):
                        entries.append((attestation, indices))
                        del entries[:-4]
                    batch.append(
                        (attestation, indices, source, target, data_root)
                    )
                # pass 2: one bulk slasher call for the whole accepted
                # batch — span updates merge across aggregates instead
                # of walking chunks per attesting index
                hit_lists = self.slasher.on_attestations_bulk(
                    [(ix, s, t, r) for _a, ix, s, t, r in batch]
                )
                for (attestation, indices, _s, _t, _r), hits in zip(
                    batch, hit_lists
                ):
                    # a committee-wide equivocation yields one hit per
                    # validator with (usually) shared evidence: skip a
                    # hit only when an ALREADY-BUILT op's index
                    # intersection covers that validator — never on the
                    # evidence key alone (validators may live in
                    # disjoint stored aggregates)
                    covered: "set[int]" = set()
                    for hit in hits:
                        if hit.validator_index in covered:
                            continue
                        newly = self._build_slashing_op(
                            hit, attestation, indices
                        )
                        if newly:
                            covered |= newly
        except Exception:
            with self._stats_lock:
                self.stats["slasher_errors"] = (
                    self.stats.get("slasher_errors", 0) + 1
                )

    def _build_slashing_op(self, hit, attestation, indices):
        """Build + pool one AttesterSlashing for `hit`; returns the set
        of validator indices the op's intersection covers (None if no op
        could be built)."""
        if self.operation_pool is None:
            return None
        # the conflicting vote as the slasher read it at detection: a
        # later vote of the same call at that target does not replace it
        if hit.kind == "double_vote":
            prior_target = int(hit.evidence["target_epoch"])
        elif hit.kind in ("surround_vote", "surrounded_vote"):
            prior_target = int(hit.evidence["existing"][1])
        else:
            return None
        if hit.evidence["roots"][0] is None:
            return None  # evidence pruned
        prior_root = bytes.fromhex(hit.evidence["roots"][0])
        entries = self._recent_attestations.get(prior_target, {}).get(
            prior_root, []
        )
        if not entries:
            return None  # conflicting attestation no longer retrievable
        # prefer evidence that contains the offending validator (the op
        # slashes the INTERSECTION of the two index sets)
        prev_att, prev_indices = entries[0]
        for att_i, idx_i in entries:
            if hit.validator_index in idx_i:
                prev_att, prev_indices = att_i, idx_i
                break
        from grandine_tpu.types.combined import fork_namespace, state_phase_of

        snap = self.controller.snapshot()
        tns = fork_namespace(
            self.cfg, state_phase_of(snap.head_state, self.cfg)
        )
        prev_indexed = tns.IndexedAttestation(
            attesting_indices=sorted(prev_indices),
            data=prev_att.data,
            signature=bytes(prev_att.signature),
        )
        cur_indexed = tns.IndexedAttestation(
            attesting_indices=sorted(indices),
            data=attestation.data,
            signature=bytes(attestation.signature),
        )
        # spec is_slashable_attestation_data(data_1, data_2) surrounds
        # as data_1.source < data_2.source AND data_2.target <
        # data_1.target: the SURROUNDING attestation must be
        # attestation_1. For a "surround_vote" hit the NEW attestation
        # surrounds the existing one.
        if hit.kind == "surround_vote":
            att1, att2 = cur_indexed, prev_indexed
        else:
            att1, att2 = prev_indexed, cur_indexed
        slashing = tns.AttesterSlashing(
            attestation_1=att1, attestation_2=att2
        )
        if self.operation_pool.insert_attester_slashing(slashing):
            with self._stats_lock:
                self.stats["slashings_emitted"] = (
                    self.stats.get("slashings_emitted", 0) + 1
                )
        return set(prev_indices) & set(indices)

    def _batch_check(self, prepared, parent=None) -> bool:
        """One synchronous verdict over `prepared`: through the entry the
        pipelined first pass takes (`_device_dispatch`: the indexed
        kernel over the resident registry, else the upload entry; padded
        to `parent`'s bucket for a probe) while the device is allowed,
        else from the host anchor."""
        if self.use_device and self.health.allow_device():
            try:
                settle = self._device_dispatch(prepared, parent)
                ok = None if settle is None else bool(settle())
            except ValueError:
                # crypto-malformed input (BlsError): the item's problem,
                # not the device's — no breaker fault
                raise
            except Exception:
                # device/runtime fault: feed the breaker, then PROPAGATE
                # (see _probe — honest votes are not silently rejected)
                self.health.record_fault("settle")
                raise
            if ok is not None:
                self.health.record_success()
                return ok
        # host anchor path (breaker OPEN / a backend without the async
        # seam / tests): all host work, so the whole check is the
        # "execute" stage
        with self._stage("execute", path="host", items=len(prepared)):
            try:
                return all(
                    A.Signature.from_bytes(prepared[i][1])
                    .fast_aggregate_verify(prepared[i][0],
                                           prepared[i][2].points)
                    for i in self._member_keys(prepared, "host")
                )
            except A.BlsError:
                return False

    # ------------------------------------------------------------ control

    def flush(self, timeout: float = 30.0) -> None:
        """Drain the queue, all in-flight batches, the pipelined settle
        queue and the slasher feed (test barrier)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            self._cond.notify()
        while time.monotonic() < deadline:
            with self._cond:
                if (not self._queue and self._active == 0
                        and self._inflight == 0 and self._unfed == 0):
                    return
                self._cond.notify()
            time.sleep(0.01)
        raise TimeoutError("attestation verifier did not drain")

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._collector.join(timeout=5)
        # the batches the collector spawned last resolve or reach the
        # completion queue before its sentinel does
        with self._cond:
            self._cond.wait_for(lambda: self._active == 0, timeout=5)
        if self._completion is not None:
            # sentinel queues BEHIND any still-pending settles, so they
            # drain before the thread exits
            self._completion.put(None)
            if self._completion_thread is not None:
                self._completion_thread.join(timeout=10)
        if self._feeder is not None:
            # and the feeder's BEHIND the last delivered batch: nothing
            # accepted goes unfed
            self._feed_queue.put(None)
            self._feeder.join(timeout=10)


__all__ = ["AttestationVerifier", "GossipAttestation", "MAX_BATCH"]
