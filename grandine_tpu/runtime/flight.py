"""Verify-plane flight recorder: one timeline for every dispatch surface.

The verify plane spans five dispatch surfaces — the attestation firehose
(runtime/attestation_verifier.py), the scheduler lanes
(runtime/verify_scheduler.py), bulk replay windows (runtime/replay.py),
canary probes and breaker transitions (runtime/health.py) — but the
aggregate histograms can't answer "which batch missed its deadline and
why", "how much device capacity is padding waste", or "which peer's
traffic keeps poisoning batches". This module records a bounded ring of
per-batch `BatchRecord` events (plus canary and breaker events, in the
SAME timeline, so a fault → breaker-open → probe → re-close sequence
reads as consecutive records) and derives four things on top:

  SLO tracker      — each settled batch is compared against its lane's
                     deadline budget; a miss increments
                     `verify_slo_miss_total{lane,cause}` where `cause`
                     names the dominant component: queue_wait (sat in
                     the lane queue), device (device execute + the host
                     pass a device fault forced), bisection (failed-
                     batch isolation), or breaker_open (dispatch was
                     skipped with the breaker open). The cause set is a
                     closed enum (SLO_CAUSES) — the metrics-cardinality
                     lint rule rejects values outside it.
  fill histograms  — items vs the pow-2 device bucket actually
                     compiled: `verify_bucket_fill_ratio{kernel}` and
                     `verify_padding_waste_total{kernel}` are the
                     capacity-planning input for multi-chip promotion
                     (ROADMAP item 1).
  origin table     — failing jobs attribute their gossip peer/validator
                     origin (threaded through `VerifyTicket`) into a
                     bounded top-K table (space-saving eviction, so k
                     counters survive adversarial origin churn). This
                     is the attribution feed the quarantine lane
                     (ROADMAP item 2) consumes. Origins appear ONLY in
                     the flight ring and the debug endpoint — never as
                     Prometheus label values (unbounded cardinality;
                     the lint rule enforces this too).
  duty cycle       — device_enter/device_exit bracket dispatched work
                     (host deltas: dispatch handed off -> settle
                     forced); the recorder integrates busy time into
                     `verify_device_duty_cycle`, which the brownout
                     controller reads. The device's own busy and idle
                     time is the profiler's timeline (runtime/
                     profiler.py).

Lock-light by design: one short-hold lock guards the ring index and the
duty-cycle accumulators; records are built outside it. Recording is
always-on (the scheduler and firehose construct a recorder when none is
injected) and must stay inside the ≤5% instrumentation-overhead guard
(tests/test_flight.py).
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Optional

# ------------------------------------------------------------------ enums

#: record kinds sharing the flight timeline
BATCH = "batch"
CANARY = "canary"
BREAKER = "breaker"
RECORD_KINDS = (BATCH, CANARY, BREAKER)

#: the CLOSED cause enum on verify_slo_miss_total — the metrics-
#: cardinality lint rule parses this tuple and rejects any literal
#: `cause` outside it, and `_slo_cause` below can only return members.
#: `expired` = a ticket's absolute deadline passed before dispatch and
#: it was shed un-dispatched; `brownout` = the overload controller
#: (runtime/brownout.py) shed it — shed-oldest overflow, or a
#: CRITICAL-level submit refusal.
SLO_CAUSES = ("queue_wait", "device", "bisection", "breaker_open",
              "expired", "brownout")

#: per-lane deadline budgets (seconds, enqueue→settle). HIGH scheduler
#: lanes sit on the block-import path; the attestation budget is the
#: spec's 4 s gossip propagation window; replay windows are wall-time
#: bounded only by throughput targets.
DEFAULT_SLO_BUDGETS = {
    "block": 0.5,
    "blob_header": 0.5,
    "sync_contribution": 0.5,
    "sync_message": 1.0,
    "slashing": 2.0,
    "exit": 2.0,
    "bls_change": 2.0,
    # suspect-origin traffic: correctness matters, latency does not —
    # small batches from quarantined origins may wait behind every
    # honest lane
    "quarantine": 5.0,
    "attestation": 4.0,
    "replay": 120.0,
    # slasher span ingestion: keep-up is throughput-gated (span-update
    # rate ≥ attestation arrival rate), but any single batch blowing the
    # gossip window means detections lag the chain
    "slasher": 4.0,
}
DEFAULT_SLO_BUDGET_S = 4.0  # unknown lanes


def bucket_of(items: int) -> int:
    """The pow-2 device bucket `items` pads into (the shape the kernel
    manifest compiles; tools/shapes bucketing). What a lane records when
    it names no bucket of its own; the attestation firehose names the one
    bucket it dispatches every batch in (`begin_batch(bucket=)`)."""
    n = max(1, int(items))
    return 1 << (n - 1).bit_length()


def _recompile_count() -> "Optional[int]":
    """The shape ledger's post-warmup recompile counter — read only when
    tpu/bls is ALREADY imported (never import jax from the recorder)."""
    mod = sys.modules.get("grandine_tpu.tpu.bls")
    if mod is None:
        return None
    try:
        return int(mod.post_warmup_recompiles())
    except Exception:
        return None


# ---------------------------------------------------------------- records


class BatchRecord:
    """One flight-timeline event. `kind=BATCH` rows carry the full
    per-batch story; CANARY/BREAKER rows reuse the shape (lane="health",
    fault/verdict describing the probe or the entered state) so the
    whole verify plane reads as one ordered sequence."""

    __slots__ = (
        "seq", "t", "kind", "lane", "kernel", "items", "bucket", "fill",
        "queue_wait_s", "device_s", "host_s", "bisect_s", "verdict",
        "fault", "retries", "bisect_depth", "probes", "breaker_state",
        "recompile",
        "slo_miss", "slo_cause", "origin", "note", "devices",
        "quarantined", "brownout", "trace_id", "collect_wait_s",
        "pool_wait_s", "dispatch_wait_s", "settle_wait_s", "settle_s",
        "closed_by", "held_s", "width", "width_min", "width_bucket",
    )

    def __init__(self, kind: str, lane: str) -> None:
        self.seq = 0
        self.t = 0.0
        self.kind = kind
        self.lane = lane
        self.kernel = ""
        self.items = 0
        self.bucket = 0
        self.fill = 0.0
        #: what the SLO tracker calls the queue: from the arrival of the
        #: batch's oldest item to the moment the batch is ready to
        #: dispatch. On the firehose that is collect_wait_s + pool_wait_s
        #: + the prevalidation stage (host work, counted as waiting); on
        #: the scheduler lanes the oldest ticket's enqueue -> flush
        self.queue_wait_s = 0.0
        #: a HOST delta under a device's name: everything between "start
        #: dispatching" and "verdict forced" that the emission sites
        #: note. On the firehose: the whole of `_device_dispatch` (G2
        #: decompression, registry sync, packing, upload, the dispatch
        #: call) plus `settle_s`; NOT dispatch_wait_s or settle_wait_s.
        #: The device's own time is the profiler's timeline
        #: (`verify_device_seconds_total`)
        self.device_s = 0.0
        self.host_s = 0.0
        self.bisect_s = 0.0
        #: the batch's spans in the Tracer ring carry this id (0: no
        #: tracer); a flight row finds its chain of spans by it
        self.trace_id = 0
        #: the firehose's waits, each also a span under the batch's root
        #: (all `perf_counter` deltas; 0.0 where the path has no such
        #: wait): oldest item's arrival -> the collector pops the batch
        self.collect_wait_s = 0.0
        #: the part of collect_wait_s the batch stood at the collector
        #: PAST its deadline, short of the batch bound, because the
        #: pipeline had no slot for it (0.0: it was not held)
        self.held_s = 0.0
        #: batch popped -> its task starts on a pool thread
        self.pool_wait_s = 0.0
        #: the pool thread blocked on the pipeline's dispatch semaphore
        self.dispatch_wait_s = 0.0
        #: dispatched batch handed over -> the completion thread takes it
        #: (it sits behind its predecessor's settle and feedback)
        self.settle_wait_s = 0.0
        #: forcing the verdict: device remainder + readback
        self.settle_s = 0.0
        #: what closed the firehose batch at the collector: "full" (the
        #: batch bound), "deadline" (its first item's deadline) or "stop";
        #: "" on lanes that form no batches this way
        self.closed_by = ""
        #: the firehose batch's widest and narrowest committee (attesting
        #: indices of an item) and the member bucket its first pass was
        #: DISPATCHED in (the verifier's width floor's; 0: no device call)
        self.width = 0
        self.width_min = 0
        self.width_bucket = 0
        self.verdict: "Optional[bool]" = None
        self.fault: "Optional[str]" = None
        self.retries = 0
        self.bisect_depth = 0
        #: re-checks the batch's descent made (0: the batch passed)
        self.probes = 0
        self.breaker_state = ""
        self.recompile = False
        self.slo_miss = False
        self.slo_cause: "Optional[str]" = None
        self.origin: "Optional[str]" = None
        self.note = ""
        #: mesh width the batch dispatched over (a record FIELD, never a
        #: Prometheus label — per-device label cardinality is forbidden)
        self.devices = 1
        #: True for quarantine-lane batches (suspect-origin traffic
        #: isolated from honest batches — runtime/isolation.py)
        self.quarantined = False
        #: the brownout level (runtime/brownout.py LEVELS) in force when
        #: the record committed — every shed reads its causing level
        #: straight off the timeline
        self.brownout = "normal"

    def total_s(self) -> float:
        return self.queue_wait_s + self.device_s + self.host_s + self.bisect_s

    def as_dict(self) -> dict:
        """JSON-ready row for the debug endpoint / bench summary."""
        return {
            "seq": self.seq,
            "t": round(self.t, 6),
            "kind": self.kind,
            "lane": self.lane,
            "kernel": self.kernel,
            "items": self.items,
            "bucket": self.bucket,
            "fill": round(self.fill, 4),
            "queue_wait_s": round(self.queue_wait_s, 6),
            "device_s": round(self.device_s, 6),
            "host_s": round(self.host_s, 6),
            "bisect_s": round(self.bisect_s, 6),
            "verdict": self.verdict,
            "fault": self.fault,
            "retries": self.retries,
            "bisect_depth": self.bisect_depth,
            "probes": self.probes,
            "breaker_state": self.breaker_state,
            "recompile": self.recompile,
            "slo_miss": self.slo_miss,
            "slo_cause": self.slo_cause,
            "origin": self.origin,
            "note": self.note,
            "devices": self.devices,
            "quarantined": self.quarantined,
            "brownout": self.brownout,
            "trace_id": self.trace_id,
            "collect_wait_s": round(self.collect_wait_s, 6),
            "held_s": round(self.held_s, 6),
            "pool_wait_s": round(self.pool_wait_s, 6),
            "dispatch_wait_s": round(self.dispatch_wait_s, 6),
            "settle_wait_s": round(self.settle_wait_s, 6),
            "settle_s": round(self.settle_s, 6),
            "closed_by": self.closed_by,
            "width": self.width,
            "width_min": self.width_min,
            "width_bucket": self.width_bucket,
        }


class BatchFlight:
    """Mutable per-batch accumulator the emission sites thread through a
    batch's life (dispatch → settle → bisection → deliver); `finish`
    hands the completed record to the recorder exactly once. All methods
    are called from the single thread that owns the batch at that stage,
    so no locking here."""

    __slots__ = ("record", "root", "_recorder", "_done",
                 "_recompiles_before")

    def __init__(self, recorder: "FlightRecorder", record: BatchRecord) -> None:
        self.record = record
        #: the batch's root span (tracing.Span), when its owner traces
        #: the batch's whole life: `finish` ends it where the record
        #: commits, on whichever thread that is
        self.root = None
        self._recorder = recorder
        self._done = False
        self._recompiles_before = _recompile_count()

    def trace(self, root) -> None:
        """Tie the record to its chain of spans."""
        self.root = root
        self.record.trace_id = root.trace_id

    def note_device(self, seconds: float) -> None:
        self.record.device_s += max(0.0, seconds)

    def note_host(self, seconds: float) -> None:
        self.record.host_s += max(0.0, seconds)

    def note_bisect(self, seconds: float, depth: int = 0,
                    probes: int = 0) -> None:
        self.record.bisect_s += max(0.0, seconds)
        self.record.bisect_depth = max(self.record.bisect_depth, int(depth))
        self.record.probes += int(probes)

    def note_retry(self) -> None:
        self.record.retries += 1

    def note_fault(self, kind: str) -> None:
        # first fault wins the record's `fault` field (it names what
        # pushed the batch off the fast path); a secondary fault — a
        # hang on the RETRY of an already-faulted batch — stays visible
        # in the note and in the recorder's aggregate fault counts
        if self.record.fault is None:
            self.record.fault = kind
        else:
            note = self.record.note
            self.record.note = f"{note}+{kind}" if note else f"also_{kind}"
        self._recorder._count_fault(kind)

    def note_origin_failure(self, origin: "Optional[str]") -> None:
        if origin:
            self.record.origin = origin
            self._recorder.note_origin_failure(origin)

    def finish(self, verdict: "Optional[bool]") -> None:
        if self._done:
            return
        self._done = True
        rec = self.record
        rec.verdict = verdict
        if self._recompiles_before is not None:
            after = _recompile_count()
            rec.recompile = bool(after is not None
                                 and after > self._recompiles_before)
        self._recorder._commit(rec)
        if self.root is not None:
            self.root.finish()


class OriginTable:
    """Bounded top-K failing-origin counters with space-saving (Misra-
    Gries) eviction: a NEW origin arriving at capacity replaces the
    minimum-count entry and inherits its count (+1), so the true
    heaviest offenders survive adversarial churn of one-shot origins and
    the table never exceeds `capacity` entries. `error` on a snapshot
    row bounds the inherited over-count."""

    __slots__ = ("capacity", "_counts", "_errors", "_lock")

    def __init__(self, capacity: int = 32) -> None:
        self.capacity = max(1, int(capacity))
        self._counts: "dict[str, int]" = {}
        self._errors: "dict[str, int]" = {}
        self._lock = threading.Lock()

    def note_failure(self, origin: str, count: int = 1) -> None:
        origin = str(origin)
        with self._lock:
            if origin in self._counts:
                self._counts[origin] += count
                return
            if len(self._counts) < self.capacity:
                self._counts[origin] = count
                self._errors[origin] = 0
                return
            victim = min(self._counts, key=self._counts.__getitem__)
            floor = self._counts.pop(victim)
            self._errors.pop(victim, None)
            self._counts[origin] = floor + count
            self._errors[origin] = floor

    def snapshot(self) -> "list[dict]":
        with self._lock:
            rows = [
                {"origin": o, "failures": c, "error": self._errors.get(o, 0)}
                for o, c in self._counts.items()
            ]
        rows.sort(key=lambda r: (-r["failures"], r["origin"]))
        return rows

    def __len__(self) -> int:
        with self._lock:
            return len(self._counts)


# --------------------------------------------------------------- recorder


class FlightRecorder:
    """The bounded flight-timeline ring plus the SLO/fill/origin/duty
    derivations. One recorder per node (runtime/node.py wires the same
    instance into the scheduler, the firehose, the replay pipeline, and
    the health supervisor); components construct a private one when none
    is injected so recording is always-on."""

    def __init__(
        self,
        capacity: int = 4096,
        metrics=None,
        slo_budgets: "Optional[dict]" = None,
        default_budget_s: float = DEFAULT_SLO_BUDGET_S,
        origin_top_k: int = 32,
        clock=time.monotonic,
    ) -> None:
        self.capacity = max(16, int(capacity))
        self.metrics = metrics
        self.clock = clock
        self.slo_budgets = dict(DEFAULT_SLO_BUDGETS)
        if slo_budgets:
            self.slo_budgets.update(
                {str(k): float(v) for k, v in slo_budgets.items()}
            )
        self.default_budget_s = float(default_budget_s)
        self.origins = OriginTable(origin_top_k)
        #: the brownout level stamped on every committed record — poked
        #: by the BrownoutController on each transition (a torn read
        #: only mis-stamps one record's level by one tick)
        self.brownout_level = "normal"
        #: ring storage: preallocated slots, one short-hold lock around
        #: index bumps and duty-cycle accounting — record assembly and
        #: SLO attribution happen outside it
        self._ring: "list[Optional[BatchRecord]]" = [None] * self.capacity
        self._lock = threading.Lock()
        self._seq = 0
        #: duty cycle integral
        self._t0 = self.clock()
        self._inflight = 0
        self._busy_since = 0.0
        self._busy_total = 0.0
        #: running aggregates for summary() (cheap dict bumps, also
        #: under the one lock so snapshots are coherent)
        self._slo_miss: "dict[tuple, int]" = {}
        self._fill_sum: "dict[str, float]" = {}
        self._fill_n: "dict[str, int]" = {}
        self._waste: "dict[str, int]" = {}
        self._batches = 0
        self._faults: "dict[str, int]" = {}

    # ------------------------------------------------------------ batches

    def begin_batch(self, lane: str, kernel: str, items: int,
                    queue_wait_s: float = 0.0,
                    breaker_state: str = "",
                    devices: int = 1,
                    quarantined: bool = False,
                    bucket: "Optional[int]" = None) -> BatchFlight:
        """Open one batch's flight context at dispatch time. Fill/waste
        are derived from the bucket the device actually pads to: the one
        the lane names (`bucket`: the firehose's one batch bucket), else
        the pow-2 bucket of `items`."""
        rec = BatchRecord(BATCH, lane)
        rec.kernel = kernel
        rec.items = int(items)
        rec.bucket = int(bucket) if bucket else bucket_of(items)
        rec.fill = rec.items / rec.bucket if rec.bucket else 0.0
        rec.queue_wait_s = max(0.0, float(queue_wait_s))
        rec.breaker_state = breaker_state
        rec.devices = max(1, int(devices))
        rec.quarantined = bool(quarantined)
        return BatchFlight(self, rec)

    def _slo_cause(self, rec: BatchRecord) -> str:
        """Attribute a miss to its dominant component. Breaker-open
        skips win outright (the batch never had a device chance); a
        device fault's forced host pass charges to "device" (the device
        caused it), bisection time to "bisection"."""
        if rec.breaker_state == "open" and rec.device_s == 0.0:
            return "breaker_open"
        exec_s = rec.device_s + rec.host_s
        if rec.bisect_s > exec_s and rec.bisect_s > rec.queue_wait_s:
            return "bisection"
        if exec_s >= rec.queue_wait_s:
            return "device"
        return "queue_wait"

    def _commit(self, rec: BatchRecord) -> None:
        """Finalize one batch record: SLO attribution, fill/waste
        accounting, metrics, and the ring append."""
        budget = self.slo_budgets.get(rec.lane, self.default_budget_s)
        if rec.total_s() > budget:
            rec.slo_miss = True
            rec.slo_cause = self._slo_cause(rec)
        m = self.metrics
        if m is not None:
            if rec.slo_miss:
                m.verify_slo_miss.inc(rec.lane, rec.slo_cause)
            if rec.kernel:
                m.verify_bucket_fill.observe(rec.kernel, value=rec.fill)
                m.verify_padding_waste.inc(
                    rec.kernel, amount=rec.bucket - rec.items
                )
        waste = rec.bucket - rec.items
        with self._lock:
            self._batches += 1
            if rec.slo_miss:
                key = (rec.lane, rec.slo_cause)
                self._slo_miss[key] = self._slo_miss.get(key, 0) + 1
            # faults already aggregated by note_fault (every noted fault
            # counts, not just the record's primary)
            if rec.kernel:
                self._fill_sum[rec.kernel] = (
                    self._fill_sum.get(rec.kernel, 0.0) + rec.fill
                )
                self._fill_n[rec.kernel] = self._fill_n.get(rec.kernel, 0) + 1
                self._waste[rec.kernel] = (
                    self._waste.get(rec.kernel, 0) + waste
                )
            self._append_locked(rec)

    def _count_fault(self, kind: str) -> None:
        with self._lock:
            self._faults[kind] = self._faults.get(kind, 0) + 1

    # ------------------------------------------------- health-plane events

    def record_canary(self, backend: str, passed: bool,
                      duration_s: float = 0.0,
                      fault: "Optional[str]" = None) -> None:
        """A HALF_OPEN canary probe, in the same timeline as the batches
        whose faults provoked it."""
        rec = BatchRecord(CANARY, "health")
        rec.kernel = backend
        rec.device_s = max(0.0, float(duration_s))
        rec.verdict = bool(passed)
        rec.fault = fault
        rec.note = "probe_pass" if passed else "probe_fail"
        with self._lock:
            if fault is not None:
                self._faults[fault] = self._faults.get(fault, 0) + 1
            self._append_locked(rec)

    def record_breaker(self, backend: str, state: str) -> None:
        """A breaker state transition (entered `state`)."""
        rec = BatchRecord(BREAKER, "health")
        rec.kernel = backend
        rec.breaker_state = state
        rec.note = f"breaker_{state}"
        with self._lock:
            self._append_locked(rec)

    def note_origin_failure(self, origin: str, count: int = 1) -> None:
        self.origins.note_failure(origin, count)

    def record_shed(self, lane: str, items: int, cause: str) -> None:
        """One shed event: jobs that never reached a device dispatch —
        a deadline expiry (`cause="expired"`) or an overload-control
        drop (`cause="brownout"`). The record joins the timeline with
        the brownout level stamped on, so every shed is attributable,
        and feeds the SLO-miss aggregates (the brownout controller's
        own escalation feed) — but not the dispatched-batch count."""
        rec = BatchRecord(BATCH, lane)
        rec.items = int(items)
        rec.verdict = False
        rec.slo_miss = True
        rec.slo_cause = cause if cause in SLO_CAUSES else "brownout"
        rec.note = "shed"
        m = self.metrics
        if m is not None:
            m.verify_slo_miss.inc(rec.lane, rec.slo_cause)
        with self._lock:
            key = (rec.lane, rec.slo_cause)
            self._slo_miss[key] = self._slo_miss.get(key, 0) + 1
            self._append_locked(rec)

    # -------------------------------------------------- duty cycle gauges

    def device_enter(self) -> None:
        """One batch entered the device (dispatch handed off)."""
        now = self.clock()
        with self._lock:
            if self._inflight == 0:
                self._busy_since = now
            self._inflight += 1

    def device_exit(self) -> None:
        """One batch left the device (settle forced)."""
        now = self.clock()
        with self._lock:
            if self._inflight > 0:
                self._inflight -= 1
                if self._inflight == 0:
                    self._busy_total += now - self._busy_since
            duty = self._duty_locked(now)
        if self.metrics is not None:
            self.metrics.verify_device_duty_cycle.set(duty)

    def _duty_locked(self, now: float) -> float:
        elapsed = now - self._t0
        if elapsed <= 0.0:
            return 0.0
        busy = self._busy_total
        if self._inflight > 0:
            busy += now - self._busy_since
        return min(1.0, busy / elapsed)

    def duty_cycle(self) -> float:
        with self._lock:
            return self._duty_locked(self.clock())

    # ----------------------------------------------------------- the ring

    def _append_locked(self, rec: BatchRecord) -> None:
        rec.seq = self._seq
        rec.t = self.clock() - self._t0
        rec.brownout = self.brownout_level
        self._ring[self._seq % self.capacity] = rec
        self._seq += 1

    def snapshot(self, lane: "Optional[str]" = None,
                 n: "Optional[int]" = None,
                 kind: "Optional[str]" = None) -> "list[BatchRecord]":
        """The newest records, oldest-first, optionally filtered by lane
        and/or kind and truncated to the newest `n` AFTER filtering.
        Safe against concurrent recording: the slot list is copied under
        the lock; records are immutable once committed."""
        with self._lock:
            seq = self._seq
            ring = list(self._ring)
        count = min(seq, self.capacity)
        out: "list[BatchRecord]" = []
        for s in range(seq - count, seq):
            rec = ring[s % self.capacity]
            # a slot being overwritten mid-copy shows a newer seq; skip
            # anything that does not match its expected position
            if rec is None or rec.seq != s:
                continue
            if lane is not None and rec.lane != lane:
                continue
            if kind is not None and rec.kind != kind:
                continue
            out.append(rec)
        if n is not None:
            n = max(0, int(n))
            out = out[-n:] if n else []
        return out

    # ------------------------------------------------------------ summary

    def slo_misses(self) -> "dict[str, dict[str, int]]":
        """{lane: {cause: count}} of recorded SLO misses."""
        with self._lock:
            items = list(self._slo_miss.items())
        out: "dict[str, dict[str, int]]" = {}
        for (lane, cause), count in items:
            out.setdefault(lane, {})[cause] = count
        return out

    def summary(self) -> dict:
        """The bench JSON-line payload: fill ratio and padding waste per
        kernel, duty cycle, SLO misses by lane and cause,
        fault counts, and the origin top-K."""
        now = self.clock()
        with self._lock:
            batches = self._batches
            recorded = min(self._seq, self.capacity)
            total = self._seq
            fills = {
                k: self._fill_sum[k] / n
                for k, n in self._fill_n.items() if n
            }
            waste = dict(self._waste)
            faults = dict(self._faults)
            duty = self._duty_locked(now)
        return {
            "batches": batches,
            "records": recorded,
            "records_total": total,
            "fill_ratio": {k: round(v, 4) for k, v in sorted(fills.items())},
            "padding_waste": dict(sorted(waste.items())),
            "device_duty_cycle": round(duty, 4),
            "slo_miss": self.slo_misses(),
            "faults": dict(sorted(faults.items())),
            "failing_origins": self.origins.snapshot()[:8],
        }


__all__ = [
    "BATCH",
    "BREAKER",
    "CANARY",
    "BatchFlight",
    "BatchRecord",
    "DEFAULT_SLO_BUDGETS",
    "FlightRecorder",
    "OriginTable",
    "RECORD_KINDS",
    "SLO_CAUSES",
    "bucket_of",
]
