"""Metrics registry — reference: prometheus_metrics crate (the one
`Metrics` struct of ~100 histograms/gauges/counters shared via
Option<Arc<Metrics>> through every constructor, prometheus_metrics/src/
metrics.rs:14-120) plus the `metrics` crate's scrape server.

Dependency-free: counters/gauges/histograms with Prometheus text
exposition. The scrape endpoint is served by the HTTP API layer.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Optional, Sequence


class Counter:
    __slots__ = ("name", "help", "_value", "_lock")

    def __init__(self, name: str, help_: str = "") -> None:
        self.name = name
        self.help = help_
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def expose(self) -> str:
        with self._lock:
            value = self._value
        return (
            f"# HELP {self.name} {self.help}\n"
            f"# TYPE {self.name} counter\n"
            f"{self.name} {value}\n"
        )


class Gauge(Counter):
    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    def expose(self) -> str:
        with self._lock:
            value = self._value
        return (
            f"# HELP {self.name} {self.help}\n"
            f"# TYPE {self.name} gauge\n"
            f"{self.name} {value}\n"
        )


_DEFAULT_BUCKETS = (
    0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0
)


class Histogram:
    __slots__ = ("name", "help", "buckets", "_counts", "_sum", "_count", "_lock")

    def __init__(self, name: str, help_: str = "",
                 buckets: "Sequence[float]" = _DEFAULT_BUCKETS) -> None:
        self.name = name
        self.help = help_
        self.buckets = tuple(buckets)
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            self._sum += value
            self._count += 1
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    self._counts[i] += 1
                    return
            self._counts[-1] += 1

    def time(self) -> "_Timer":
        return _Timer(self)

    def expose(self) -> str:
        with self._lock:
            counts = list(self._counts)
            total, count = self._sum, self._count
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ]
        cumulative = 0
        for bound, bucket in zip(self.buckets, counts):
            cumulative += bucket
            out.append(f'{self.name}_bucket{{le="{bound}"}} {cumulative}')
        cumulative += counts[-1]
        out.append(f'{self.name}_bucket{{le="+Inf"}} {cumulative}')
        out.append(f"{self.name}_sum {total}")
        out.append(f"{self.name}_count {count}")
        return "\n".join(out) + "\n"


class _Timer:
    def __init__(self, hist) -> None:
        self._hist = hist

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *_):
        self._hist.observe(time.perf_counter() - self._t0)


# --- labeled families -------------------------------------------------------
#
# The reference client leans on prometheus's labeled vectors
# (IntCounterVec / HistogramVec) for anything with a dimension — gossip
# topic, req/resp protocol, kernel variant. Children are cached per
# label-value tuple so the hot path is one dict lookup, and exposition
# emits one HELP/TYPE header per family with `{label="value"}` samples.


def _escape_label_value(value: str) -> str:
    """Prometheus text-format escaping for label values."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def _label_str(labelnames, values) -> str:
    return ",".join(
        f'{n}="{_escape_label_value(v)}"'
        for n, v in zip(labelnames, values)
    )


class _LabeledFamily:
    """Shared child-caching machinery for labeled counters/gauges/
    histograms. `labels(*values)` returns (creating on first use) the
    child for that label-value tuple; children are never evicted, so
    label cardinality must stay bounded by construction (topic names,
    protocol ids, kernel names — not peer ids).

    `defaults` maps TRAILING label names to fill-in values so a family
    can grow a dimension without breaking existing call sites: after
    widening `verify_stage_seconds` from ("stage",) to ("stage", "lane",
    "op") with defaults={"lane": "attestation", "op": ""},
    `labels("execute")` keeps resolving to the attestation series."""

    def __init__(self, name: str, help_: str,
                 labelnames: "Sequence[str]",
                 defaults: "Optional[dict]" = None) -> None:
        if not labelnames:
            raise ValueError(f"{name}: labeled family needs >= 1 label")
        self.name = name
        self.help = help_
        self.labelnames = tuple(str(n) for n in labelnames)
        self.defaults = {str(k): str(v) for k, v in (defaults or {}).items()}
        for k in self.defaults:
            if k not in self.labelnames:
                raise ValueError(f"{name}: default for unknown label {k!r}")
        self._children: dict = {}
        self._lock = threading.Lock()

    def _make_child(self):
        raise NotImplementedError

    def labels(self, *values, **kwargs):
        if kwargs:
            if values:
                raise ValueError("pass label values positionally or by name")
            try:
                values = tuple(
                    kwargs[n] if n in kwargs else self.defaults[n]
                    for n in self.labelnames
                )
            except KeyError as e:
                raise ValueError(f"{self.name}: missing label {e}") from e
        values = tuple(str(v) for v in values)
        if len(values) < len(self.labelnames):
            tail = self.labelnames[len(values):]
            if all(n in self.defaults for n in tail):
                values = values + tuple(self.defaults[n] for n in tail)
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected {len(self.labelnames)} label "
                f"values, got {len(values)}"
            )
        # single-lock lookup (no bare double-checked read): an uncontended
        # Lock acquire is cheap enough for the inc() hot path, and every
        # thread then agrees on one child per label tuple
        with self._lock:
            child = self._children.get(values)
            if child is None:
                child = self._make_child()
                self._children[values] = child
        return child

    def children(self) -> dict:
        with self._lock:
            return dict(self._children)

    def _sorted_children(self):
        with self._lock:
            return sorted(self._children.items())


class LabeledCounter(_LabeledFamily):
    _TYPE = "counter"

    class Child:
        __slots__ = ("_value", "_lock")

        def __init__(self) -> None:
            self._value = 0.0
            self._lock = threading.Lock()

        def inc(self, amount: float = 1.0) -> None:
            with self._lock:
                self._value += amount

        @property
        def value(self) -> float:
            with self._lock:
                return self._value

    def _make_child(self):
        return self.Child()

    def inc(self, *values, amount: float = 1.0) -> None:
        self.labels(*values).inc(amount)

    def value(self, *values) -> float:
        return self.labels(*values).value

    def expose(self) -> str:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} {self._TYPE}",
        ]
        for values, child in self._sorted_children():
            ls = _label_str(self.labelnames, values)
            out.append(f"{self.name}{{{ls}}} {child.value}")
        return "\n".join(out) + "\n"


class LabeledGauge(LabeledCounter):
    _TYPE = "gauge"

    class Child(LabeledCounter.Child):
        __slots__ = ()

        def set(self, value: float) -> None:
            with self._lock:
                self._value = float(value)

        def dec(self, amount: float = 1.0) -> None:
            self.inc(-amount)

    def set(self, *values, value: float) -> None:
        self.labels(*values).set(value)


class LabeledHistogram(_LabeledFamily):
    def __init__(self, name: str, help_: str,
                 labelnames: "Sequence[str]",
                 buckets: "Sequence[float]" = _DEFAULT_BUCKETS,
                 defaults: "Optional[dict]" = None) -> None:
        super().__init__(name, help_, labelnames, defaults=defaults)
        self.buckets = tuple(buckets)

    class Child:
        __slots__ = ("buckets", "_counts", "_sum", "_count", "_lock")

        def __init__(self, buckets) -> None:
            self.buckets = buckets
            self._counts = [0] * (len(buckets) + 1)
            self._sum = 0.0
            self._count = 0
            self._lock = threading.Lock()

        def observe(self, value: float) -> None:
            with self._lock:
                self._sum += value
                self._count += 1
                for i, bound in enumerate(self.buckets):
                    if value <= bound:
                        self._counts[i] += 1
                        return
                self._counts[-1] += 1

        def time(self) -> "_Timer":
            return _Timer(self)

        @property
        def count(self) -> int:
            with self._lock:
                return self._count

        @property
        def sum(self) -> float:
            with self._lock:
                return self._sum

        def snapshot(self) -> "tuple[list, float, int]":
            """(bucket counts, sum, count) read consistently under the
            child's lock — the scrape path's view."""
            with self._lock:
                return list(self._counts), self._sum, self._count

    def _make_child(self):
        return self.Child(self.buckets)

    def observe(self, *values, value: float) -> None:
        self.labels(*values).observe(value)

    def time(self, *values) -> "_Timer":
        return self.labels(*values).time()

    def expose(self) -> str:
        out = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ]
        for values, child in self._sorted_children():
            base = _label_str(self.labelnames, values)
            counts, total, count = child.snapshot()
            cumulative = 0
            for bound, bucket in zip(self.buckets, counts):
                cumulative += bucket
                out.append(
                    f'{self.name}_bucket{{{base},le="{bound}"}} {cumulative}'
                )
            cumulative += counts[-1]
            out.append(f'{self.name}_bucket{{{base},le="+Inf"}} {cumulative}')
            out.append(f"{self.name}_sum{{{base}}} {total}")
            out.append(f"{self.name}_count{{{base}}} {count}")
        return "\n".join(out) + "\n"


class Metrics:
    """The shared metrics struct: the framework's counterpart of
    prometheus_metrics::Metrics, passed as Optional through constructors."""

    def __init__(self) -> None:
        # fork choice / mutator (metrics.rs:49-53,106)
        self.fc_blocks_applied = Counter(
            "fc_blocks_applied_total", "blocks applied to the store")
        self.fc_head_changes = Counter(
            "fc_head_changes_total", "head switches")
        # attestation verifier (metrics.rs:58-60)
        self.att_batches = Counter(
            "attestation_verifier_batches_total", "verified gossip batches")
        self.att_batch_times = Histogram(
            "attestation_verifier_batch_seconds", "batch verify duration")
        self.att_fallbacks = Counter(
            "attestation_verifier_fallbacks_total",
            "batches degraded to singular verification")
        # how the collector closed each batch: at the batch bound, at
        # the deadline after its first item, or at stop
        self.att_batches_closed = LabeledCounter(
            "attestation_batches_closed_total",
            "gossip batches formed, by what closed them",
            ("by",),
        )
        # a batch that met its deadline short of the bound and found the
        # pipeline full (pipeline_depth calls + one batch preparing): it
        # waits at the collector for a slot, counted when the hold begins
        self.att_batches_held = Counter(
            "attestation_batches_held_total",
            "short gossip batches held at the collector for a pipeline slot")
        # a first pass of any size runs in the node's one batch bucket:
        # items / slots is what padding a partial batch costs (twins of
        # the probe pair below)
        self.att_first_pass_items = Counter(
            "attestation_first_pass_items_total",
            "real items in first-pass device calls")
        self.att_first_pass_slots = Counter(
            "attestation_first_pass_slots_total",
            "padded batch slots of those calls")
        # the member axis has one bucket a node too, the width floor's
        # (the widest committee dispatched so far): members / member slots
        # is how much of the padded member matrix a first pass fills
        self.att_first_pass_members = Counter(
            "attestation_first_pass_members_total",
            "real committee members in first-pass device calls")
        self.att_first_pass_member_slots = Counter(
            "attestation_first_pass_member_slots_total",
            "padded member slots of those calls (batch slots x width bucket)")
        self.att_width_floor_raised = Counter(
            "attestation_width_floor_raised_total",
            "times the firehose's width floor moved to a higher member bucket")
        self.att_width_bucket = Gauge(
            "attestation_width_bucket",
            "member bucket the firehose's device calls run in")
        self.att_mixed_batches = Counter(
            "attestation_mixed_batches_total",
            "gossip batches whose narrowest and widest item fall in "
            "different member buckets")
        # the slasher feed's own thread takes every delivered batch that
        # waits for it in one slasher call: batches / calls is how far it
        # coalesces (1.0 where nothing queues); a hand-over that found its
        # bounded queue full waited for room
        self.att_slasher_feed_calls = Counter(
            "attestation_slasher_feed_calls_total",
            "slasher calls the firehose's feeder thread made")
        self.att_slasher_feed_batches = Counter(
            "attestation_slasher_feed_batches_total",
            "delivered batches those calls fed")
        self.att_slasher_feed_blocked = Counter(
            "attestation_slasher_feed_blocked_total",
            "hand-overs to the feeder that found its queue full")
        # prevalidation decompresses no member key: a check off the
        # resident registry's path does, for its own items (upload: no
        # registry in sync; host: the host anchor)
        self.att_member_keys_decompressed = LabeledCounter(
            "attestation_member_keys_decompressed_total",
            "member public keys the firehose decompressed on the host for "
            "checks off the registry path",
            ("path",),
        )
        # the descent over a failed batch (_isolate): its probes are
        # calls of the batch's own executable, padded to the batch's
        # bucket — items / slots is what that padding costs
        self.att_isolation_probes = Counter(
            "attestation_isolation_probes_total",
            "device calls made by descents over failed batches")
        self.att_isolation_probe_items = Counter(
            "attestation_isolation_probe_items_total",
            "real items in those calls")
        self.att_isolation_probe_slots = Counter(
            "attestation_isolation_probe_slots_total",
            "padded batch slots of those calls")
        self.att_isolated_batches = Counter(
            "attestation_isolated_batches_total",
            "failed batches whose descent named at least one bad item")
        # how often the descent's schedule engages: a suspect set taken
        # on without a probe of its own, and the one call that clears
        # every half the walk passed over
        self.att_isolation_inferred = Counter(
            "attestation_isolation_inferred_total",
            "suspect sets a descent entered without a probe of their own")
        self.att_isolation_union_probes = LabeledCounter(
            "attestation_isolation_union_probes_total",
            "probes of all of a descent's deferred halves at once",
            ("verdict",),
        )
        # device plane
        self.device_batch_sigs = Counter(
            "device_batch_signatures_total",
            "signatures shipped to the accelerator")
        # which decoder the firehose's g2_decompress stage ran: "native"
        # (one call a batch, no GIL held) where the runtime library
        # loaded, else "python" (crypto.bls.g2_from_bytes an item)
        self.signature_decompress_items = LabeledCounter(
            "signature_decompress_items_total",
            "signatures handed to the batch decoder, by the path it took",
            ("path",),
        )
        self.block_processing_times = Histogram(
            "block_processing_seconds", "state-transition duration")
        self.head_slot = Gauge("head_slot", "current head slot")
        self.finalized_epoch = Gauge("finalized_epoch", "finalized epoch")
        # system stats (the reference's metrics SERVICE collects these
        # via sysinfo; here straight from /proc, dependency-free)
        self.process_resident_memory_bytes = Gauge(
            "process_resident_memory_bytes", "resident set size")
        self.process_cpu_seconds_total = Gauge(
            "process_cpu_seconds_total", "user+system CPU time")
        self.process_open_fds = Gauge(
            "process_open_fds", "open file descriptors")
        self.process_start_time_seconds = Gauge(
            "process_start_time_seconds", "process start, unix time")
        self.data_dir_bytes = Gauge(
            "grandine_data_dir_bytes", "on-disk size of the data dir")
        # gossip boundary (labeled per topic kind: the reference's
        # gossipsub acceptance vectors)
        self.gossip_messages = LabeledCounter(
            "gossip_messages_total",
            "gossip messages by topic kind and validation result",
            ("topic", "result"),
        )
        # req/resp boundary: requests served per protocol
        self.rpc_requests = LabeledCounter(
            "rpc_requests_total",
            "req/resp requests served, by protocol",
            ("protocol",),
        )
        # device plane, per kernel variant
        self.device_kernel_calls = LabeledCounter(
            "device_kernel_calls_total",
            "accelerator kernel dispatches, by kernel variant",
            ("kernel",),
        )
        self.device_kernel_sigs = LabeledCounter(
            "device_kernel_signatures_total",
            "signatures processed per kernel variant",
            ("kernel",),
        )
        # host→device transfer accounting, per kernel variant: the basis
        # of the no-per-batch-pubkey-upload guard (the lint rule
        # tools/lint/rules/no_per_batch_upload.py) — registry uploads land
        # under kernel="pubkey_registry", per-batch uploads under the
        # dispatching kernel's name
        self.device_upload_bytes = LabeledCounter(
            "device_upload_bytes_total",
            "host to device bytes uploaded, by kernel variant",
            ("kernel",),
        )
        # device-resident pubkey registry (tpu/registry.py)
        self.pubkey_registry_size = Gauge(
            "pubkey_registry_size",
            "validator pubkeys resident on the accelerator")
        self.pubkey_registry_events = LabeledCounter(
            "pubkey_registry_events_total",
            "registry lifecycle events "
            "(hit/miss/append/refresh/invalidate)",
            ("event",),
        )
        # bounded host-side device-point caches (hash-to-curve, …)
        self.device_cache_size = LabeledGauge(
            "device_cache_size",
            "entries held in bounded device-point caches, by cache",
            ("cache",),
        )
        self.device_cache_events = LabeledCounter(
            "device_cache_events_total",
            "cache lookups and evictions, by cache and event "
            "(hit/miss/evict)",
            ("cache", "event"),
        )
        # two-deep verify dispatch queue occupancy (0..2): batches
        # dispatched to the device whose readback has not completed
        self.verify_pipeline_depth = Gauge(
            "verify_pipeline_depth",
            "device verify batches in flight (dispatched, not settled)")
        # verify-plane stage attribution: host_prep / upload_bytes /
        # compile / execute / readback / fallback, split by lane since
        # the verify scheduler shares the device plane across object
        # kinds. lane defaults to "attestation" so pre-lane dashboards
        # and call sites keep resolving to the same series. Finer low
        # end than the defaults: host prep for a 64-att batch is
        # ~100 µs.
        # `op` (closed set: tracing.STAGE_OPS) splits a stage that
        # several call sites feed (host_prep: prevalidate / g2_decompress
        # / registry_sync / pack_*; feedback: deliver / slasher_feed); ""
        # for a stage of one part. A stage's time is the SUM over `op`.
        self.verify_stage_seconds = LabeledHistogram(
            "verify_stage_seconds",
            "batch-verify latency, by pipeline stage, lane and part (op)",
            ("stage", "lane", "op"),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
            ),
            defaults={"lane": "attestation", "op": ""},
        )
        # the same stages' CPU seconds on their own thread (`thread_time`):
        # wall minus CPU is time off the CPU, waiting for the GIL, a lock
        # or I/O
        self.verify_stage_cpu_seconds = LabeledCounter(
            "verify_stage_cpu_seconds_total",
            "CPU seconds of the stage's thread inside the stage, by "
            "pipeline stage, lane and part (op)",
            ("stage", "lane", "op"),
            defaults={"lane": "attestation", "op": ""},
        )
        # the compile scope by phase (tpu/compile_scope.py): what JAX
        # itself reports (jax.monitoring) of the time the program spent
        # inside `compiling()`. Process-wide counts, brought up to date
        # at each scrape (`expose`), so a fresh Metrics shows all of it.
        self.verify_compile_phase_seconds = LabeledCounter(
            "verify_compile_phase_seconds_total",
            "seconds inside the compile scope by JAX phase: trace "
            "(Python to jaxpr), lower (jaxpr to MLIR), backend (XLA "
            "compile OR persistent-cache load), cache_retrieval (the "
            "cache read alone, part of backend)",
            ("phase",))
        self.verify_compile_cache = LabeledCounter(
            "verify_compile_cache_total",
            "persistent compilation cache lookups inside the compile "
            "scope, by result (hit / miss)",
            ("result",))
        # verify scheduler (runtime/verify_scheduler.py): per-lane
        # queue occupancy, flushed batches by outcome, enqueue→flush
        # wait, and overload sheds (low lanes drop oldest-first rather
        # than stall block import)
        self.verify_lane_depth = LabeledGauge(
            "verify_lane_depth",
            "verify-scheduler jobs queued, by lane",
            ("lane",),
        )
        self.verify_lane_batches = LabeledCounter(
            "verify_lane_batches_total",
            "verify-scheduler batches flushed, by lane and result "
            "(ok/invalid/degraded)",
            ("lane", "result"),
        )
        self.verify_lane_wait_seconds = LabeledHistogram(
            "verify_lane_wait_seconds",
            "enqueue-to-flush wait of verify-scheduler jobs, by lane",
            ("lane",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
            ),
        )
        self.verify_lane_dropped = LabeledCounter(
            "verify_lane_dropped_total",
            "verify-scheduler jobs shed under overload, by lane",
            ("lane",),
        )
        # device signing plane (runtime/sign_plane.py): per-lane queue
        # occupancy, flushed batches by outcome (device = released
        # through the gate / degraded = gate or device fault re-signed
        # on the host anchor / host = breaker-open host signing),
        # enqueue→release wait, release-gate latency, and slashing-
        # interlock refusals. Labels are CLOSED sets — lane names and
        # refusal reasons are fixed enums, never per-key values.
        self.sign_lane_depth = LabeledGauge(
            "sign_lane_depth",
            "signing-plane requests queued, by lane",
            ("lane",),
        )
        self.sign_lane_batches = LabeledCounter(
            "sign_lane_batches_total",
            "signing-plane batches released, by lane and result "
            "(device/degraded/host)",
            ("lane", "result"),
        )
        self.sign_lane_wait_seconds = LabeledHistogram(
            "sign_lane_wait_seconds",
            "enqueue-to-release wait of signing-plane requests, by lane",
            ("lane",),
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
            ),
        )
        # sheds/drops reuse verify_lane_dropped_total (the ONE drop
        # family — drop-counter-reuse lint): sign lanes carry their own
        # label values in it
        self.sign_release_gate_seconds = Histogram(
            "sign_release_gate_seconds",
            "release-gate batch-verify latency per signing batch",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        self.sign_refused = LabeledCounter(
            "sign_refused_total",
            "signing requests refused by the slashing interlock before "
            "reaching a kernel, by reason "
            "(block_regression/attestation_regression)",
            ("reason",),
        )
        self.sign_pipeline_depth = Gauge(
            "sign_pipeline_depth",
            "signing batches in flight (dispatched, not released)",
        )
        # device health supervisor (runtime/health.py): breaker state
        # machine, canary re-promotion probes, settle watchdog, bounded
        # transient retries, and daemon-loop crash containment
        self.verify_breaker_state = LabeledGauge(
            "verify_breaker_state",
            "device circuit-breaker state (0=closed 1=open 2=half_open), "
            "by backend",
            ("backend",),
        )
        self.verify_breaker_transitions = LabeledCounter(
            "verify_breaker_transitions_total",
            "device circuit-breaker state transitions, by backend and "
            "entered state",
            ("backend", "state"),
        )
        self.verify_breaker_faults = LabeledCounter(
            "verify_breaker_faults_total",
            "faults filed with the device circuit breaker, by backend "
            "and kind (dispatch/settle/watchdog/verdict)",
            ("backend", "kind"),
        )
        self.verify_canary_probes = LabeledCounter(
            "verify_canary_probes_total",
            "HALF_OPEN canary probe batches, by backend and result "
            "(pass/fail)",
            ("backend", "result"),
        )
        self.verify_watchdog_fired = LabeledCounter(
            "verify_watchdog_fired_total",
            "device settles abandoned by the watchdog deadline, by lane",
            ("lane",),
        )
        self.verify_retry = LabeledCounter(
            "verify_retry_total",
            "bounded transient re-dispatches of a faulted device batch, "
            "by lane",
            ("lane",),
        )
        self.el_retries = Counter(
            "el_retry_total",
            "execution-engine call retries (capped exponential backoff "
            "with jitter)",
        )
        self.daemon_loop_failures = LabeledCounter(
            "daemon_loop_failures_total",
            "contained crashes of long-running daemon loops, by thread",
            ("thread",),
        )
        self.verify_recompiles = Counter(
            "verify_recompiles_total",
            "novel kernel shape signatures dispatched AFTER warmup "
            "declared completion — each one is an XLA compile stalling "
            "a live batch; steady state must hold at zero "
            "(tools/shapes manifest)",
        )
        # flight recorder (runtime/flight.py): per-lane SLO misses with
        # a CLOSED cause enum (flight.SLO_CAUSES — the lint rule
        # rejects values outside it), bucket-fill/padding-waste per
        # kernel (multi-chip capacity planning), and the duty-cycle gauge
        # the brownout controller reads. Origins are
        # NEVER labels here — they live only in the bounded flight
        # top-K table.
        self.verify_slo_miss = LabeledCounter(
            "verify_slo_miss_total",
            "verify batches that blew their lane's deadline budget, by "
            "lane and dominant cause (queue_wait/device/bisection/"
            "breaker_open/expired/brownout)",
            ("lane", "cause"),
        )
        self.verify_bucket_fill = LabeledHistogram(
            "verify_bucket_fill_ratio",
            "items over the pow-2 device bucket actually dispatched, "
            "by kernel",
            ("kernel",),
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0),
        )
        self.verify_padding_waste = LabeledCounter(
            "verify_padding_waste_total",
            "padded-out device batch slots (bucket minus items), by "
            "kernel",
            ("kernel",),
        )
        # adversarial isolation plane (runtime/isolation.py): on-device
        # fault localization passes, the quarantine lane, and per-origin
        # admission control. Origin identities are NEVER labels — the
        # `kernel` and `lane` labels here are closed sets; attribution
        # lives in the flight recorder's bounded top-K origin table.
        self.verify_isolation_passes = LabeledCounter(
            "verify_isolation_passes_total",
            "fault-localization passes run against a failed verify "
            "batch, by kernel (rlc_partition/g2_subgroup device passes, "
            "host for degraded host sweeps)",
            ("kernel",),
        )
        self.verify_quarantine_lane_depth = Gauge(
            "verify_quarantine_lane_depth",
            "verify jobs queued in the quarantine lane (suspect-origin "
            "traffic isolated from honest batches)",
        )
        self.verify_quarantine_batches = Counter(
            "verify_quarantine_batches_total",
            "verify batches flushed from the quarantine lane",
        )
        self.verify_admission_rejected = LabeledCounter(
            "verify_admission_rejected_total",
            "verify submissions rejected by per-origin fair-share "
            "admission control, by lane",
            ("lane",),
        )
        # brownout overload-control plane (runtime/brownout.py): the
        # current ladder level, every transition by endpoint pair (the
        # from/to labels are the CLOSED brownout.LEVELS enum — lint-
        # enforced like SLO causes), and deadline-budget expiries by
        # lane (the shed-before-dispatch path)
        self.verify_brownout_level = Gauge(
            "verify_brownout_level",
            "current brownout ladder level as an index into "
            "brownout.LEVELS (0=normal .. 4=critical)",
        )
        self.verify_brownout_transitions = LabeledCounter(
            "verify_brownout_transitions_total",
            "brownout ladder transitions, by from/to level (closed "
            "enum: normal/b1/b2/b3/critical)",
            ("from", "to"),
        )
        self.verify_expired = LabeledCounter(
            "verify_expired_total",
            "tickets shed because their absolute deadline passed "
            "before dispatch (the budget-expiry path), by lane",
            ("lane",),
        )
        self.verify_device_duty_cycle = Gauge(
            "verify_device_duty_cycle",
            "fraction of wall time with at least one verify batch on "
            "the device",
        )
        # device-time profiling plane (runtime/profiler.py): the device
        # timeline's busy seconds by kernel and its idle seconds by what
        # held the next call back, live device bytes by array family, and
        # capture-session churn. Labels are the CLOSED kernel/scheme/
        # cause/family sets — never session ids (lint: metrics-cardinality)
        self.verify_device_seconds = LabeledCounter(
            "verify_device_seconds_total",
            "device busy seconds per kernel and scheme: each call from "
            "max(its dispatch, the previous call's end) to its output "
            "being ready",
            ("kernel", "scheme"),
        )
        self.verify_device_idle_seconds = LabeledCounter(
            "verify_device_idle_seconds_total",
            "device idle seconds, by what held back the call that ended "
            "the idle stretch (closed enum: profiler.IDLE_CAUSES)",
            ("cause",),
        )
        # the interpreter's collections (runtime/profiler.py
        # watch_collections): raised to the observer's totals on expose
        self.process_gc_pause_seconds = LabeledCounter(
            "process_gc_pause_seconds_total",
            "seconds the interpreter spent in garbage collections, by "
            "generation",
            ("generation",),
        )
        self.process_gc_collections = LabeledCounter(
            "process_gc_collections_total",
            "garbage collections of the interpreter, by generation",
            ("generation",),
        )
        self.verify_device_hbm_bytes = LabeledGauge(
            "verify_device_hbm_bytes",
            "live device bytes by array family (jax.live_arrays "
            "snapshot, taken at session close or on demand)",
            ("family",),
        )
        self.verify_profile_sessions = Counter(
            "verify_profile_sessions_total",
            "profiler capture sessions started",
        )
        # bulk replay pipeline (runtime/replay.py): whole-window wall
        # time (transition+collect through settle), cross-block
        # signature sets and blocks verified, and how many windows are
        # in flight (dispatched, not settled — 0..pipeline_depth)
        self.replay_window_seconds = Histogram(
            "replay_window_seconds",
            "bulk replay window wall time, transition through settle",
            buckets=(
                0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
                25.0, 60.0,
            ),
        )
        self.replay_sigsets = Counter(
            "replay_sigsets_total",
            "signature sets verified by the bulk replay pipeline",
        )
        self.replay_blocks = Counter(
            "replay_blocks_total",
            "blocks whose window batch settled valid in the bulk "
            "replay pipeline",
        )
        self.replay_pipeline_depth = Gauge(
            "replay_pipeline_depth",
            "replay windows in flight (dispatched, not settled)",
        )
        # slasher span plane (slasher.py): bounded LRU chunk-cache
        # traffic, batched span-update latency, and attesting indices
        # folded into the span store — the keep-up numerator the
        # --mainnet soak gates against the derived attestation arrival
        # rate. The event label is a closed set.
        self.slasher_chunk_cache_events = LabeledCounter(
            "slasher_chunk_cache_events_total",
            "slasher span-chunk cache lookups and evictions, by event "
            "(hit/miss/evict)",
            ("event",),
        )
        self.slasher_chunk_cache_size = Gauge(
            "slasher_chunk_cache_size",
            "span chunks held in the slasher's bounded LRU cache",
        )
        self.slasher_span_update_seconds = Histogram(
            "slasher_span_update_seconds",
            "batched slasher span-update duration, per aggregate or "
            "bulk window",
            buckets=(
                0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
            ),
        )
        self.slasher_span_indices = Counter(
            "slasher_span_indices_total",
            "attesting indices folded into the slasher span store",
        )
        # one storage transaction per slasher call: commits count the
        # `put` / `put_batch` calls the slasher issues, reads say how
        # many record lookups the call's own write set served. The
        # source label is a closed set.
        self.slasher_storage_commits = Counter(
            "slasher_storage_commits_total",
            "storage transactions (put / put_batch) the slasher issued",
        )
        self.slasher_record_reads = LabeledCounter(
            "slasher_record_reads_total",
            "slasher record lookups, by source (write_set/db)",
            ("source",),
        )
        # pubkey registry memory accounting (tpu/registry.py): the
        # mainnet-capacity audit's observables — allocated vs occupied
        # rows, host-mirror footprint, and device bytes total/per shard
        self.pubkey_registry_capacity = Gauge(
            "pubkey_registry_capacity",
            "allocated pubkey-registry rows (pow-2 device capacity)",
        )
        self.pubkey_registry_host_bytes = Gauge(
            "pubkey_registry_host_bytes",
            "host-mirror bytes held by the pubkey registry",
        )
        self.pubkey_registry_device_bytes = Gauge(
            "pubkey_registry_device_bytes",
            "device bytes held by the pubkey registry across all shards",
        )
        self.pubkey_registry_shard_bytes = Gauge(
            "pubkey_registry_shard_bytes",
            "device bytes per mesh shard in the pubkey registry",
        )

    def collect_system_stats(self, data_dir: "str | None" = None) -> None:
        """Refresh the /proc-sourced gauges (metrics/src/service.rs
        system-stats collection). Called from the /metrics handler so
        every scrape sees fresh values; all reads are best-effort."""
        import os

        try:
            with open("/proc/self/statm") as f:
                rss_pages = int(f.read().split()[1])
            self.process_resident_memory_bytes.set(
                rss_pages * os.sysconf("SC_PAGE_SIZE")
            )
        except (OSError, ValueError, IndexError):
            pass
        try:
            tck = os.sysconf("SC_CLK_TCK")
            with open("/proc/self/stat") as f:
                parts = f.read().rsplit(")", 1)[1].split()
            utime, stime = int(parts[11]), int(parts[12])
            self.process_cpu_seconds_total.set((utime + stime) / tck)
            with open("/proc/uptime") as f:
                uptime = float(f.read().split()[0])
            starttime = int(parts[19]) / tck
            self.process_start_time_seconds.set(
                time.time() - uptime + starttime
            )
        except (OSError, ValueError, IndexError):
            pass
        try:
            self.process_open_fds.set(len(os.listdir("/proc/self/fd")))
        except OSError:
            pass
        if data_dir:
            # the recursive walk is O(files); refresh at most once a
            # minute so Prometheus scrape latency stays flat as the DB
            # grows
            now = time.monotonic()
            if now - getattr(self, "_data_dir_scanned", 0.0) >= 60.0:
                self._data_dir_scanned = now
                try:
                    total = 0
                    for root, _dirs, files in os.walk(data_dir):
                        for name in files:
                            try:
                                total += os.path.getsize(
                                    os.path.join(root, name)
                                )
                            except OSError:
                                pass
                    self.data_dir_bytes.set(total)
                except OSError:
                    pass

    def all(self):
        return [
            v for v in vars(self).values()
            if isinstance(v, (Counter, Gauge, Histogram, _LabeledFamily))
        ]

    def _sync_compile_scope(self) -> None:
        """Raise the compile-phase counters to the process's totals."""
        from grandine_tpu.tpu import compile_scope

        seconds, lookups = compile_scope.phase_totals()
        for family, totals in (
            (self.verify_compile_phase_seconds, seconds),
            (self.verify_compile_cache, lookups),
        ):
            for label, total in totals.items():
                child = family.labels(label)
                child.inc(total - child.value)

    def _sync_profiler(self) -> None:
        """The device timeline's open idle stretch charged up to now, and
        the collection counters raised to the process's totals, where
        the profiler counts into these metrics (runtime/profiler.py
        `sync_metrics`; nothing when it is not loaded)."""
        mod = sys.modules.get("grandine_tpu.runtime.profiler")
        if mod is not None:
            mod.sync_metrics(self)

    def expose(self) -> str:
        """Prometheus text exposition of every registered metric."""
        self._sync_compile_scope()
        self._sync_profiler()
        return "".join(m.expose() for m in self.all())


class RemoteMetricsService:
    """Periodic push of client stats to a beaconcha.in-style endpoint —
    reference metrics/src/service.rs (METRICS_UPDATE_INTERVAL = 60 s) +
    beaconchain.rs (the MetricsContent JSON shape: a list of
    {version, timestamp, process, ...} entries).

    `post` is an injected callable (url, json_body) → status for tests;
    the default uses urllib. Runs on a daemon thread; failures are
    counted, never raised (losing a stats push must not hurt the node)."""

    INTERVAL_S = 60.0

    def __init__(self, url: str, metrics: "Metrics", controller=None,
                 data_dir: "str | None" = None, post=None) -> None:
        self.url = url
        self.metrics = metrics
        self.controller = controller
        self.data_dir = data_dir
        self.post = post or self._default_post
        self.stats = {"pushes": 0, "failures": 0}
        #: guards `stats` (push thread + direct push_once callers) and
        #: the start()/stop() thread handle
        self._lock = threading.Lock()
        #: stop signal as an Event: set() from any thread, is_set()/wait()
        #: from the push loop — no bare-bool publication
        self._stop = threading.Event()
        self._thread = None

    @staticmethod
    def _default_post(url: str, body: dict) -> int:
        import json as _json
        import urllib.request

        req = urllib.request.Request(
            url,
            data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status

    def snapshot_body(self) -> list:
        """The beaconcha.in client-stats payload (beaconchain.rs
        MetricsContent: one 'beaconnode' entry + one 'system' entry)."""
        self.metrics.collect_system_stats(self.data_dir)

        def g(m):
            v = m.value
            return v() if callable(v) else v
        beaconnode: dict = {
            "version": 1,
            "timestamp": int(time.time() * 1000),
            "process": "beaconnode",
            "cpu_process_seconds_total": g(
                self.metrics.process_cpu_seconds_total
            ),
            "memory_process_bytes": g(
                self.metrics.process_resident_memory_bytes
            ),
        }
        if self.controller is not None:
            snap = self.controller.snapshot()
            beaconnode["sync_beacon_head_slot"] = int(snap.head_state.slot)
            beaconnode["sync_eth2_synced"] = bool(
                snap.slot - int(snap.head_state.slot) <= 1
            )
        system = {
            "version": 1,
            "timestamp": beaconnode["timestamp"],
            "process": "system",
            "disk_beaconchain_bytes_total": g(self.metrics.data_dir_bytes),
            "memory_node_bytes_total": g(
                self.metrics.process_resident_memory_bytes
            ),
        }
        return [beaconnode, system]

    def push_once(self) -> bool:
        try:
            status = self.post(self.url, self.snapshot_body())
            ok = 200 <= int(status) < 300
        except Exception:
            ok = False
        with self._lock:
            self.stats["pushes" if ok else "failures"] += 1
        return ok

    def start(self) -> None:
        import threading

        def loop() -> None:
            # thread ownership: the single "metrics-push" daemon owns
            # this loop; it shares `stats` with direct push_once()
            # callers under _lock and watches the _stop Event
            while not self._stop.is_set():
                # push_once contains its own network errors, but snapshot
                # assembly reads live controller/metrics state — contain
                # every iteration so one bad snapshot can't kill the
                # push thread for the life of the process
                try:
                    self.push_once()
                    self._stop.wait(self.INTERVAL_S)
                except Exception:
                    with self._lock:
                        self.stats["failures"] += 1
                    self._stop.wait(1.0)

        with self._lock:
            if self._thread is not None:
                return  # already running: keep the singleton push loop
            self._thread = threading.Thread(
                target=loop, name="metrics-push", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()


__all__ = [
    "Counter", "Gauge", "Histogram",
    "LabeledCounter", "LabeledGauge", "LabeledHistogram",
    "Metrics", "RemoteMetricsService",
]
