"""Node-wide kernel profiler: the device's own timeline, per-kernel
device time, the interpreter's collections, plus bounded on-demand
capture sessions.

Three planes share this module:

* **The device timeline** (always on) — every dispatch seam (`TpuBlsBackend.
  _run_kernel`, `Ed25519Backend.verify_batch_async`, `KzgDeviceBackend.
  verify_blobs_async`, the kzg MSM tail) counts its dispatches here, and
  `TpuBlsBackend._run_kernel` hands each call's output to `dispatched()`,
  which stamps `enqueued`. One watcher thread takes the calls in dispatch
  order (the order the chip runs them), blocks on each output (off the
  GIL) and stamps `ready`: the call was busy from max(enqueued, previous
  ready) to ready, and the device idle before it for max(0, enqueued -
  previous ready). Busy time feeds `verify_device_seconds_total{kernel,
  scheme}` and one `device` span a call (parented on the dispatching
  thread's span, on the spans' `perf_counter` clock); each idle stretch
  is charged by cause (`IDLE_CAUSES`) to `verify_device_idle_seconds_
  total{cause}`: to the interpreter's collections that overlap it first,
  the rest to the phase of the call that ended it (`dispatch_phases`, set
  by the firehose around its dispatch), or `other`. `verify_device_hbm_
  bytes{family}` snapshots live device bytes by family. No jax import.
* **The interpreter's collections** — one `gc.callbacks` observer a
  process (`watch_collections`, wired where the node wires its profiler):
  `process_gc_pause_seconds_total{generation}`, `process_gc_collections_
  total{generation}`, a `gc` span on the collecting thread (generations 1
  and 2), and a ring of the last pauses for the `gc` idle cause.
* **Capture sessions** — `start()`/`stop()` open at most one session at
  a time; while a session is active every dispatch runs inside a
  `jax.profiler.TraceAnnotation("{scheme}/{kernel}/b{bucket}")` scope
  and every pipeline stage (tracing.stage) inside
  `TraceAnnotation("{lane}/{op or stage}/b{bucket}")`, so the device
  timeline in the resulting perfetto/Chrome trace is keyed by the same
  `(scheme, kernel, bucket)` coordinates the shape ledger uses and the
  host's stages lie beside it on the profiler's clock. Sessions with a `trace_dir` also drive `jax.profiler.
  start_trace`/`stop_trace`; finished sessions land in a bounded ring
  of the last K. `GET /eth/v1/debug/grandine/profile` serves the
  summary and the start/stop control (http_api/routing.py).

Entering/leaving a capture session MUST NOT perturb the shape ledger or
the recompile guarantees: annotation scopes wrap the already-jitted
callable invocation — they never touch tracing-time state, so
`post_warmup_recompiles()` stays 0 across a mid-soak toggle
(tests/test_profiler.py proves it).

The `KERNEL_SCHEMES` table below is the annotation registry: every
dispatch name in the shapes manifest MUST have an entry — enforced
statically by the `profiler-scope` check in tools/shapes.

Import discipline: stdlib only at module scope. jax is reached through
`sys.modules` on the always-on paths (never imported — a host-only node
must not pay the import) and imported lazily only inside a capture
session.
"""

from __future__ import annotations

import contextlib
import gc
import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Optional

#: the CLOSED cause set on verify_device_idle_seconds_total{cause} (the
#: metrics-cardinality lint rule reads this tuple): what held back the
#: call that ended an idle stretch. The firehose's phases of a first pass
#: in order — no item had arrived (`traffic`), the items queued and the
#: collector accumulated (`collect`), the formed batch waited at the
#: collector for a slot: a short one for the pipeline, any for the bound
#: on batches in flight, whose pool threads wait for the pipeline's
#: settles (`hold`), the pool had no thread for it (`pool_wait`),
#: `prevalidate`, then decompression, registry sync, packing and upload
#: up to the dispatch (`host_prep`) — a probe of a failed batch's descent (`descent`), a collection of the
#: interpreter (`gc`, charged first) and whatever no phase covers: a call
#: dispatched with no phases, or a stretch still open when the counters
#: are read (`other`)
IDLE_CAUSES = ("traffic", "collect", "hold", "pool_wait", "prevalidate",
               "host_prep", "descent", "gc", "other")

#: closed scheme-label set for verify_device_seconds_total{scheme} —
#: the tpu/schemes.py registry names plus the slasher span plane and
#: the catch-all (metrics-cardinality: no open-ended label values)
SCHEMES = ("bls", "ed25519", "blob_kzg", "slasher", "other")

#: the annotation registry: dispatch name → scheme label. Covers the
#: shapes-manifest dispatch universe (every `contract` row) plus the
#: flight-record kernel labels the runtime stamps on batches
#: (scheme.kernel_label values, the replay window kernels, the host
#: twin). The tools/shapes `profiler-scope` check asserts statically
#: that no manifest dispatch name is missing here.
KERNEL_SCHEMES = {
    # tpu/bls.py jit entry points (TpuBlsBackend ASYNC_SEAM + sync)
    "agg_fast_verify_msm": "bls",
    "agg_fast_verify_msm_idx": "bls",
    "agg_fast_verify_msm_comp": "bls",
    "agg_fast_verify_msm_idx_comp": "bls",
    "multi_verify_msm_comp": "bls",
    "g1_decompress": "bls",
    "batch_sign": "bls",
    "g2_aggregate": "bls",
    "g1_aggregate": "bls",
    "g2_subgroup_check": "bls",
    "grouped_multi_verify_msm": "bls",
    "multi_verify_msm": "bls",
    "multi_verify_msm_idx": "bls",
    "rlc_partition": "bls",
    "sharded_multi_verify": "bls",
    "sharded_multi_verify_msm": "bls",
    "make_sharded_multi_verify": "bls",
    "make_sharded_multi_verify_msm": "bls",
    # flight-record kernel labels (scheme.kernel_label / firehose /
    # replay), annotated under their scheme
    "fast_aggregate": "bls",
    "fast_aggregate_fused": "bls",
    "multi_verify": "bls",
    "host": "bls",
    "pubkey_registry": "bls",
    # other schemes' dispatch names double as their flight labels
    "ed25519_verify": "ed25519",
    "kzg_blob_verify": "blob_kzg",
    "blob_kzg_verify": "blob_kzg",
    "kzg_msm": "blob_kzg",
    # slasher span plane
    "span_update_grid": "slasher",
    "span_update": "slasher",
}

#: closed family set for verify_device_hbm_bytes{family}
HBM_FAMILIES = ("registry", "kernel_io", "other")

#: field-element limb count — live arrays whose trailing dimension is
#: a limb plane belong to the verify plane (tpu/limbs.NLIMBS, kept as a
#: literal so this module never imports the kernel layer)
_NLIMBS = 26
#: rows at or above this look like registry planes, not batch operands
#: (tpu/registry.MIN_CAPACITY covers tests; production registries are
#: 2^20 rows — the boundary only needs to separate per-batch operands)
_REGISTRY_MIN_ROWS = 16384

DEFAULT_SESSION_RING = 8


def _bucket(items: int) -> int:
    """Pow-2 padding bucket, same policy as runtime/flight.bucket_of
    (duplicated two lines rather than importing the flight module from
    the annotation fast path)."""
    if items <= 1:
        return 1
    return 1 << (int(items) - 1).bit_length()


def _family_of(a) -> str:
    """Classify one live device array into an HBM family. Shape
    heuristic, documented rather than hidden: limb planes with a
    registry-scale leading dimension are "registry", any other integer/
    bool plane is per-batch "kernel_io", the rest (prng keys, tracer
    scratch) is "other"."""
    shape = tuple(getattr(a, "shape", ()) or ())
    if len(shape) >= 2 and shape[-1] == _NLIMBS:
        return "registry" if shape[0] >= _REGISTRY_MIN_ROWS else "kernel_io"
    dt = str(getattr(a, "dtype", ""))
    if dt.startswith(("int", "uint", "bool")):
        return "kernel_io"
    return "other"


def _leaves(out):
    """The arrays of a kernel's output (a tuple / list / dict of arrays
    or one array), without importing jax."""
    if isinstance(out, (tuple, list)):
        return [leaf for x in out for leaf in _leaves(x)]
    if isinstance(out, dict):
        return [leaf for x in out.values() for leaf in _leaves(x)]
    return [out]


# ------------------------------------------------------ dispatch phases

_DISPATCH = threading.local()


@contextlib.contextmanager
def dispatch_phases(*phases):
    """Name the phases of what the calls dispatched on this thread inside
    the block were doing before their dispatch: `(cause, start)` pairs in
    time order (`perf_counter` readings), each phase running from its
    start to the next one's, the last up to the dispatch; before the first
    start no item had arrived (`traffic`). An idle stretch the call ends
    is charged by these (`split_idle`)."""
    prev = getattr(_DISPATCH, "phases", None)
    _DISPATCH.phases = phases
    try:
        yield
    finally:
        _DISPATCH.phases = prev


def split_idle(lo: float, hi: float, phases, pauses) -> "dict[str, float]":
    """The idle stretch [lo, hi] by cause, summing to hi - lo: what
    overlaps one of the process's collections `pauses` ((start, end) in
    time order) goes to `gc`; each remaining instant to the phase of
    `phases` it falls in (see `dispatch_phases`), or to `other` where no
    phases were named."""
    out: "dict[str, float]" = {}
    rest, t = [], lo
    for a, b in pauses:
        a, b = max(a, t), min(b, hi)
        if b > a:
            rest.append((t, a))
            out["gc"] = out.get("gc", 0.0) + (b - a)
            t = b
    rest.append((t, hi))
    for a, b in rest:
        if b <= a:
            continue
        if phases is None:
            out["other"] = out.get("other", 0.0) + (b - a)
            continue
        cause, since = "traffic", float("-inf")
        for nxt, start in (*phases, (None, float("inf"))):
            seg = min(b, start) - max(a, since)
            if seg > 0:
                out[cause] = out.get(cause, 0.0) + seg
            cause, since = nxt, max(since, start)
    return out


# ------------------------------------------------------------ collections

#: pause intervals kept for the `gc` idle cause: a stretch is charged
#: when the call that ends it is ready, so the ring only has to outlast
#: one stretch's collections
_GC_RING = 256
#: the youngest generation whose collections leave a `gc` span: one of
#: generation 0 comes every ~700 net allocations (thousands in a node's
#: set-up, tens a second under load) and would crowd the batches' spans
#: out of the tracer's ring; it is counted and timed all the same
_GC_SPAN_GENERATION = 1


class _Collections:
    """The process's one `gc.callbacks` observer (`watch_collections`):
    times every collection of the interpreter, keeps totals by generation
    (`Metrics.expose()` raises `process_gc_pause_seconds_total` and
    `process_gc_collections_total` of the metrics it was wired with to
    them), a ring of the last pauses (the `gc` idle cause), a `gc` span on
    the collecting thread in the tracer it was wired with (generations 1
    and 2) and, while a capture session is on, a `process/gc_gen<g>/b0`
    annotation in the profiler's trace. A callback takes no lock a
    collection could have interrupted (a metric family's): collections run
    one at a time and never inside a callback, so plain fields and a deque
    suffice."""

    def __init__(self) -> None:
        self.metrics = None
        self.tracer = None
        self._began = 0.0
        self._mark = None
        self._pause_s = [0.0, 0.0, 0.0]
        self._collections = [0, 0, 0]
        self._pauses: "deque[tuple[float, float]]" = deque(maxlen=_GC_RING)

    def __call__(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        gen = info.get("generation", 0)
        if phase == "start":
            self._began = now
            if capturing():
                self._mark = _trace_annotation(f"process/gc_gen{gen}/b0")
                if self._mark is not None:
                    self._mark.__enter__()
            return
        began, mark, self._mark = self._began, self._mark, None
        if mark is not None:
            mark.__exit__(None, None, None)
        self._pauses.append((began, now))
        self._pause_s[gen] += now - began
        self._collections[gen] += 1
        tracer = self.tracer
        if tracer is not None and gen >= _GC_SPAN_GENERATION:
            tracer.span("gc", {"generation": gen,
                               "collected": info.get("collected", 0)},
                        start=began).finish(end=now)

    def totals(self) -> "tuple[list[float], list[int]]":
        """(pause seconds, collections), by generation."""
        return list(self._pause_s), list(self._collections)

    def pauses(self, lo: float, hi: float) -> "list[tuple[float, float]]":
        """The pauses that overlap [lo, hi], in time order."""
        return [(a, b) for a, b in tuple(self._pauses) if b > lo and a < hi]


_COLLECTIONS = _Collections()
_watching_lock = threading.Lock()


def watch_collections(metrics=None, tracer=None) -> None:
    """Observe the interpreter's collections from now on (installed once
    a process; a later call re-points the counters and the spans)."""
    _COLLECTIONS.metrics, _COLLECTIONS.tracer = metrics, tracer
    with _watching_lock:
        if _COLLECTIONS not in gc.callbacks:
            gc.callbacks.append(_COLLECTIONS)


class KernelProfiler:
    """See the module docstring. One instance per node (runtime/node.py
    publishes it as the module default so the dispatch seams reach it);
    tests construct private instances freely."""

    def __init__(
        self,
        *,
        metrics=None,
        capacity: int = DEFAULT_SESSION_RING,
        trace_root: "Optional[str]" = None,
        clock: "Callable[[], float]" = time.monotonic,
    ) -> None:
        self.metrics = metrics
        self.capacity = max(1, int(capacity))
        #: root directory for capture traces (cli --profile-dir); a
        #: session without it is annotation-only (no device trace file)
        self.trace_root = trace_root
        self.clock = clock
        self._lock = threading.Lock()
        #: capture flag annotate() reads per dispatch (under the same
        #: lock as the dispatch bump) and tracing.stage reads per stage
        #: through `capturing()` (no lock: one bool); only start/stop
        #: write it
        self._capturing = False
        self._active: "Optional[dict]" = None
        self._ring: "list[dict]" = []  # finished sessions, newest last
        self._sessions_total = 0
        self._dispatches: "dict[str, int]" = {}
        self._extra_kernels: "dict[str, str]" = {}
        self._hbm: "dict[str, int]" = {}
        #: the device timeline (`dispatched` -> the watcher thread), under
        #: `_lock`: calls dispatched and not yet ready, in dispatch order;
        #: the previous call's ready stamp; the instant up to which idle
        #: time is charged; busy seconds and calls by (kernel, scheme);
        #: idle seconds by cause
        self._wake = threading.Condition(self._lock)
        self._calls: "deque[tuple]" = deque()
        self._watcher: "Optional[threading.Thread]" = None
        self._last_ready: "Optional[float]" = None
        self._charged_to = 0.0
        self._device_s: "dict[tuple, float]" = {}
        self._calls_n: "dict[tuple, int]" = {}
        self._idle_s: "dict[str, float]" = {}
        if metrics is not None:
            for cause in IDLE_CAUSES:  # every series present from the start
                metrics.verify_device_idle_seconds.labels(cause)

    # ------------------------------------------------ annotation registry

    def register_kernel(self, kernel: str, scheme: str = "other") -> None:
        """Register a dispatch name outside the static table (tests,
        experimental kernels). `scheme` must come from SCHEMES — the
        metric label set is closed."""
        if scheme not in SCHEMES:
            raise ValueError(f"unknown scheme {scheme!r} (want {SCHEMES})")
        with self._lock:
            self._extra_kernels[kernel] = scheme

    def annotation_keys(self) -> "dict[str, str]":
        with self._lock:
            extra = dict(self._extra_kernels)
        out = dict(KERNEL_SCHEMES)
        out.update(extra)
        return out

    def scheme_of(self, kernel: str) -> str:
        scheme = KERNEL_SCHEMES.get(kernel)
        if scheme is None:
            with self._lock:
                scheme = self._extra_kernels.get(kernel, "other")
        return scheme if scheme in SCHEMES else "other"

    # ------------------------------------------------- annotation scopes

    def annotate(self, kernel: str, items: int = 0):
        """The per-dispatch scope: always bumps the dispatch counter;
        only while a capture session is active does it open a
        jax.profiler.TraceAnnotation (keyed scheme/kernel/bucket) — the
        always-off path is one locked dict bump per BATCH, which is what
        keeps the overhead guard ≤5% (tests/test_profiler.py)."""
        with self._lock:
            self._dispatches[kernel] = self._dispatches.get(kernel, 0) + 1
            capturing = self._capturing
        if not capturing:
            return contextlib.nullcontext()
        jax = sys.modules.get("jax")
        if jax is None:
            return contextlib.nullcontext()
        label = f"{self.scheme_of(kernel)}/{kernel}/b{_bucket(items)}"
        try:
            return jax.profiler.TraceAnnotation(label)
        except Exception:
            return contextlib.nullcontext()

    # ------------------------------------------------ the device timeline

    def dispatched(self, kernel: str, out, items: int = 0,
                   tracer=None) -> None:
        """A call's output, handed over as its jitted call returns: stamps
        `enqueued` and queues the call for the watcher thread (started on
        the first call), with the thread's current span of `tracer` (the
        `device` span's parent) and the phases named around the dispatch
        (`dispatch_phases`)."""
        parent = tracer.current() if tracer is not None else None
        phases = getattr(_DISPATCH, "phases", None)
        with self._wake:
            self._calls.append((kernel, out, items, tracer, parent, phases,
                                time.perf_counter()))
            self._wake.notify()
            if self._watcher is None:
                self._watcher = threading.Thread(
                    target=self._watch, name="device-timeline", daemon=True
                )
                self._watcher.start()

    def _watch(self) -> None:
        """The watcher thread: each call in dispatch order, blocked on
        off the GIL, stamped ready. A call that failed is ready where it
        failed."""
        while True:
            with self._wake:
                while not self._calls:
                    self._wake.wait()
                kernel, out, items, tracer, parent, phases, enqueued = (
                    self._calls[0]
                )
            for leaf in _leaves(out):
                try:
                    leaf.block_until_ready()
                except Exception:
                    pass  # not an array, or its computation failed
            del out
            ready = time.perf_counter()
            with self._wake:
                self._calls.popleft()
                prev = self._last_ready
                idle_from = (enqueued if prev is None
                             else max(prev, self._charged_to))
                start = enqueued if prev is None else max(enqueued, prev)
                self._last_ready = self._charged_to = ready
            try:
                self._stamp(kernel, items, tracer, parent, phases, enqueued,
                            start, ready, idle_from)
            except Exception:
                pass  # the timeline must outlive a faulty tracer or metric

    def _stamp(self, kernel, items, tracer, parent, phases, enqueued,
               start, ready, idle_from) -> None:
        busy = ready - start
        idle = max(0.0, enqueued - idle_from)
        causes = (split_idle(idle_from, enqueued, phases,
                             _COLLECTIONS.pauses(idle_from, enqueued))
                  if idle > 0.0 else {})
        scheme = self.scheme_of(kernel)
        key = (kernel, scheme)
        with self._lock:
            self._device_s[key] = self._device_s.get(key, 0.0) + busy
            self._calls_n[key] = self._calls_n.get(key, 0) + 1
            active = self._active
            if active is not None:
                active["device_s"] += busy
                active["calls"] += 1
        self._charge(causes)
        if self.metrics is not None:
            self.metrics.verify_device_seconds.labels(kernel, scheme).inc(
                busy)
        if tracer is not None:
            tracer.span("device", {
                "kernel": kernel, "items": items, "bucket": _bucket(items),
                "idle_before_s": idle,
            }, parent=parent, start=start).finish(end=ready)

    def _charge(self, causes: "dict[str, float]") -> None:
        with self._lock:
            for cause, seconds in causes.items():
                self._idle_s[cause] = self._idle_s.get(cause, 0.0) + seconds
        if self.metrics is not None:
            for cause, seconds in causes.items():
                self.metrics.verify_device_idle_seconds.labels(cause).inc(
                    seconds)

    def charge_open_idle(self) -> None:
        """Charge the idle stretch still open (the device idle since its
        last call, none dispatched since) up to now, to `gc` where a
        collection overlaps it and to `other` for the rest: no call has
        ended it, so nothing names its cause. The call that ends it is
        charged from here on. What `Metrics.expose()` runs first, so busy
        plus idle seconds tile the time between two readings."""
        with self._wake:
            if self._calls or self._last_ready is None:
                return
            lo = max(self._last_ready, self._charged_to)
            now = time.perf_counter()
            if now <= lo:
                return
            self._charged_to = now
        self._charge(split_idle(lo, now, None, _COLLECTIONS.pauses(lo, now)))

    def device_seconds(self) -> "dict[tuple, float]":
        """Busy seconds of the device timeline by (kernel, scheme)."""
        with self._lock:
            return dict(self._device_s)

    def idle_seconds(self) -> "dict[str, float]":
        """Idle seconds of the device timeline by cause."""
        with self._lock:
            return dict(self._idle_s)

    def update_hbm(self, live_arrays=None) -> "dict[str, int]":
        """Snapshot live device bytes per family into
        verify_device_hbm_bytes. Uses the injected iterable (tests) or
        jax.live_arrays() when jax is already imported — never imports
        jax itself."""
        arrays = live_arrays
        if arrays is None:
            jax = sys.modules.get("jax")
            if jax is None:
                return {}
            try:
                arrays = jax.live_arrays()
            except Exception:
                return {}
        totals = {fam: 0 for fam in HBM_FAMILIES}
        for a in arrays:
            totals[_family_of(a)] += int(getattr(a, "nbytes", 0) or 0)
        with self._lock:
            self._hbm = dict(totals)
        if self.metrics is not None:
            for fam, nbytes in totals.items():
                self.metrics.verify_device_hbm_bytes.labels(fam).set(nbytes)
        return totals

    # --------------------------------------------------- capture sessions

    def start(self, trace_dir: "Optional[str]" = None,
              note: str = "") -> dict:
        """Open a capture session (at most one). With a trace dir —
        explicit, or derived from `trace_root` — the jax profiler writes
        a perfetto/Chrome trace there; without one the session is
        annotation-only (still ringed, still counted). Raises
        RuntimeError if a session is already active."""
        with self._lock:
            if self._active is not None:
                raise RuntimeError("profiler capture session already active")
            self._sessions_total += 1
            sid = self._sessions_total
            tdir = trace_dir
            if tdir is None and self.trace_root:
                tdir = os.path.join(self.trace_root, f"session-{sid:04d}")
            sess = {
                "id": sid,
                "started": self.clock(),
                "stopped": None,
                "trace_dir": tdir,
                "note": note,
                "device_s": 0.0,
                "calls": 0,
                "tracing": False,
                "error": None,
            }
            self._active = sess
            self._capturing = True
        if tdir is not None:
            try:
                import jax

                os.makedirs(tdir, exist_ok=True)
                jax.profiler.start_trace(tdir)
                sess["tracing"] = True
            except Exception as exc:  # host-only node: annotation-only
                sess["error"] = f"device trace unavailable: {exc!r}"
        if self.metrics is not None:
            self.metrics.verify_profile_sessions.inc()
        return dict(sess)

    def stop(self) -> dict:
        """Close the active session: stop the device trace (if any),
        stamp the duration, append to the bounded ring of the last
        `capacity` sessions. Raises RuntimeError when none is active."""
        with self._lock:
            sess = self._active
            if sess is None:
                raise RuntimeError("no active profiler capture session")
            self._active = None
            self._capturing = False
        if sess["tracing"]:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as exc:
                sess["error"] = f"stop_trace failed: {exc!r}"
        sess["stopped"] = self.clock()
        with self._lock:
            self._ring.append(sess)
            if len(self._ring) > self.capacity:
                del self._ring[: len(self._ring) - self.capacity]
        self.update_hbm()  # best-effort close-of-session snapshot
        return dict(sess)

    def sessions(self) -> "list[dict]":
        with self._lock:
            return [dict(s) for s in self._ring]

    def active_session(self) -> "Optional[dict]":
        with self._lock:
            return dict(self._active) if self._active is not None else None

    @property
    def sessions_total(self) -> int:
        with self._lock:
            return self._sessions_total

    # ------------------------------------------------------------ summary

    def summary(self, kernel: "Optional[str]" = None,
                scheme: "Optional[str]" = None,
                n_sessions: "Optional[int]" = None) -> dict:
        """The debug-endpoint payload: the device timeline's busy rows
        (filterable by kernel/scheme) and idle seconds by cause, dispatch
        counts, the session ring and the HBM snapshot."""
        with self._lock:
            rows = [
                {
                    "kernel": k,
                    "scheme": s,
                    "device_s": round(v, 6),
                    "calls": self._calls_n.get((k, s), 0),
                }
                for (k, s), v in sorted(self._device_s.items())
            ]
            idle = {c: round(v, 6) for c, v in sorted(self._idle_s.items())}
            dispatches = dict(sorted(self._dispatches.items()))
            ring = [dict(x) for x in self._ring]
            active = dict(self._active) if self._active else None
            total = self._sessions_total
            hbm = dict(self._hbm)
        if kernel is not None:
            rows = [r for r in rows if r["kernel"] == kernel]
            dispatches = {k: v for k, v in dispatches.items() if k == kernel}
        if scheme is not None:
            rows = [r for r in rows if r["scheme"] == scheme]
        if n_sessions is not None:
            ring = ring[-n_sessions:] if n_sessions else []
        return {
            "device_seconds": rows,
            "idle_seconds": idle,
            "dispatches": dispatches,
            "sessions": ring,
            "active_session": active,
            "sessions_total": total,
            "hbm_bytes": hbm,
        }


# ------------------------------------------------------- module default

_default_lock = threading.Lock()
_DEFAULT: "Optional[KernelProfiler]" = None


def get_profiler() -> KernelProfiler:
    """The process-wide profiler the dispatch seams annotate through.
    Metrics-less until a node (or bench) publishes a configured instance
    via set_profiler."""
    global _DEFAULT
    with _default_lock:
        if _DEFAULT is None:
            _DEFAULT = KernelProfiler()
        return _DEFAULT


def set_profiler(profiler: KernelProfiler) -> KernelProfiler:
    global _DEFAULT
    with _default_lock:
        _DEFAULT = profiler
    return profiler


def stage_annotation(lane: str, what: str, items: int = 0):
    """`<lane>/<op or stage>/b<bucket>` in the profiler's trace: the
    kernel annotation's coordinates, so the host stages and the device
    kernels of one batch line up on one clock (tracing.stage opens it
    while `capturing()`). None when JAX is not loaded."""
    return _trace_annotation(f"{lane}/{what}/b{_bucket(items)}")


def _trace_annotation(label: str):
    """A host span named `label` in the profiler's trace, or None when
    JAX is not loaded."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation(label)
    except Exception:
        return None


def sync_metrics(metrics) -> None:
    """`Metrics.expose()`'s hook: where the process-wide profiler counts
    into `metrics`, its open idle stretch charged (`charge_open_idle`);
    where the collections observer was wired with `metrics`, the
    `process_gc_*` counters raised to its totals."""
    prof = _DEFAULT
    if prof is not None and prof.metrics is metrics:
        prof.charge_open_idle()
    if _COLLECTIONS.metrics is metrics:
        seconds, counts = _COLLECTIONS.totals()
        for gen in range(3):
            for family, total in (
                (metrics.process_gc_pause_seconds, seconds[gen]),
                (metrics.process_gc_collections, counts[gen]),
            ):
                child = family.labels(gen)
                child.inc(total - child.value)


def capturing() -> bool:
    """Whether the process-wide profiler has a capture session on. The
    stage helper (tracing.stage) asks before every stage, so: no lock, no
    lazy construction — a torn read mislabels one stage's edge."""
    prof = _DEFAULT
    return prof is not None and prof._capturing


__all__ = [
    "KernelProfiler",
    "KERNEL_SCHEMES",
    "SCHEMES",
    "HBM_FAMILIES",
    "IDLE_CAUSES",
    "DEFAULT_SESSION_RING",
    "dispatch_phases",
    "split_idle",
    "watch_collections",
    "get_profiler",
    "set_profiler",
    "sync_metrics",
    "capturing",
    "stage_annotation",
]
