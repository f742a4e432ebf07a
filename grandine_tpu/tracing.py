"""Structured tracing: monotonic-clock spans with parent context that
survives thread hops, a bounded ring buffer of completed spans, and
Chrome trace-event JSON export (load the dump in `chrome://tracing` or
Perfetto).

Design mirrors the reference client's tracing feature flag: spans are
cheap enough to leave on (two `perf_counter` calls and a deque append),
carry string attributes, and nest via an explicit parent id rather than
global state — the current span is tracked per-thread, and
`Tracer.capture()` / `Tracer.attach()` move that context across the
runtime's thread pool (see runtime/thread_pool.py, which captures at
`spawn` and attaches in the worker).
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["Span", "Tracer", "NULL_TRACER", "STAGE_OPS", "stage"]

#: process-wide epoch for trace timestamps: Chrome trace-event `ts` is in
#: microseconds from an arbitrary origin; anchoring every tracer at import
#: keeps spans from different tracers on one comparable timeline.
_EPOCH = time.perf_counter()
#: the wall clock at that instant: `chrome_trace()` hands it out so a span
#: dump can be laid beside a profiler trace (whose events are on the
#: profiler's clock, Unix-epoch nanoseconds on a TPU host)
_EPOCH_TIME_NS = time.time_ns()


class Span:
    """One timed operation. Use as a context manager (finishes on exit)
    or call `finish()` explicitly for hand-rolled begin/end pairs."""

    __slots__ = (
        "name",
        "trace_id",
        "span_id",
        "parent_id",
        "start",
        "end",
        "attrs",
        "thread_id",
        "thread_name",
        "_tracer",
        "_token",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: int,
        span_id: int,
        parent_id: Optional[int],
        attrs: Optional[Dict[str, Any]] = None,
        start: Optional[float] = None,
    ) -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        #: `start` (a `perf_counter` reading) back-dates a span whose
        #: beginning was stamped before anyone could open it: a wait
        self.start = time.perf_counter() if start is None else start
        self.end: Optional[float] = None
        self.attrs: Dict[str, Any] = dict(attrs) if attrs else {}
        t = threading.current_thread()
        self.thread_id = t.ident or 0
        self.thread_name = t.name
        self._tracer = tracer
        self._token: Optional[Span] = None

    # ------------------------------------------------------------ lifecycle

    @property
    def duration(self) -> float:
        """Seconds; 0.0 while the span is still open."""
        if self.end is None:
            return 0.0
        return self.end - self.start

    def set_attr(self, key: str, value: Any) -> "Span":
        self.attrs[key] = value
        return self

    def finish(self, end: Optional[float] = None) -> None:
        """End the span now, or at the `perf_counter` reading `end` (a
        span stamped by a thread that did not live it: the device's)."""
        if self.end is not None:  # idempotent
            return
        self.end = time.perf_counter() if end is None else end
        self._tracer._on_finish(self)

    def __enter__(self) -> "Span":
        self._token = self._tracer._push(self)
        return self

    def __exit__(self, *_exc) -> None:
        self._tracer._pop(self, self._token)
        self.finish()

    # -------------------------------------------------------------- export

    def to_chrome_event(self) -> Dict[str, Any]:
        """Chrome trace-event "complete" event (ph=X, µs timestamps)."""
        dur = self.duration
        ev: Dict[str, Any] = {
            "name": self.name,
            "ph": "X",
            "ts": round((self.start - _EPOCH) * 1e6, 3),
            "dur": round(dur * 1e6, 3),
            "pid": os.getpid(),
            "tid": self.thread_id,
            "args": dict(self.attrs),
        }
        ev["args"]["trace_id"] = self.trace_id
        ev["args"]["span_id"] = self.span_id
        if self.parent_id is not None:
            ev["args"]["parent_id"] = self.parent_id
        return ev


class _NullSpan:
    """Do-nothing span so instrumented code never branches on tracer
    presence: `with tracer.span(...)` works whether tracing is live."""

    __slots__ = ()
    name = "null"
    trace_id = 0
    span_id = 0
    parent_id = None
    duration = 0.0
    attrs: Dict[str, Any] = {}

    def set_attr(self, *_a, **_k) -> "_NullSpan":
        return self

    def finish(self, end: Optional[float] = None) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *_exc) -> None:
        pass

    def to_chrome_event(self) -> Dict[str, Any]:
        return {}


_NULL_SPAN = _NullSpan()


class Tracer:
    """Span factory + bounded ring buffer of completed spans.

    Thread-safe: span-id allocation and buffer appends take a lock; the
    per-thread "current span" lives in a `threading.local`, so nesting is
    tracked independently on every thread. To carry context across a
    thread hop, call `capture()` on the submitting thread and `attach()`
    (a context manager) on the worker.
    """

    def __init__(self, capacity: int = 4096, enabled: bool = True) -> None:
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        #: re-entrant, as is the sink's: a collection of the interpreter
        #: can run on a thread inside either, and its `gc` span (runtime/
        #: profiler.py) is opened and ended right there
        self._lock = threading.RLock()
        self._finished: deque = deque(maxlen=self.capacity)
        self._next_id = 1
        self._local = threading.local()
        #: the `--trace-out` sink: ONE handle kept open, written under a
        #: lock, flushed when a root span ends (a whole trace is then on
        #: disk), on `clear()`, on `flush()` and at interpreter exit
        self._jsonl = None
        self._jsonl_lock = threading.RLock()

    # ----------------------------------------------------------- span API

    def _alloc_id(self) -> int:
        with self._lock:
            i = self._next_id
            self._next_id += 1
            return i

    def current(self) -> Optional[Span]:
        return getattr(self._local, "span", None)

    def span(
        self,
        name: str,
        attrs: Optional[Dict[str, Any]] = None,
        parent: Optional[Span] = None,
        start: Optional[float] = None,
    ):
        """New span parented on `parent` (or the thread's current span),
        begun now or at the `perf_counter` reading `start`. Returns a
        no-op span when the tracer is disabled."""
        if not self.enabled:
            return _NULL_SPAN
        if parent is None:
            parent = self.current()
        if parent is not None and not isinstance(parent, Span):
            parent = None  # a _NullSpan or foreign token: no parent
        sid = self._alloc_id()
        if parent is not None:
            return Span(self, name, parent.trace_id, sid, parent.span_id,
                        attrs, start)
        return Span(self, name, sid, sid, None, attrs, start)

    def _push(self, span: Span):
        prev = getattr(self._local, "span", None)
        self._local.span = span
        return prev

    def _pop(self, span: Span, prev) -> None:
        if getattr(self._local, "span", None) is span:
            self._local.span = prev

    def _on_finish(self, span: Span) -> None:
        with self._lock:
            self._finished.append(span)
        if self._jsonl is not None:
            line = json.dumps(span.to_chrome_event(), separators=(",", ":"))
            with self._jsonl_lock:
                fh = self._jsonl
                if fh is None:
                    return
                try:
                    fh.write(line + "\n")
                    if span.parent_id is None:
                        fh.flush()
                except (OSError, ValueError):
                    self._jsonl = None  # dead sink: stop trying

    # --------------------------------------------------- cross-thread hops

    def capture(self) -> Optional[Span]:
        """Current span on this thread, to hand to `attach()` elsewhere."""
        return self.current()

    def attach(self, parent: Optional[Span]):
        """Context manager installing `parent` as the current span on the
        calling (worker) thread for the duration of a task."""
        return _Attach(self, parent)

    # -------------------------------------------------------------- export

    def finished_spans(self) -> List[Span]:
        with self._lock:
            return list(self._finished)

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()
        self.flush()

    def flush(self) -> None:
        """Push what the JSONL sink has buffered to its file."""
        with self._jsonl_lock:
            if self._jsonl is not None:
                try:
                    self._jsonl.flush()
                except (OSError, ValueError):
                    self._jsonl = None

    def chrome_trace(self) -> Dict[str, Any]:
        """The whole ring buffer as a Chrome trace-event JSON object."""
        spans = self.finished_spans()
        return {
            "traceEvents": [s.to_chrome_event() for s in spans],
            "displayTimeUnit": "ms",
            "otherData": {
                "clock": "perf_counter",
                # `ts` 0 of this dump on the wall clock
                "epoch_time_ns": _EPOCH_TIME_NS,
                "span_count": len(spans),
                "capacity": self.capacity,
            },
        }

    def set_jsonl_path(self, path: Optional[str]) -> None:
        """Mirror every finished span to `path` as one JSON line each
        (Chrome trace-event objects; `jq -s '{traceEvents:.}'` rebuilds a
        loadable trace). Truncates any existing file; `None` closes the
        sink."""
        fh = open(path, "w") if path is not None else None
        with self._jsonl_lock:
            old, self._jsonl = self._jsonl, fh
        if old is not None:
            old.close()
        elif fh is not None:
            atexit.register(self.flush)


class _Attach:
    __slots__ = ("_tracer", "_parent", "_prev")

    def __init__(self, tracer: Tracer, parent: Optional[Span]) -> None:
        self._tracer = tracer
        self._parent = parent if isinstance(parent, Span) else None
        self._prev: Optional[Span] = None

    def __enter__(self) -> "_Attach":
        self._prev = self._tracer.current()
        self._tracer._local.span = self._parent
        return self

    def __exit__(self, *_exc) -> None:
        self._tracer._local.span = self._prev


#: shared disabled tracer: modules can default to this and never check
#: for None before opening spans.
NULL_TRACER = Tracer(capacity=1, enabled=False)


# ------------------------------------------------------- pipeline stages
#
# One helper for every verify plane (the firehose, the scheduler lanes,
# bulk replay, the device backend): a stage is a child span under the
# thread's current span, one `verify_stage_seconds{stage,lane,op}`
# observation, its thread's CPU seconds into `verify_stage_cpu_seconds_
# total{stage,lane,op}` and the span's `cpu_s` (wall minus CPU is time the
# thread was off the CPU: waiting for the GIL, a lock or I/O) and, while a
# profiler capture session is on, a host span in the profiler's own trace.

#: the CLOSED set of `op` values on verify_stage_seconds: what a stage
#: that several call sites feed is split by. "" is a stage with one part.
#: The metrics-cardinality lint rule reads this tuple and refuses an
#: `op="..."` literal outside it; at run time an unknown op reads "other".
STAGE_OPS = (
    "",
    # host_prep, firehose (runtime/attestation_verifier.py)
    "prevalidate", "g2_decompress", "registry_sync",
    # host_prep, device backend (tpu/bls.py)
    "pack", "pack_aggregate", "pack_aggregate_idx",
    "pack_aggregate_compressed", "pack_aggregate_idx_compressed",
    "pack_compressed", "pack_grouped", "pack_idx", "pack_partition",
    "pack_sign", "pack_subgroup", "point_convert", "msm_plan",
    "sharded_msm_plan",
    # host_prep, scheduler lanes (tpu/schemes.py)
    "sig_bytes", "resolve_keys", "ed25519_decode", "kzg_prep",
    # feedback (the firehose's settle side)
    "deliver", "slasher_feed",
    "other",
)
_STAGE_OPS = frozenset(STAGE_OPS)


def _profiler_span(lane: str, what: str, attrs, tracer: Tracer):
    """The stage's host span in the profiler's own trace while the node's
    KernelProfiler has a capture session on (the flag its `annotate()`
    reads), else None: one module lookup and one flag read.
    runtime/profiler.py is never imported from here. The bucket is the
    stage's own `items`, else the enclosing span's (`items`, or the
    root's `batch`)."""
    mod = sys.modules.get("grandine_tpu.runtime.profiler")
    if mod is None or not mod.capturing():
        return None
    items = (attrs or {}).get("items")
    if items is None:
        outer = tracer.current()
        if outer is not None:
            items = outer.attrs.get("items", outer.attrs.get("batch"))
    return mod.stage_annotation(lane, what, int(items or 0))


class stage:
    """`with stage(tracer, metrics, "host_prep", lane, op="pack", items=n)`
    — yields the stage's span. `metrics` may be None (span only) and
    `tracer` NULL_TRACER (observation only). A stage must not be opened
    inside a span of its own stage name: the sum over `op` of a stage is
    what the stage took."""

    __slots__ = ("_tracer", "_metrics", "_stage", "_lane", "_op", "_attrs",
                 "_t0", "_c0", "_span", "_mark")

    def __init__(self, tracer: Tracer, metrics, stage: str, lane: str,
                 op: str = "", **attrs) -> None:
        if op not in _STAGE_OPS:
            op = "other"
        if op:
            attrs["op"] = op
        self._tracer = tracer
        self._metrics = metrics
        self._stage = stage
        self._lane = lane
        self._op = op
        self._attrs = attrs or None

    def __enter__(self):
        mark = self._mark = _profiler_span(
            self._lane, self._op or self._stage, self._attrs, self._tracer
        )
        if mark is not None:
            mark.__enter__()
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time()
        self._span = self._tracer.span(self._stage, self._attrs)
        return self._span.__enter__()

    def __exit__(self, *exc) -> None:
        cpu = time.thread_time() - self._c0
        self._span.set_attr("cpu_s", cpu)
        self._span.__exit__(*exc)
        dt = time.perf_counter() - self._t0
        if self._mark is not None:
            self._mark.__exit__(*exc)
        if self._metrics is not None:
            self._metrics.verify_stage_seconds.labels(
                self._stage, self._lane, self._op
            ).observe(dt)
            self._metrics.verify_stage_cpu_seconds.labels(
                self._stage, self._lane, self._op
            ).inc(cpu)
