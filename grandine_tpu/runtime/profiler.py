"""Node-wide kernel profiler: per-kernel device-time attribution plus
bounded on-demand capture sessions.

Two independent planes share this module:

* **Always-on estimators** — every dispatch seam (`TpuBlsBackend.
  _run_kernel`, `Ed25519Backend.verify_batch_async`, `KzgDeviceBackend.
  verify_blobs_async`, the kzg MSM tail) counts its dispatches here, and
  the flight recorder reconciles every committed `BatchRecord`'s
  dispatch→settle delta into per-`(kernel, scheme)` device-second
  totals via `on_batch` (`FlightRecorder.profiler` hook). These feed
  `verify_device_seconds_total{kernel,scheme}` and, together with
  `jax.live_arrays`-derived per-family live-byte gauges
  (`verify_device_hbm_bytes{family}`), cost nothing but a dict bump per
  batch — no jax import, no trace machinery.

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
`sys.modules` on the estimator paths (never imported — a host-only node
must not pay the import) and imported lazily only inside a capture
session.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Callable, Optional

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
    # replay) — the estimator sees these on BatchRecords
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


class KernelProfiler:
    """See the module docstring. One instance per node (runtime/node.py
    wires it into the shared FlightRecorder and publishes it as the
    module default so the dispatch seams reach it); tests construct
    private instances freely."""

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
        self._device_s: "dict[tuple, float]" = {}
        self._batches: "dict[tuple, int]" = {}
        self._dispatches: "dict[str, int]" = {}
        self._extra_kernels: "dict[str, str]" = {}
        self._hbm: "dict[str, int]" = {}

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

    # --------------------------------------------- always-on estimators

    def on_batch(self, rec) -> None:
        """FlightRecorder._commit hook: reconcile one committed record's
        dispatch→settle device seconds into the estimator. Accepts any
        record carrying a kernel (batches and canary probes — both are
        device time)."""
        kernel = getattr(rec, "kernel", "") or ""
        if not kernel:
            return
        dev = max(0.0, float(getattr(rec, "device_s", 0.0) or 0.0))
        scheme = self.scheme_of(kernel)
        key = (kernel, scheme)
        with self._lock:
            self._device_s[key] = self._device_s.get(key, 0.0) + dev
            self._batches[key] = self._batches.get(key, 0) + 1
            active = self._active
            if active is not None:
                active["device_s"] += dev
                active["batches"] += 1
        if self.metrics is not None and dev > 0.0:
            self.metrics.verify_device_seconds.labels(
                kernel, scheme
            ).inc(dev)

    def device_seconds(self) -> "dict[tuple, float]":
        with self._lock:
            return dict(self._device_s)

    def attributed_seconds(self) -> float:
        with self._lock:
            return sum(self._device_s.values())

    def coverage(self, flight) -> "Optional[float]":
        """Fraction of the flight recorder's device-busy integral the
        estimator attributed to named kernels — the `profiler_coverage`
        field the firehose bench reports (acceptance: ≥0.90). None when
        the recorder saw no device time."""
        if flight is None:
            return None
        busy = flight.busy_seconds()
        if busy <= 0.0:
            return None
        return min(1.0, self.attributed_seconds() / busy)

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
                "batches": 0,
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
                n_sessions: "Optional[int]" = None,
                flight=None) -> dict:
        """The debug-endpoint payload: estimator rows (filterable by
        kernel/scheme), dispatch counts, the session ring, the HBM
        snapshot, and coverage against the given flight recorder."""
        with self._lock:
            rows = [
                {
                    "kernel": k,
                    "scheme": s,
                    "device_s": round(v, 6),
                    "batches": self._batches.get((k, s), 0),
                }
                for (k, s), v in sorted(self._device_s.items())
            ]
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
        out = {
            "device_seconds": rows,
            "dispatches": dispatches,
            "sessions": ring,
            "active_session": active,
            "sessions_total": total,
            "hbm_bytes": hbm,
        }
        cov = self.coverage(flight)
        if cov is not None:
            out["coverage"] = round(cov, 4)
        return out


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
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation(f"{lane}/{what}/b{_bucket(items)}")
    except Exception:
        return None


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
    "DEFAULT_SESSION_RING",
    "get_profiler",
    "set_profiler",
    "capturing",
    "stage_annotation",
]
