"""Where the program compiles: one scope, one clock, one trim, one freeze.

A first dispatch of a (kernel, shapes) pair blocks on trace + XLA
compilation — minutes and ~6 GB of host memory for the pairing kernels,
whatever the batch size. Two things follow from that, and both live here
so every compile site (the backend's first-call stage in tpu/bls.py, each
entry of the warm loop in runtime/warmup.py) behaves the same:

  - the compiler's working memory is freed into the C allocator, not to
    the system: each further compile grows the resident set by ~3.6 GB
    until `malloc_trim(0)` hands it back. `trim_host_memory()` does that,
    and `compiling()` calls it on exit.
  - a compile is not a device fault. The settle watchdog
    (runtime/health.run_with_deadline) reads `compile_seconds(thread)` and
    charges only the time its thread spent OUTSIDE a `compiling()` scope
    to the deadline, so a first call that lands inside a watchdog-bounded
    settle cannot open the breaker.

  - what is alive when a compile ends stays alive: the chain's state, the
    registry, JAX's traced programs, ~1.1 million containers by the
    firehose's first call at 50,000 validators. A collection of Python's
    oldest generation walks all of them with every thread stopped, 350 to
    470 ms, and comes whenever a quarter as many new containers have
    survived: inside a gossip phase such a stall owns the window's tail
    (PERF.md section 6, PR 31 and PR 33). `settle_heap()` collects once,
    where a stall of minutes is ending anyway, and moves what survived to
    the permanent generation, which no later collection walks;
    `compiling()` calls it on exit.
  - what a compile costs is three different things (Python tracing,
    lowering to MLIR, the XLA compile or the persistent cache's load), and
    only the last one the cache saves. JAX reports each itself
    (`jax.monitoring`); `listen()` subscribes once per process and
    `phase_totals()` holds what arrived while the reporting thread was
    inside `compiling()`. `Metrics.expose()` publishes them as
    `verify_compile_phase_seconds_total{phase}` and
    `verify_compile_cache_total{result}`.

Imports nothing heavy: runtime/health.py pulls this in on host-only nodes
(`listen()` is handed `jax.monitoring` by tpu/bls.py, which has JAX).
"""

from __future__ import annotations

import ctypes
import gc
import threading
import time
from contextlib import contextmanager

_LOCK = threading.Lock()
#: thread ident -> [finished compile seconds, start of the open scope or
#: None, {phase: [(start, seconds), ...] reported inside the open scope}]
_CLOCK: "dict[int, list]" = {}
_TOTAL = [0.0, 0]  # process-wide compile seconds, compile count

#: the CLOSED phase set of verify_compile_phase_seconds_total, by the JAX
#: 0.9 event that feeds each (checked against the installed JAX's
#: jax/_src/dispatch.py and compiler.py). `backend` is the XLA compile OR
#: the cache load, whichever happened; `cache_retrieval` is the cache read
#: alone and lies inside `backend`.
PHASE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
PHASES = tuple(PHASE_EVENTS.values())
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_PHASE_S = {phase: 0.0 for phase in PHASES}
_CACHE_N = {"hit": 0, "miss": 0}
_LISTENING = [False]

try:
    _malloc_trim = ctypes.CDLL(None).malloc_trim
except (OSError, AttributeError):  # not glibc: nothing to give back
    _malloc_trim = None


def trim_host_memory() -> None:
    """Return the C allocator's free pages to the system."""
    if _malloc_trim is not None:
        _malloc_trim(0)


def settle_heap() -> None:
    """Collect now, then keep every container that survived out of all
    later collections (`gc.freeze`). Reference counting frees a frozen
    object as before; only a frozen CYCLE that dies later stays, until
    someone thaws it (`gc.unfreeze`, as chip_smoke.py does where it drops
    its executables). After the first call a further one walks only what
    was made since."""
    gc.collect()
    gc.freeze()


@contextmanager
def compiling():
    """Mark the calling thread as compiling; trims host memory and
    settles the heap on exit. Scopes nest (the warm loop wraps the
    backend's own first-call stage): only the outermost one runs the
    clock, the trim and the freeze."""
    ident = threading.get_ident()
    with _LOCK:
        row = _CLOCK.setdefault(ident, [0.0, None, {}])
        outer = row[1] is None
        if outer:
            row[1] = time.monotonic()
    try:
        yield
    finally:
        if outer:
            with _LOCK:
                dt = time.monotonic() - row[1]
                row[0] += dt
                row[1] = None
                row[2].clear()
                _TOTAL[0] += dt
                _TOTAL[1] += 1
            settle_heap()
            trim_host_memory()


def compile_seconds(ident: int) -> "tuple[float, bool]":
    """(seconds thread `ident` has spent compiling, whether it is inside
    a scope right now). The open scope's elapsed time is included."""
    with _LOCK:
        row = _CLOCK.get(ident)
        if row is None:
            return 0.0, False
        if row[1] is None:
            return row[0], False
        return row[0] + (time.monotonic() - row[1]), True


def totals() -> "tuple[float, int]":
    """(compile seconds, outermost compile scopes closed) process-wide."""
    with _LOCK:
        return _TOTAL[0], _TOTAL[1]


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """A `jax.monitoring` duration, on the thread that did the work. Kept
    only inside `compiling()`; an event JAX does not have in PHASE_EVENTS
    is dropped. JAX times a jitted function called while another is being
    traced on its own AND inside the outer one's duration (every `jnp`
    operation is such a function), so a duration that contains earlier
    ones of its phase replaces them: a phase reads the union."""
    phase = PHASE_EVENTS.get(event)
    if phase is None:
        return
    end = time.monotonic()
    with _LOCK:
        row = _CLOCK.get(threading.get_ident())
        if row is None or row[1] is None:
            return
        start = end - seconds
        inner = row[2].setdefault(phase, [])
        while inner and inner[-1][0] >= start:
            seconds -= inner.pop()[1]
        inner.append((start, end - start))
        _PHASE_S[phase] += seconds


def _on_event(event: str, **_kw) -> None:
    result = CACHE_EVENTS.get(event)
    if result is None:
        return
    with _LOCK:
        row = _CLOCK.get(threading.get_ident())
        if row is not None and row[1] is not None:
            _CACHE_N[result] += 1


def listen(monitoring) -> None:
    """Subscribe to `jax.monitoring` (the module is handed in: nothing
    here imports JAX). Once per process; later calls do nothing."""
    with _LOCK:
        if _LISTENING[0]:
            return
        _LISTENING[0] = True
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def phase_totals() -> "tuple[dict, dict]":
    """({phase: seconds}, {"hit"|"miss": lookups}) reported from inside
    compile scopes, process-wide."""
    with _LOCK:
        return dict(_PHASE_S), dict(_CACHE_N)


__all__ = ["compiling", "compile_seconds", "totals", "trim_host_memory",
           "settle_heap", "listen", "phase_totals", "PHASES"]
