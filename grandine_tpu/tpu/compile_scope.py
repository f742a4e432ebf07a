"""Where the program compiles: one scope, one clock, one trim.

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

Imports nothing heavy: runtime/health.py pulls this in on host-only nodes.
"""

from __future__ import annotations

import ctypes
import threading
import time
from contextlib import contextmanager

_LOCK = threading.Lock()
#: thread ident -> [finished compile seconds, start of the open scope or None]
_CLOCK: "dict[int, list]" = {}
_TOTAL = [0.0, 0]  # process-wide compile seconds, compile count

try:
    _malloc_trim = ctypes.CDLL(None).malloc_trim
except (OSError, AttributeError):  # not glibc: nothing to give back
    _malloc_trim = None


def trim_host_memory() -> None:
    """Return the C allocator's free pages to the system."""
    if _malloc_trim is not None:
        _malloc_trim(0)


@contextmanager
def compiling():
    """Mark the calling thread as compiling; trims host memory on exit.
    Scopes nest (the warm loop wraps the backend's own first-call stage):
    only the outermost one runs the clock and the trim."""
    ident = threading.get_ident()
    with _LOCK:
        row = _CLOCK.setdefault(ident, [0.0, None])
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
                _TOTAL[0] += dt
                _TOTAL[1] += 1
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


__all__ = ["compiling", "compile_seconds", "totals", "trim_host_memory"]
