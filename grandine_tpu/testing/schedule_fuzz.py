"""Deterministic cross-thread schedule fuzzer for the verify plane.

The thread-affinity lint rule (tools/lint/rules/thread_affinity.py)
proves lock coverage statically, but every `# lint: atomic=<attr>:`
annotation is a claim the static analysis cannot check — "this bare
access is safe because of a happens-before edge the lock graph doesn't
see". This module is the dynamic side of that contract: a seeded,
fully deterministic interleaving fuzzer that drives the annotated
objects (plus the other lock-dense runtime structures) through
adversarial schedules and checks their invariants after every run.

How determinism works:

* Exactly ONE thread runs at a time. A controller thread and N worker
  threads hand a baton around via per-worker Event pairs — the
  controller resumes one worker, the worker runs until its step budget
  expires (or it blocks), parks, and the controller picks again.
* Steps are BYTECODE OPCODES, delivered by a per-thread `sys.settrace`
  hook with `f_trace_opcodes` enabled — but only for frames whose code
  lives in the watched module files. Harness code is unwatched, so its
  operations are atomic w.r.t. the schedule; a preemption can land
  between the LOAD and STORE of `self.n = self.n + 1` in watched code,
  which is exactly the window a torn read-modify-write needs.
* All randomness (which worker next, how many opcodes it may run) is
  drawn from ONE `random.Random(seed)` owned by the controller. The
  workers never consult a clock or an RNG, so the full schedule — and
  the sha256 trace hash over every (worker, file, line, opcode) step —
  is a pure function of the seed.
* The scenario objects' real `threading.Lock`/`RLock`/`Event` fields
  are swapped for Fuzz* proxies BEFORE the workers start. A would-block
  acquire parks the worker in a "blocked" state instead of blocking the
  (serialized) scheduler; the controller wakes it when the holder
  releases. Runnable-set-empty with blocked workers remaining is
  reported as a deadlock violation.

`COVERAGE` maps every `atomic=` annotation in the runtime sources to
the scenario that exercises it; tests/test_schedule_fuzz.py fails if an
annotation appears without a backing scenario (or vice versa).
"""

from __future__ import annotations

import hashlib
import os
import random
import sys
import threading
from typing import Callable, Optional

__all__ = [
    "COVERAGE",
    "FuzzEvent",
    "FuzzLock",
    "FuzzRLock",
    "SCENARIOS",
    "ScheduleFuzzer",
    "run_fuzz",
]

_RUNNABLE = "runnable"
_BLOCKED = "blocked"
_FINISHED = "finished"

#: identity of the controller/setup thread for lock bookkeeping
_MAIN = object()


class _FuzzAbort(BaseException):
    """Raised inside workers to unwind them when the run is aborted
    (deadlock, hang, step-budget blown). BaseException so scenario code
    cannot swallow it with `except Exception`."""


class _TickClock:
    """Injectable clock: strictly increasing, schedule-independent-ish
    (ticks advance per call, and calls are serialized by the baton), so
    timestamps never feed nondeterminism back into a trace."""

    def __init__(self, step: float = 1e-4) -> None:
        self.t = 0.0
        self.step = step

    def __call__(self) -> float:
        self.t += self.step
        return self.t


# ---------------------------------------------------------------- workers


class _Worker:
    """One fuzzed thread: a real threading.Thread serialized under the
    controller's baton. `budget` opcodes of watched code per turn."""

    def __init__(self, harness: "ScheduleFuzzer", name: str,
                 fn: Callable[[], None]) -> None:
        self.harness = harness
        self.name = name
        self.fn = fn
        self.state = _RUNNABLE
        self.budget = 0
        self.wake_pred: "Optional[Callable[[], bool]]" = None
        self.blocked_on: "Optional[str]" = None
        self.error: "Optional[BaseException]" = None
        self.resume = threading.Event()
        self.parked = threading.Event()
        self.thread = threading.Thread(
            target=self._main, name=f"fuzz-{name}", daemon=True
        )

    def _main(self) -> None:
        self.harness._by_ident[threading.get_ident()] = self
        try:
            self._wait_resume()
            sys.settrace(self._trace)
            try:
                self.fn()
            finally:
                sys.settrace(None)
        except _FuzzAbort:
            pass
        except BaseException as exc:  # noqa: BLE001 — report, don't mask
            self.error = exc
        finally:
            sys.settrace(None)
            self.state = _FINISHED
            self.parked.set()

    def _wait_resume(self) -> None:
        self.resume.wait()
        self.resume.clear()
        if self.harness._aborted:
            # Unwinding an exception through a frame that is being traced
            # per OPCODE segfaults CPython 3.12 (the abort surfaces inside
            # a watched `with` body). Stop tracing every frame of this
            # thread before the abort starts to unwind.
            sys.settrace(None)
            frame = sys._getframe()
            while frame is not None:
                frame.f_trace = None
                frame.f_trace_opcodes = False
                frame = frame.f_back
            raise _FuzzAbort

    def _park(self) -> None:
        """Hand the baton back and wait to be scheduled again."""
        self.parked.set()
        self._wait_resume()

    def block(self, pred: Callable[[], bool], why: str) -> None:
        """Park in the blocked state until `pred` goes true (checked by
        the controller between turns)."""
        self.state = _BLOCKED
        self.wake_pred = pred
        self.blocked_on = why
        self.harness._note(f"block|{self.name}|{why}")
        self._park()
        self.blocked_on = None

    # trace hooks — installed via sys.settrace in THIS thread only

    def _trace(self, frame, event, arg):
        if frame.f_code.co_filename not in self.harness.watched:
            return None
        frame.f_trace_opcodes = True
        return self._local

    def _local(self, frame, event, arg):
        if event == "opcode":
            self.harness._on_step(self, frame)
        return self._local


# ----------------------------------------------------------- lock proxies


class FuzzLock:
    """Drop-in for threading.Lock on a fuzzed object. Acquire from a
    worker parks it when contended; acquire from the controller (setup
    or invariant checks, when no worker runs) is uncontended by
    construction."""

    _reentrant = False

    def __init__(self, harness: "ScheduleFuzzer", name: str = "lock") -> None:
        self._h = harness
        self.name = name
        self._owner = None
        self._depth = 0

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        me = self._h._current() or _MAIN
        while True:
            if self._owner is None:
                self._owner = me
                self._depth = 1
                return True
            if self._reentrant and self._owner is me:
                self._depth += 1
                return True
            if not blocking:
                return False
            if me is _MAIN:
                raise RuntimeError(
                    f"{self.name}: controller would block — a worker "
                    f"still holds the lock after the run"
                )
            me.block(lambda: self._owner is None, f"lock:{self.name}")

    def release(self) -> None:
        me = self._h._current() or _MAIN
        if self._owner is not me:
            if self._h._aborted:
                return  # unwinding after abort: tolerate
            raise RuntimeError(f"{self.name}: release by non-owner")
        self._depth -= 1
        if self._depth <= 0:
            self._owner = None
            self._depth = 0

    def locked(self) -> bool:
        return self._owner is not None

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self.release()


class FuzzRLock(FuzzLock):
    """Reentrant variant (DevicePubkeyRegistry's lock)."""

    _reentrant = True


class FuzzEvent:
    """Drop-in for threading.Event: wait() parks the worker instead of
    sleeping, so the happens-before edge annotations rely on is visible
    to the schedule."""

    def __init__(self, harness: "ScheduleFuzzer") -> None:
        self._h = harness
        self._flag = False

    def is_set(self) -> bool:
        return self._flag

    def set(self) -> None:
        self._flag = True

    def clear(self) -> None:
        self._flag = False

    def wait(self, timeout: "Optional[float]" = None) -> bool:
        me = self._h._current()
        if me is None:
            return self._flag
        while not self._flag:
            me.block(lambda: self._flag, "event")
        return True


# --------------------------------------------------------------- harness


class ScheduleFuzzer:
    """One seeded run: add workers, then `run()`. The result dict holds
    the violation list (empty == clean), the sha256 trace hash (equal
    for equal seeds), and every preemption point the schedule hit."""

    def __init__(
        self,
        seed: int,
        watched: "list[str]",
        max_quantum: int = 6,
        max_steps: int = 200_000,
        hang_timeout_s: float = 30.0,
    ) -> None:
        self.seed = int(seed)
        self.rng = random.Random(self.seed)
        self.watched = {os.path.abspath(p) for p in watched}
        self.max_quantum = max(1, int(max_quantum))
        self.max_steps = int(max_steps)
        self.hang_timeout_s = float(hang_timeout_s)
        self.workers: "list[_Worker]" = []
        self.violations: "list[dict]" = []
        self.preempt_points: "set[tuple[str, int]]" = set()
        self.steps = 0
        self.switches = 0
        self._hash = hashlib.sha256()
        self._by_ident: "dict[int, _Worker]" = {}
        self._aborted = False

    # -- plumbing used by workers/locks (single-runner, so no locking)

    def _current(self) -> "Optional[_Worker]":
        return self._by_ident.get(threading.get_ident())

    def _note(self, event: str) -> None:
        self._hash.update(event.encode())
        self._hash.update(b";")

    def _on_step(self, worker: _Worker, frame) -> None:
        if self._aborted:
            raise _FuzzAbort
        self.steps += 1
        if self.steps > self.max_steps:
            self.violations.append({
                "kind": "step-budget",
                "detail": f"exceeded {self.max_steps} steps — livelock?",
            })
            self._abort()
            raise _FuzzAbort
        code = frame.f_code
        lineno = frame.f_lineno or 0  # some opcodes carry no line
        self._note(
            f"{worker.name}|{os.path.basename(code.co_filename)}"
            f"|{lineno}|{frame.f_lasti}"
        )
        worker.budget -= 1
        if worker.budget <= 0:
            self.preempt_points.add(
                (os.path.basename(code.co_filename), lineno)
            )
            worker._park()

    # -- controller

    def add_worker(self, name: str, fn: Callable[[], None]) -> None:
        self.workers.append(_Worker(self, name, fn))

    def lock(self, name: str) -> FuzzLock:
        return FuzzLock(self, name)

    def rlock(self, name: str) -> FuzzRLock:
        return FuzzRLock(self, name)

    def event(self) -> FuzzEvent:
        return FuzzEvent(self)

    def _abort(self) -> None:
        self._aborted = True
        for w in self.workers:
            if w.state != _FINISHED:
                w.state = _RUNNABLE
                w.resume.set()

    def run(self) -> dict:
        for w in self.workers:
            w.thread.start()
        while True:
            for w in self.workers:
                if (
                    w.state == _BLOCKED
                    and w.wake_pred is not None
                    and w.wake_pred()
                ):
                    w.state = _RUNNABLE
                    w.wake_pred = None
            runnable = [w for w in self.workers if w.state == _RUNNABLE]
            if not runnable:
                blocked = {
                    w.name: w.blocked_on
                    for w in self.workers if w.state == _BLOCKED
                }
                if blocked:
                    self.violations.append({
                        "kind": "deadlock", "detail": repr(blocked),
                    })
                    self._abort()
                break
            w = runnable[self.rng.randrange(len(runnable))]
            w.budget = self.rng.randint(1, self.max_quantum)
            self.switches += 1
            self._note(f"pick|{w.name}|{w.budget}")
            w.parked.clear()
            w.resume.set()
            if not w.parked.wait(self.hang_timeout_s):
                self.violations.append({
                    "kind": "hung",
                    "detail": f"{w.name} did not yield within "
                              f"{self.hang_timeout_s}s — real blocking "
                              f"primitive left unproxied?",
                })
                self._abort()
                break
        for w in self.workers:
            w.thread.join(timeout=5.0)
            if w.error is not None:
                self.violations.append({
                    "kind": "exception",
                    "detail": f"{w.name}: {w.error!r}",
                })
        return {
            "seed": self.seed,
            "steps": self.steps,
            "switches": self.switches,
            "trace_sha256": self._hash.hexdigest(),
            "preemption_points": sorted(
                [f, ln] for f, ln in self.preempt_points
            ),
            "violations": self.violations,
        }


# -------------------------------------------------------------- scenarios


def _invariant(res: dict, scenario: str, failures: "list[str]") -> dict:
    for msg in failures:
        res["violations"].append({
            "kind": "invariant", "detail": f"{scenario}: {msg}",
        })
    return res


def scenario_ticket_verdict(seed: int, **kw) -> dict:
    """Backs `atomic=_ok` on VerifyTicket: two racing settlers, a
    result() reader gated on the Event, and a racing add_callback. The
    happens-before claim is that any reader passing the Event gate sees
    the winning settler's verdict, and callbacks fire exactly once."""
    import grandine_tpu.runtime.verify_scheduler as vs

    fz = ScheduleFuzzer(seed, watched=[vs.__file__], **kw)
    t = vs.VerifyTicket("attestation", origin="peer:fuzz")
    t._lock = fz.lock("ticket._lock")
    t._event = fz.event()
    fired: "list[bool]" = []
    seen: dict = {}

    def settle_ok() -> None:
        t._resolve(True)

    def settle_drop() -> None:
        t._resolve(False, dropped=True)

    def reader() -> None:
        seen["result"] = t.result(timeout=5.0)

    def register() -> None:
        t.add_callback(lambda tk: fired.append(tk.ok))

    fz.add_worker("settle_ok", settle_ok)
    fz.add_worker("settle_drop", settle_drop)
    fz.add_worker("reader", reader)
    fz.add_worker("register", register)
    res = fz.run()

    bad: "list[str]" = []
    if not t.done():
        bad.append("ticket never settled")
    if (t.ok, t.dropped) not in {(True, False), (False, True)}:
        bad.append(f"mixed verdict: ok={t.ok} dropped={t.dropped}")
    if len(fired) != 1:
        bad.append(f"callback fired {len(fired)} times (want 1)")
    elif fired[0] != t.ok:
        bad.append(f"callback saw ok={fired[0]}, settled ok={t.ok}")
    if "result" not in seen:
        bad.append("reader never returned")
    elif seen["result"] != t.ok:
        bad.append(f"reader saw {seen['result']}, settled ok={t.ok}")
    return _invariant(res, "ticket_verdict", bad)


def scenario_sign_ticket(seed: int, **kw) -> dict:
    """Backs `atomic=_sig` on SignTicket (runtime/sign_plane.py): two
    racing settlers (a signature vs a drop), a result() reader gated on
    the Event, and a racing add_callback. The happens-before claim is
    that any reader passing the Event gate sees the winning settler's
    outcome — the signature bytes or the dropped RuntimeError — and
    callbacks fire exactly once."""
    import grandine_tpu.runtime.sign_plane as sp

    fz = ScheduleFuzzer(seed, watched=[sp.__file__], **kw)
    t = sp.SignTicket("attestation")
    t._lock = fz.lock("sign_ticket._lock")
    t._event = fz.event()
    fired: "list[bool]" = []
    seen: dict = {}

    def settle_sig() -> None:
        t._resolve(b"fuzz-signature")

    def settle_drop() -> None:
        t._resolve(None, dropped=True)

    def reader() -> None:
        try:
            seen["result"] = t.result(timeout=5.0)
        except RuntimeError:
            seen["result"] = "dropped"

    def register() -> None:
        t.add_callback(lambda tk: fired.append(tk.dropped))

    fz.add_worker("settle_sig", settle_sig)
    fz.add_worker("settle_drop", settle_drop)
    fz.add_worker("reader", reader)
    fz.add_worker("register", register)
    res = fz.run()

    bad: "list[str]" = []
    if not t.done():
        bad.append("ticket never settled")
    if (t._sig, t.dropped) not in {(b"fuzz-signature", False), (None, True)}:
        bad.append(f"mixed outcome: sig={t._sig!r} dropped={t.dropped}")
    if len(fired) != 1:
        bad.append(f"callback fired {len(fired)} times (want 1)")
    elif fired[0] != t.dropped:
        bad.append(f"callback saw dropped={fired[0]}, settled {t.dropped}")
    if "result" not in seen:
        bad.append("reader never returned")
    elif t.dropped and seen["result"] != "dropped":
        bad.append(f"reader saw {seen['result']!r} on a dropped ticket")
    elif not t.dropped and seen["result"] != t._sig:
        bad.append(f"reader saw {seen['result']!r}, settled {t._sig!r}")
    return _invariant(res, "sign_ticket", bad)


def scenario_flight_ring(seed: int, **kw) -> dict:
    """FlightRecorder under concurrent commit/snapshot/duty traffic: the
    ring, aggregate counters, origin table, and the duty-cycle integral
    must stay coherent."""
    import grandine_tpu.runtime.flight as fl

    fz = ScheduleFuzzer(seed, watched=[fl.__file__], **kw)
    fr = fl.FlightRecorder(capacity=16, origin_top_k=4, clock=_TickClock())
    fr._lock = fz.lock("flight._lock")
    fr.origins._lock = fz.lock("origins._lock")
    n = 5

    def writer(lane: str, origin: str) -> Callable[[], None]:
        def fn() -> None:
            for i in range(n):
                bf = fr.begin_batch(lane, "verify_fixed", items=3,
                                    queue_wait_s=0.01)
                bf.note_device(0.001)
                if i % 2:
                    bf.note_fault("watchdog")
                    bf.note_origin_failure(origin)
                bf.finish(i % 2 == 0)
        return fn

    def reader() -> None:
        for _ in range(4):
            fr.snapshot()
            fr.summary()
            fr.duty_cycle()
            fr.slo_misses()

    def duty() -> None:
        for _ in range(n):
            fr.device_enter()
            fr.device_exit()

    fz.add_worker("writer_att", writer("attestation", "peer:a"))
    fz.add_worker("writer_blk", writer("block", "peer:b"))
    fz.add_worker("reader", reader)
    fz.add_worker("duty", duty)
    res = fz.run()

    bad: "list[str]" = []
    s = fr.summary()
    if s["batches"] != 2 * n:
        bad.append(f"batches={s['batches']} (want {2 * n}) — lost commit")
    if s["records_total"] != 2 * n:
        bad.append(f"records_total={s['records_total']} (want {2 * n})")
    if s["faults"].get("watchdog", 0) != 2 * (n // 2):
        bad.append(f"faults={s['faults']} — lost fault count")
    if fr._inflight != 0:
        bad.append(f"inflight={fr._inflight} after balanced enter/exit")
    origins = {r["origin"]: r["failures"] for r in fr.origins.snapshot()}
    if origins != {"peer:a": n // 2, "peer:b": n // 2}:
        bad.append(f"origin table {origins} — lost attribution")
    return _invariant(res, "flight_ring", bad)


def scenario_breaker_walk(seed: int, **kw) -> dict:
    """CircuitBreaker legal-state walk: faulters, succeeders, and a
    probe installer race; the breaker must stay in a legal state with
    transition counters that balance."""
    import grandine_tpu.runtime.health as hl

    fz = ScheduleFuzzer(seed, watched=[hl.__file__], **kw)
    br = hl.CircuitBreaker(
        name="fuzz", fault_threshold=2, window=4, fault_rate=0.5,
        backoff_initial_s=0.0, backoff_max_s=0.0, jitter_frac=0.0,
        clock=_TickClock(), rng=random.Random(seed),
    )
    br._lock = fz.lock("breaker._lock")

    def probe() -> bool:
        return True

    def faulter() -> None:
        for _ in range(4):
            br.allow()
            br.record_fault("settle")

    def succeeder() -> None:
        for _ in range(4):
            br.allow()
            br.record_success()

    def prober() -> None:
        for _ in range(3):
            br.ensure_probe(probe)
            br.allow()

    fz.add_worker("faulter", faulter)
    fz.add_worker("succeeder", succeeder)
    fz.add_worker("prober", prober)
    res = fz.run()

    bad: "list[str]" = []
    final = br.state
    if final not in (hl.CLOSED, hl.OPEN, hl.HALF_OPEN):
        bad.append(f"illegal state {final!r}")
    expect = 0 if final == hl.CLOSED else 1
    if br.stats["opens"] - br.stats["closes"] != expect:
        bad.append(
            f"state {final} with opens={br.stats['opens']} "
            f"closes={br.stats['closes']} — transition counters torn"
        )
    if len(br._window) > br.window_size:
        bad.append(f"window overflow: {len(br._window)}")
    if br._consecutive < 0:
        bad.append(f"negative consecutive: {br._consecutive}")
    if br.probe is not probe:
        bad.append("ensure_probe lost the first-writer race to nobody")
    return _invariant(res, "breaker_walk", bad)


def scenario_registry_lifecycle(seed: int, **kw) -> dict:
    """DevicePubkeyRegistry ensure/mark_stale/invalidate churn under the
    RLock, with the numpy/JAX upload seams stubbed so the fuzz stays
    kernel-free. Hit/miss accounting must balance and the visible set
    must always be one of the ensured tuples (or empty)."""
    import grandine_tpu.tpu.registry as rg

    fz = ScheduleFuzzer(seed, watched=[rg.__file__], **kw)
    reg = rg.DevicePubkeyRegistry()
    reg._lock = fz.rlock("registry._lock")
    # device-upload seams: called only under the (fuzz) RLock, so plain
    # state pokes preserve ensure()'s locked-section semantics
    reg._append = lambda pubkeys, start: None
    reg._refresh = lambda pubkeys: setattr(reg, "_pubkeys", pubkeys)

    set_a = (b"k1", b"k2")
    set_b = (b"k1", b"k2", b"k3")

    def ensure(pubkeys: tuple) -> Callable[[], None]:
        def fn() -> None:
            for _ in range(3):
                reg.ensure(pubkeys)
        return fn

    def churn() -> None:
        reg.mark_stale()
        reg.invalidate()
        reg.mark_stale()

    def reader() -> None:
        for _ in range(4):
            reg.count
            reg.capacity

    fz.add_worker("ensure_a", ensure(set_a))
    fz.add_worker("ensure_b", ensure(set_b))
    fz.add_worker("churn", churn)
    fz.add_worker("reader", reader)
    res = fz.run()

    bad: "list[str]" = []
    if reg._pubkeys not in (None, set_a, set_b):
        bad.append(f"torn pubkey set: {reg._pubkeys!r}")
    if reg.count not in (0, len(set_a), len(set_b)):
        bad.append(f"impossible count {reg.count}")
    total = reg.stats["hits"] + reg.stats["misses"]
    if total != 6:
        bad.append(f"hits+misses={total} (want 6) — lost ensure() bump")
    if reg._stale not in (True, False):
        bad.append(f"stale flag corrupt: {reg._stale!r}")
    return _invariant(res, "registry_lifecycle", bad)


def scenario_cached_pubkey(seed: int, **kw) -> dict:
    """CachedPublicKey first-use fill race: concurrent decompress()
    callers on one shared key must decompress exactly once and all
    observe the same object. The pre-lock code's unlocked check-then-set
    let two threads both see None and both pay the pure-Python G1
    decompress — this scenario preempts between the check and the set
    and fails on any duplicate fill or torn read."""
    import grandine_tpu.crypto.bls as cb

    fz = ScheduleFuzzer(seed, watched=[cb.__file__], **kw)
    key = cb.CachedPublicKey(b"\x99" * 48)
    key._lock = fz.lock("cached_pubkey._lock")

    calls = [0]
    sentinel = object()
    real_from_bytes = cb.PublicKey.from_bytes

    def counting_from_bytes(data: bytes):
        calls[0] += 1
        return sentinel

    seen: "list[object]" = []

    def reader() -> None:
        for _ in range(3):
            seen.append(key.decompress())

    cb.PublicKey.from_bytes = staticmethod(counting_from_bytes)
    try:
        fz.add_worker("reader_a", reader)
        fz.add_worker("reader_b", reader)
        fz.add_worker("reader_c", reader)
        res = fz.run()
    finally:
        cb.PublicKey.from_bytes = real_from_bytes

    bad: "list[str]" = []
    if calls[0] != 1:
        bad.append(
            f"from_bytes ran {calls[0]} times (want 1) — unlocked "
            "check-then-set let two fills race"
        )
    if any(obj is not sentinel for obj in seen):
        bad.append("a reader observed a torn/foreign decompressed value")
    if key._decompressed is not sentinel:
        bad.append("cached value lost after the fill")
    return _invariant(res, "cached_pubkey", bad)


def scenario_brownout_ladder(seed: int, **kw) -> dict:
    """BrownoutController ladder walk under concurrent evaluate() calls:
    pressure feeders push SLO misses into a stub flight recorder while
    several workers tick the controller. The ladder must only ever move
    one adjacent step per transition, the engaged-actuator set must
    match the level exactly (a torn _shift would strand a shrunk lane
    config at NORMAL or skip an engage on the way up), and replaying
    the transition log from NORMAL must land on the final level."""
    import grandine_tpu.runtime.brownout as bo
    from grandine_tpu.runtime.thread_pool import Priority

    class _StubLane:
        def __init__(self, priority, shed):
            self.priority = priority
            self.shed = shed
            self.max_wait_s = 1.0
            self.max_queue = 64

    class _StubSched:
        def __init__(self):
            self.merge_window_s = 0.5
            self.lanes = {
                "high": _StubLane(Priority.HIGH, False),
                "low": _StubLane(Priority.LOW, True),
            }
            self.brownout_route_host = frozenset()
            self.brownout_shed_lanes = frozenset()
            self.depth = 0.0

        def lane_pressure(self):
            return {"low": self.depth}

    class _StubFlight:
        def __init__(self):
            self.miss = 0
            self.brownout_level = "normal"

        def slo_misses(self):
            return {"low": {"queue_wait": self.miss}}

        def duty_cycle(self):
            return 0.0

    sched = _StubSched()
    flight = _StubFlight()
    ctrl = bo.BrownoutController(
        sched, flight=flight, clock=_TickClock(),
        recovery_window_s=3e-4, escalate_dwell_s=0.0,
    )
    fz = ScheduleFuzzer(seed, watched=[bo.__file__], **kw)
    ctrl._lock = fz.lock("brownout._lock")

    def pressurize() -> None:
        for _ in range(5):
            flight.miss += 1  # harness code: atomic w.r.t. the schedule
            ctrl.evaluate()

    def cooldown() -> None:
        for _ in range(6):
            ctrl.evaluate()

    fz.add_worker("pressure_a", pressurize)
    fz.add_worker("pressure_b", pressurize)
    fz.add_worker("cooler", cooldown)
    res = fz.run()

    bad: "list[str]" = []
    final = ctrl._idx
    if not 0 <= final < len(bo.LEVELS):
        bad.append(f"level index {final} outside the ladder")
    replay_idx = 0
    for _t, frm, to in ctrl._transitions:
        if frm != bo.LEVELS[replay_idx]:
            bad.append(
                f"transition {frm}->{to} does not chain from "
                f"{bo.LEVELS[replay_idx]} — a torn _shift"
            )
            break
        step = bo.LEVELS.index(to) - bo.LEVELS.index(frm)
        if abs(step) != 1:
            bad.append(f"non-adjacent transition {frm}->{to}")
            break
        replay_idx = bo.LEVELS.index(to)
    else:
        if replay_idx != final:
            bad.append(
                f"transition log replays to {bo.LEVELS[replay_idx]} "
                f"but controller sits at {bo.LEVELS[final]}"
            )
    want_engaged = sorted(
        lvl for lvl in (bo.B1, bo.B2)
        if final >= bo.LEVELS.index(lvl)
    )
    if sorted(ctrl._baselines) != want_engaged:
        bad.append(
            f"engaged baselines {sorted(ctrl._baselines)} != "
            f"{want_engaged} for level {bo.LEVELS[final]}"
        )
    if final < 1 and sched.merge_window_s != 0.5:
        bad.append("merge_window_s not restored at NORMAL")
    if (final >= 3) != bool(sched.brownout_route_host):
        bad.append("brownout_route_host inconsistent with level")
    if (final >= 4) != bool(sched.brownout_shed_lanes):
        bad.append("brownout_shed_lanes inconsistent with level")
    if flight.brownout_level != bo.LEVELS[final] and ctrl._transitions:
        bad.append(
            f"flight stamp {flight.brownout_level!r} lags level "
            f"{bo.LEVELS[final]!r}"
        )
    return _invariant(res, "brownout_ladder", bad)


SCENARIOS: "dict[str, Callable[..., dict]]" = {
    "ticket_verdict": scenario_ticket_verdict,
    "sign_ticket": scenario_sign_ticket,
    "flight_ring": scenario_flight_ring,
    "breaker_walk": scenario_breaker_walk,
    "registry_lifecycle": scenario_registry_lifecycle,
    "cached_pubkey": scenario_cached_pubkey,
    "brownout_ladder": scenario_brownout_ladder,
}

#: every `# lint: atomic=<attr>:` annotation in the runtime sources maps
#: to the scenario whose invariants back it. Key format:
#: "<module basename>.<Class>.<attr>". tests/test_schedule_fuzz.py
#: cross-checks this against the annotations the lint rule actually
#: parses — an annotation without a scenario (or a stale entry here)
#: fails the suite.
COVERAGE: "dict[str, str]" = {
    "verify_scheduler.VerifyTicket._ok": "ticket_verdict",
    "sign_plane.SignTicket._sig": "sign_ticket",
}


def run_fuzz(
    seeds=(0, 1, 2),
    scenarios: "Optional[list[str]]" = None,
    max_quantum: int = 6,
    max_steps: int = 200_000,
) -> dict:
    """Run every scenario under every seed; aggregate violations, the
    preemption-point union, and the per-(scenario, seed) trace hashes
    (equal seeds reproduce equal hashes — the determinism contract)."""
    names = sorted(SCENARIOS) if scenarios is None else list(scenarios)
    traces: "dict[str, str]" = {}
    union: "set[tuple[str, int]]" = set()
    violations: "list[dict]" = []
    steps = switches = 0
    for seed in seeds:
        for name in names:
            res = SCENARIOS[name](
                seed, max_quantum=max_quantum, max_steps=max_steps
            )
            traces[f"{name}:{seed}"] = res["trace_sha256"]
            union.update((f, ln) for f, ln in res["preemption_points"])
            for v in res["violations"]:
                violations.append({"scenario": name, "seed": seed, **v})
            steps += res["steps"]
            switches += res["switches"]
    return {
        "seeds": list(seeds),
        "scenarios": names,
        "steps": steps,
        "switches": switches,
        "preemption_points": len(union),
        "violations": violations,
        "traces": traces,
    }
