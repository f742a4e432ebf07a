"""Adaptive overload control: the brownout controller.

Every robustness seam so far (breakers, chaos, isolation, quarantine)
reacts to *faults*; this module defends the verify/sign planes against
*overload* — arrival rate exceeding device capacity. The controller
consumes three feeds the plane already produces — the flight recorder's
per-lane SLO-miss stream, the scheduler's lane depths, and the device
duty cycle — and walks a hysteretic degradation ladder:

  NORMAL    — nothing engaged.
  B1        — stop waiting for fill: `merge_window_s` goes to zero and
              sheddable-lane `max_wait_s` shrinks, so batches flush at
              whatever size they have instead of padding the queue wait.
  B2        — shed harder: sheddable-lane `max_queue` shrinks (the
              existing shed-oldest valve fires earlier) and admission
              quotas squeeze toward `min_quota` through the
              AdmissionController's brownout-pressure hook, which the
              ReputationTable failure-rate feed already modulates —
              distrusted origins are clamped first.
  B3        — the device serves HIGH lanes only: bulk replay / slasher
              backfill pauses on its run gate and LOW lanes route to
              the host twin (`VerifyScheduler.brownout_route_host`).
  CRITICAL  — HIGH lanes exclusively; every sheddable lane's submits
              resolve dropped at the door, with full accounting (shed
              stat, drop metric, a flight-timeline record attributing
              the shed to the brownout).

Escalation moves ONE level per evaluation tick whenever the window saw
new SLO misses or a lane queue crossed its high-water mark. Recovery is
hysteretic: stepping DOWN one level requires a sustained clean window —
no misses and no depth pressure for `recovery_window_s`, re-armed at
every level — so the controller never flaps between adjacent levels.

End-to-end deadline budgets ride with the controller: `VerifyTicket`
and `SignTicket` carry an absolute deadline stamped at submit, the
scheduler/sign plane shed already-expired tickets before wasting a
device dispatch, and every shed lands on the flight timeline with an
`expired`/`brownout` SLO cause plus the brownout level stamped on the
record (flight.py).

Threading: all mutable controller state lives under one lock; actuator
pokes (scheduler knobs, lane configs, admission pressure, the replay
gate) happen under it too — none of those acquire the scheduler's
condition or the flight lock, so there is no ordering hazard. Feed
reads (which DO take those locks) happen before the controller lock is
taken. `evaluate()` is deterministic given its feeds and an injected
clock; `start()` runs it on a crash-contained daemon thread.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from grandine_tpu.runtime.thread_pool import Priority

#: the CLOSED brownout-level enum, in escalation order. The metrics-
#: cardinality lint rule parses this tuple (like flight.SLO_CAUSES) and
#: rejects any literal `from`/`to` label outside it on
#: `verify_brownout_transitions_total`.
LEVELS = ("normal", "b1", "b2", "b3", "critical")

NORMAL, B1, B2, B3, CRITICAL = LEVELS


class BrownoutController:
    """The hysteretic ladder walker. One per node (runtime/node.py);
    tests drive `evaluate()` directly under a fake clock for
    determinism, production uses `start()`."""

    def __init__(
        self,
        scheduler,
        flight=None,
        sign_plane=None,
        admission=None,
        replay=None,
        metrics=None,
        clock=time.monotonic,
        interval_s: float = 0.25,
        escalate_misses: int = 1,
        depth_high_water: float = 0.5,
        recovery_window_s: float = 5.0,
        escalate_dwell_s: float = 0.0,
        b1_wait_factor: float = 0.25,
        b2_queue_factor: float = 0.25,
        b2_admission_pressure: float = 0.75,
    ) -> None:
        self.scheduler = scheduler
        self.flight = (
            flight if flight is not None
            else getattr(scheduler, "flight", None)
        )
        self.sign_plane = sign_plane
        self.admission = admission
        self.replay = replay
        self.metrics = metrics
        self.clock = clock
        #: controller-thread tick period (start()); immutable after init
        self.interval_s = float(interval_s)
        #: new SLO misses in one window that count as pressure
        self.escalate_misses = max(1, int(escalate_misses))
        #: lane fullness (jobs / max_queue) that counts as pressure even
        #: before the queue wait materializes as an SLO miss
        self.depth_high_water = float(depth_high_water)
        #: the sustained clean window a ONE-level recovery step needs,
        #: re-armed at every level — the anti-flap hysteresis
        self.recovery_window_s = float(recovery_window_s)
        #: minimum dwell at a level before escalating again (0 = one
        #: step per evaluation tick)
        self.escalate_dwell_s = float(escalate_dwell_s)
        self.b1_wait_factor = float(b1_wait_factor)
        self.b2_queue_factor = float(b2_queue_factor)
        self.b2_admission_pressure = float(b2_admission_pressure)

        self._lock = threading.Lock()
        self._idx = 0
        self._since = float(clock())
        #: clean-window arming: recovery may only fire once the clock
        #: passes this mark (re-pushed by every hot observation)
        self._hot_until = float(clock())
        self._miss_seen = 0
        self._transitions: "list[tuple[float, str, str]]" = []
        #: per-level saved baselines, restored on de-escalation
        self._baselines: "dict[str, dict]" = {}
        self._daemon_failures = 0
        self._stop_evt = threading.Event()
        self._thread: "Optional[threading.Thread]" = None

    # ------------------------------------------------------------- feeds

    def _miss_total(self) -> int:
        fl = self.flight
        if fl is None:
            return 0
        misses = fl.slo_misses()
        return sum(c for causes in misses.values() for c in causes.values())

    def _depth_pressure(self) -> float:
        pressure = getattr(self.scheduler, "lane_pressure", None)
        if pressure is None:
            return 0.0
        depths = pressure()
        return max(depths.values()) if depths else 0.0

    def _duty(self) -> float:
        fl = self.flight
        if fl is None:
            return 0.0
        try:
            return float(fl.duty_cycle())
        except Exception:
            return 0.0

    # ---------------------------------------------------------- evaluate

    def evaluate(self, now: "Optional[float]" = None) -> str:
        """One deterministic control tick: read the feeds, walk the
        ladder at most one step, apply/revert actuators. Returns the
        level after the tick. Callers serialize through the controller
        lock, so concurrent ticks cannot tear a transition."""
        now = float(self.clock()) if now is None else float(now)
        misses = self._miss_total()
        pressure = self._depth_pressure()
        with self._lock:
            new = misses - self._miss_seen
            self._miss_seen = misses
            hot = (
                new >= self.escalate_misses
                or pressure >= self.depth_high_water
            )
            if hot:
                self._hot_until = now + self.recovery_window_s
            if hot and self._idx < len(LEVELS) - 1:
                if now - self._since >= self.escalate_dwell_s:
                    self._shift_locked(self._idx + 1, now)
            elif (
                not hot
                and self._idx > 0
                and now >= self._hot_until
                and now - self._since >= self.recovery_window_s
            ):
                self._shift_locked(self._idx - 1, now)
            return LEVELS[self._idx]

    def _shift_locked(self, new_idx: int, now: float) -> None:
        """Move to `new_idx` (always ±1 from the current level),
        engaging or reverting each level's actuators in order."""
        frm = LEVELS[self._idx]
        to = LEVELS[new_idx]
        if new_idx > self._idx:
            for k in range(self._idx + 1, new_idx + 1):
                self._engage_locked(LEVELS[k])
        else:
            for k in range(self._idx, new_idx, -1):
                self._revert_locked(LEVELS[k])
        self._idx = new_idx
        self._since = now
        self._transitions.append((now, frm, to))
        fl = self.flight
        if fl is not None:
            fl.brownout_level = to
        m = self.metrics
        if m is not None:
            m.verify_brownout_level.set(float(new_idx))
            m.verify_brownout_transitions.inc(frm, to)

    # --------------------------------------------------------- actuators

    def _engage_locked(self, level: str) -> None:
        sched = self.scheduler
        if level == B1:
            base: dict = {
                "merge_window_s": getattr(sched, "merge_window_s", 0.0),
                "max_wait_s": {},
            }
            if hasattr(sched, "merge_window_s"):
                sched.merge_window_s = 0.0
            for name, lane in getattr(sched, "lanes", {}).items():
                if lane.shed:
                    base["max_wait_s"][name] = lane.max_wait_s
                    lane.max_wait_s = lane.max_wait_s * self.b1_wait_factor
            self._baselines[B1] = base
        elif level == B2:
            base = {"max_queue": {}}
            for name, lane in getattr(sched, "lanes", {}).items():
                if lane.shed and name != "quarantine":
                    base["max_queue"][name] = lane.max_queue
                    lane.max_queue = max(
                        1, int(lane.max_queue * self.b2_queue_factor)
                    )
            self._baselines[B2] = base
            if self.admission is not None:
                self.admission.set_brownout_pressure(
                    self.b2_admission_pressure
                )
        elif level == B3:
            gate = getattr(self.replay, "run_gate", None)
            if gate is not None:
                gate.clear()
            if hasattr(sched, "brownout_route_host"):
                sched.brownout_route_host = frozenset(
                    n for n, l in sched.lanes.items()
                    if l.priority != Priority.HIGH
                )
        elif level == CRITICAL:
            if hasattr(sched, "brownout_shed_lanes"):
                sched.brownout_shed_lanes = frozenset(
                    n for n, l in sched.lanes.items() if l.shed
                )

    def _revert_locked(self, level: str) -> None:
        sched = self.scheduler
        if level == B1:
            base = self._baselines.pop(B1, None)
            if base is not None:
                if hasattr(sched, "merge_window_s"):
                    sched.merge_window_s = base["merge_window_s"]
                for name, wait in base["max_wait_s"].items():
                    lane = sched.lanes.get(name)
                    if lane is not None:
                        lane.max_wait_s = wait
        elif level == B2:
            base = self._baselines.pop(B2, None)
            if base is not None:
                for name, cap in base["max_queue"].items():
                    lane = sched.lanes.get(name)
                    if lane is not None:
                        lane.max_queue = cap
            if self.admission is not None:
                self.admission.set_brownout_pressure(0.0)
        elif level == B3:
            gate = getattr(self.replay, "run_gate", None)
            if gate is not None:
                gate.set()
            if hasattr(sched, "brownout_route_host"):
                sched.brownout_route_host = frozenset()
        elif level == CRITICAL:
            if hasattr(sched, "brownout_shed_lanes"):
                sched.brownout_shed_lanes = frozenset()

    # ----------------------------------------------------------- queries

    @property
    def level(self) -> str:
        with self._lock:
            return LEVELS[self._idx]

    def transitions(self) -> "list[tuple[float, str, str]]":
        with self._lock:
            return list(self._transitions)

    def status(self) -> dict:
        """Debug-endpoint / bench-summary payload."""
        duty = self._duty()
        pressure = self._depth_pressure()
        with self._lock:
            return {
                "level": LEVELS[self._idx],
                "level_index": self._idx,
                "since": self._since,
                "transitions": len(self._transitions),
                "misses_seen": self._miss_seen,
                "engaged": sorted(self._baselines),
                "daemon_failures": self._daemon_failures,
                "duty_cycle": round(duty, 4),
                "depth_pressure": round(pressure, 4),
            }

    # ------------------------------------------------------------ thread

    def start(self) -> None:
        """Run `evaluate` every `interval_s` on a daemon thread."""
        with self._lock:
            if self._thread is not None:
                return
            self._thread = threading.Thread(
                target=self._run, name="brownout", daemon=True
            )
            t = self._thread
        t.start()

    def _run(self) -> None:
        while not self._stop_evt.wait(self.interval_s):
            # crash containment: one bad tick (a feed raising mid-
            # teardown) must not kill the controller — account it and
            # keep walking the ladder
            try:
                self.evaluate()
            except Exception:
                with self._lock:
                    self._daemon_failures += 1
                if self.metrics is not None:
                    self.metrics.daemon_loop_failures.inc("brownout")

    def stop(self) -> None:
        """Stop the tick thread and revert every engaged level, so a
        node shutdown (or a --no-brownout restart) never strands shrunk
        lane configs or a cleared replay gate."""
        self._stop_evt.set()
        with self._lock:
            t, self._thread = self._thread, None
            while self._idx > 0:
                self._shift_locked(self._idx - 1, float(self.clock()))
        if t is not None:
            t.join(timeout=5)


__all__ = [
    "B1",
    "B2",
    "B3",
    "CRITICAL",
    "LEVELS",
    "NORMAL",
    "BrownoutController",
]
