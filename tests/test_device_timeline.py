"""The device timeline (runtime/profiler.py): every call stamped by the
program, busy from max(its dispatch, the previous call's end) to its
output being ready; each idle stretch charged to what held back the call
that ended it; the stages' CPU clock beside their wall clock; the
interpreter's collections counted and put on the timeline. No kernel: the
calls' outputs are stand-ins whose `block_until_ready` waits on an event
the test sets.
"""

import gc
import threading
import time

import pytest

from benchmark import observe
from grandine_tpu import tracing
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime import profiler as P
from grandine_tpu.tracing import Tracer

KERNEL = "agg_fast_verify_msm_idx"
#: what a wake of the watcher may add to a stamp on a loaded CPU
SLACK = 0.03


class Out:
    """A call's output: ready once the test sets it."""

    def __init__(self) -> None:
        self.ready = threading.Event()

    def block_until_ready(self):
        assert self.ready.wait(10.0), "the test never set this output"
        return self


@pytest.fixture
def quiet():
    """No automatic collection inside a test that times idle stretches,
    unless the test makes one."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def settled(prof, calls: int, timeout: float = 5.0) -> None:
    end = time.monotonic() + timeout
    while sum(r["calls"] for r in prof.summary()["device_seconds"]) < calls:
        assert time.monotonic() < end, prof.summary()
        time.sleep(0.002)


def run_calls(prof, gaps, busy=0.02):
    """Calls one after another: each dispatched `gap` seconds after the
    previous one is ready (a gap of None: dispatched while it still
    runs), busy `busy` seconds. Returns (first dispatch, last ready)."""
    first, outs = None, []
    for i, gap in enumerate(gaps):
        if gap is not None and outs:
            outs[-1].ready.set()
            settled(prof, i)
            time.sleep(gap)
        out = Out()
        t = time.perf_counter()
        prof.dispatched(KERNEL, out, 64)
        first = t if first is None else first
        if outs and gap is None:
            time.sleep(busy)
            outs[-1].ready.set()
        outs.append(out)
        time.sleep(busy)
    outs[-1].ready.set()
    settled(prof, len(gaps))
    return first, time.perf_counter()


def busy_and_idle(metrics):
    text = observe.parse_exposition(metrics.expose())
    busy = sum(v for (n, _), v in text.items()
               if n == "verify_device_seconds_total")
    idle = {dict(lab)["cause"]: v for (n, lab), v in text.items()
            if n == "verify_device_idle_seconds_total"}
    return busy, idle


# ---------------------------------------------------------- the stamps


def test_back_to_back_calls_leave_no_idle_and_tile_the_interval(quiet):
    prof = P.KernelProfiler()
    first, last = run_calls(prof, [None] * 5)
    assert prof.idle_seconds() == {}
    busy = sum(prof.device_seconds().values())
    assert busy == pytest.approx(last - first, abs=SLACK)


def test_busy_plus_idle_is_first_dispatch_to_last_ready(quiet):
    m = Metrics()
    prof = P.KernelProfiler(metrics=m)
    first, last = run_calls(prof, [0.0, 0.03, None, 0.05, 0.01])
    busy = sum(prof.device_seconds().values())
    idle = sum(prof.idle_seconds().values())
    assert idle == pytest.approx(0.09, abs=2 * SLACK)
    assert busy + idle == pytest.approx(last - first, abs=SLACK)
    # the exposition holds the same seconds, every cause's series
    # present from the start
    exp_busy, exp_idle = busy_and_idle(m)
    assert exp_busy == pytest.approx(busy)
    assert set(exp_idle) == set(P.IDLE_CAUSES)
    assert sum(exp_idle.values()) >= idle


def test_device_seconds_grow_by_busy_not_by_a_host_delta(quiet):
    """A call dispatched 50 ms after the host began its work and ready
    30 ms after its dispatch: the counter grows by the 30 ms the device
    ran, where the flight record's host delta would say 80."""
    m = Metrics()
    prof = P.KernelProfiler(metrics=m)
    out = Out()
    time.sleep(0.05)  # host work before the dispatch
    prof.dispatched(KERNEL, out, 64)
    time.sleep(0.03)
    out.ready.set()
    settled(prof, 1)
    grown = m.verify_device_seconds.labels(KERNEL, "bls").value
    assert 0.03 <= grown < 0.03 + SLACK


def test_the_device_span_hangs_under_the_dispatching_threads_span(quiet):
    tracer = Tracer()
    prof = P.KernelProfiler()
    out = Out()
    with tracer.span("verify_batch") as root:
        prof.dispatched(KERNEL, out, 47, tracer)
    time.sleep(0.02)
    out.ready.set()
    settled(prof, 1)
    (span,) = [s for s in tracer.finished_spans() if s.name == "device"]
    assert (span.parent_id, span.trace_id) == (root.span_id, root.trace_id)
    assert span.attrs["kernel"] == KERNEL and span.attrs["bucket"] == 64
    assert span.attrs["idle_before_s"] == 0.0
    assert span.thread_name == "device-timeline"
    assert 0.02 <= span.duration < 0.02 + SLACK
    assert "ph" in span.to_chrome_event()


# ---------------------------------------------------- idle by its cause


def phases_around(cause: str, lo: float, hi: float):
    """The firehose's phases of a first pass, placed so that the one
    named `cause` holds all of [lo, hi] (`traffic`: all start after)."""
    order = ["collect", "hold", "pool_wait", "prevalidate", "host_prep"]
    at = order.index(cause) if cause in order else -1
    starts = []
    for i, name in enumerate(order):
        if i <= at:
            starts.append((name, lo - 0.001 * (at - i + 1)))
        else:
            starts.append((name, hi + 10.0 + i))
    return starts


@pytest.mark.parametrize("cause", ["traffic", "collect", "hold",
                                   "pool_wait", "prevalidate", "host_prep",
                                   "descent"])
def test_a_gap_is_charged_to_the_phase_that_holds_it(quiet, cause):
    m = Metrics()
    prof = P.KernelProfiler(metrics=m)
    first = Out()
    prof.dispatched(KERNEL, first, 64)
    first.ready.set()
    settled(prof, 1)
    lo = time.perf_counter()
    time.sleep(0.04)
    hi = time.perf_counter() + 1.0  # the dispatch comes before it
    phases = (("descent", float("-inf")),) if cause == "descent" else (
        phases_around(cause, lo - SLACK, hi))
    second = Out()
    with P.dispatch_phases(*phases):
        prof.dispatched(KERNEL, second, 64)
    second.ready.set()
    settled(prof, 2)
    idle = prof.idle_seconds()
    assert list(idle) == [cause]
    assert 0.04 <= idle[cause] < 0.04 + SLACK
    assert m.verify_device_idle_seconds.labels(cause).value == idle[cause]


def test_a_call_with_no_phases_charges_other(quiet):
    prof = P.KernelProfiler()
    run_calls(prof, [0.0, 0.03])
    idle = prof.idle_seconds()
    assert list(idle) == ["other"] and idle["other"] >= 0.03


def test_a_collection_inside_a_gap_goes_to_gc(quiet):
    prof = P.KernelProfiler()
    P.watch_collections()
    first = Out()
    prof.dispatched(KERNEL, first, 64)
    first.ready.set()
    settled(prof, 1)
    time.sleep(0.01)
    junk = [[i] for i in range(200_000)]  # something to walk
    t0 = time.perf_counter()
    gc.collect()
    pause = time.perf_counter() - t0
    del junk
    time.sleep(0.01)
    now = time.perf_counter()
    second = Out()
    with P.dispatch_phases(("prevalidate", now - 10.0)):
        prof.dispatched(KERNEL, second, 64)
    second.ready.set()
    settled(prof, 2)
    idle = prof.idle_seconds()
    assert set(idle) == {"gc", "prevalidate"}
    assert 0.5 * pause <= idle["gc"] <= pause
    assert idle["prevalidate"] >= 0.02


@pytest.mark.parametrize("lo,hi,phases,pauses,want", [
    (0.0, 1.0, None, [], {"other": 1.0}),
    (0.0, 1.0, (("collect", 0.5),), [], {"traffic": 0.5, "collect": 0.5}),
    (2.0, 3.0, (("collect", 0.0), ("hold", 1.0), ("pool_wait", 2.5),
                ("prevalidate", 2.75), ("host_prep", 2.9)), [],
     {"hold": 0.5, "pool_wait": 0.25, "prevalidate": 0.15,
      "host_prep": 0.1}),
    (0.0, 1.0, (("host_prep", 0.0),), [(0.2, 0.3), (0.9, 1.5)],
     {"gc": 0.2, "host_prep": 0.8}),
    (0.0, 1.0, None, [(-1.0, 2.0)], {"gc": 1.0}),
    (0.0, 1.0, (("descent", float("-inf")),), [(0.5, 0.6)],
     {"descent": 0.9, "gc": 0.1}),
], ids=["none", "arrival", "pipeline", "collections", "all_gc", "descent"])
def test_split_idle_sums_to_the_stretch(lo, hi, phases, pauses, want):
    got = P.split_idle(lo, hi, phases, pauses)
    assert set(got) == set(want)
    for cause, seconds in want.items():
        assert got[cause] == pytest.approx(seconds)
    assert sum(got.values()) == pytest.approx(hi - lo)


def test_the_open_stretch_is_charged_when_the_counters_are_read(quiet):
    """Busy and idle seconds tile the time between two readings of the
    counters, even where the device idles at the end of it."""
    m = Metrics()
    prev = P.get_profiler()
    prof = P.set_profiler(P.KernelProfiler(metrics=m))
    try:
        run_calls(prof, [0.0])
        b0, i0 = busy_and_idle(m)
        t0 = time.perf_counter()
        run_calls(prof, [0.02, 0.02])
        time.sleep(0.05)  # idle at the end, no call ends it
        b1, i1 = busy_and_idle(m)
        span = time.perf_counter() - t0
        grown = (b1 - b0) + sum(i1.values()) - sum(i0.values())
        assert grown == pytest.approx(span, abs=SLACK)
        assert i1["other"] - i0["other"] >= 0.05
    finally:
        P.set_profiler(prev)


# ------------------------------------------------ the stages' CPU clock


@pytest.mark.parametrize("work", ["sleep", "spin"])
def test_a_stage_reads_its_threads_cpu_beside_its_wall_clock(work):
    m, tracer = Metrics(), Tracer()
    with tracing.stage(tracer, m, "host_prep", "attestation",
                       op="prevalidate") as span:
        c0 = time.thread_time()
        if work == "sleep":
            time.sleep(0.05)
        else:  # 50 ms of this thread's own CPU, however loaded the host
            while time.thread_time() - c0 < 0.05:
                pass
    wall = m.verify_stage_seconds.labels(
        "host_prep", "attestation", "prevalidate").sum
    cpu = m.verify_stage_cpu_seconds.labels(
        "host_prep", "attestation", "prevalidate").value
    assert span.attrs["cpu_s"] == cpu
    if work == "sleep":
        assert cpu < 0.01 and wall >= 0.05
    else:
        assert 0.05 <= cpu <= wall + 0.001
    assert "verify_stage_cpu_seconds_total{" in m.expose()


# ------------------------------------------------- the collections


def test_a_collection_moves_both_counters_and_leaves_a_span():
    m, tracer = Metrics(), Tracer()
    P.watch_collections(m, tracer)
    try:
        before = observe.parse_exposition(m.expose())
        with tracer.span("verify_batch") as root:
            gc.collect()
        after = observe.parse_exposition(m.expose())
        for name in ("process_gc_collections_total",
                     "process_gc_pause_seconds_total"):
            assert observe.series_delta(before, after, name,
                                        generation="2") > 0, name
        spans = [s for s in tracer.finished_spans() if s.name == "gc"
                 and s.attrs["generation"] == 2]
        assert spans and spans[-1].parent_id == root.span_id
        assert "collected" in spans[-1].attrs
        assert spans[-1].thread_name == threading.current_thread().name
    finally:
        P.watch_collections()


def test_a_collection_inside_a_span_of_its_own_tracer_does_not_deadlock():
    """The callback runs on whichever thread collects, possibly one
    inside the tracer's lock: that lock is re-entrant."""
    tracer = Tracer()
    P.watch_collections(None, tracer)
    try:
        with tracer._lock:
            gc.collect()
        assert any(s.name == "gc" for s in tracer.finished_spans())
    finally:
        P.watch_collections()
