"""One batch, one chain of spans: the firehose's root span covers the
batch's whole life (oldest arrival -> flight record committed), every
stage and every wait is its descendant and a field of the flight record,
the `op` label splits a stage without changing its sum, the stages reach
the profiler's clock only during a capture session, and the compile scope
reads by JAX phase.
"""

import re
import sys
import time

import pytest

from benchmark import trace_reduce
from grandine_tpu import native, tracing
from grandine_tpu.consensus.verifier import NullVerifier
from grandine_tpu.fork_choice.store import Tick, TickKind
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime import AttestationVerifier, Controller
from grandine_tpu.runtime import profiler as profiler_mod
from grandine_tpu.runtime.flight import BATCH
from grandine_tpu.slasher import Slasher
from grandine_tpu.tracing import NULL_TRACER, Tracer
from grandine_tpu.transition.genesis import interop_genesis_state
from grandine_tpu.types.config import Config
from grandine_tpu.validator.duties import produce_attestations, produce_block

CFG = Config.minimal()

#: the waits of the pipelined (device) path and of every path
WAITS_ALWAYS = ("collect_wait", "pool_wait")
WAITS_PIPELINED = ("dispatch_wait", "settle_wait")


@pytest.fixture(scope="module")
def genesis():
    return interop_genesis_state(32, CFG)


class SeamBackend:
    """A device backend's async seam with its stages and none of its
    kernel: dispatch packs and uploads, the settle (run by the watchdog on
    a thread of its own) executes and reads back. Every batch is valid."""

    fuse_subgroup = True
    lane = "attestation"

    def __init__(self, metrics, tracer, work_s: float = 0.002) -> None:
        self.metrics, self.tracer, self.work_s = metrics, tracer, work_s

    def _stage(self, stage, **attrs):
        return tracing.stage(self.tracer, self.metrics, stage, self.lane,
                             **attrs)

    def g2_subgroup_check_batch_async(self, points):
        raise AssertionError("fused: never called")

    def fast_aggregate_verify_batch_async(self, messages, sigs, members,
                                          **_pin):
        with self._stage("host_prep", op="pack_aggregate",
                         items=len(messages)):
            time.sleep(self.work_s)
        with self._stage("upload_bytes", bytes=1, kernel="stub"):
            time.sleep(self.work_s)

        def settle():
            with self._stage("execute", kernel="stub"):
                time.sleep(self.work_s)
            with self._stage("readback", kernel="stub"):
                return True

        return settle


def drive(genesis, metrics, tracer, device: bool, then=None):
    """One slot's attestations through the firehose, as
    tests/test_observability.py `_run_firehose_batch` drives them; with
    `device` through the pipelined path over SeamBackend. Returns the
    verifier's flight rows (its spans are in `tracer`). `then(verifier)`
    runs once the slot is through, before the verifier stops; what
    `_device_dispatch` was given, call by call, is in
    `verifier.dispatched`."""
    ctrl = Controller(genesis, CFG, verifier_factory=NullVerifier,
                      metrics=metrics, tracer=tracer)
    verifier = AttestationVerifier(
        ctrl, use_device=device, use_registry=False, deadline_s=0.01,
        backend=SeamBackend(metrics, tracer) if device else None,
        slasher=Slasher(metrics=metrics),
    )
    verifier.dispatched = []
    dispatch = verifier._device_dispatch

    def recording(prepared, parent=None, **kw):
        verifier.dispatched.append(prepared)
        return dispatch(prepared, parent, **kw)

    verifier._device_dispatch = recording
    try:
        blk, post = produce_block(genesis, 1, CFG,
                                  full_sync_participation=False)
        ctrl.on_tick(Tick(1, TickKind.PROPOSE))
        ctrl.on_own_block(blk)
        ctrl.wait()
        atts = produce_attestations(post, CFG, slot=1)
        verifier.submit_many(atts)
        verifier.flush()
        ctrl.wait()
        assert verifier.stats["accepted"] == len(atts)
        if then is not None:
            then(verifier)
        return [r for r in verifier.flight.snapshot(lane="attestation")
                if r.kind == BATCH]
    finally:
        verifier.stop()
        ctrl.stop()


def descendants(spans, root):
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s.parent_id, []).append(s)
    out, todo = [], [root.span_id]
    while todo:
        kids = by_parent.get(todo.pop(), [])
        out.extend(kids)
        todo.extend(k.span_id for k in kids)
    return out


@pytest.fixture(scope="module", params=[False, True], ids=["host", "device"])
def driven(request, genesis):
    metrics, tracer = Metrics(), Tracer()
    rows = drive(genesis, metrics, tracer, device=request.param)
    return request.param, metrics, tracer.finished_spans(), rows


def test_every_batch_has_one_root_and_the_named_children(driven):
    device, _metrics, spans, rows = driven
    assert rows
    roots = {s.trace_id: s for s in spans if s.name == "verify_batch"}
    # one root a batch, and the flight row finds it
    assert len(roots) == len(rows)
    assert sorted(roots) == sorted(r.trace_id for r in rows)
    # no stage of the batch is an orphan: every span belongs to a root's
    # trace (the settle runs on the watchdog's own thread)
    assert {s.trace_id for s in spans} == set(roots)
    want = set(WAITS_ALWAYS) | {"host_prep", "feedback"}
    want |= ({"settle", "upload_bytes", "execute", "readback"}
             | set(WAITS_PIPELINED)) if device else {"execute"}
    for root in roots.values():
        assert root.parent_id is None
        family = descendants(spans, root)
        assert all(s.trace_id == root.trace_id for s in family)
        assert want <= {s.name for s in family}, sorted(
            s.name for s in family)
        ops = {s.attrs.get("op") for s in family if s.name == "host_prep"}
        assert "prevalidate" in ops
        assert {s.attrs.get("op") for s in family
                if s.name == "feedback"} == {"deliver", "slasher_feed"}
        # per batch, never per item
        assert len(family) + 1 <= 20
        if device:
            settle = next(s for s in family if s.name == "settle")
            inner = {s.name for s in family if s.parent_id == settle.span_id}
            assert inner == {"execute", "readback"}


def test_children_cover_the_root(driven):
    _device, _metrics, spans, _rows = driven
    for root in (s for s in spans if s.name == "verify_batch"):
        # the slasher feed (`batches`: those it fed) runs on the feeder
        # thread once the verdict is out, past the root's end
        children = [s for s in spans if s.parent_id == root.span_id
                    and "batches" not in s.attrs]
        covered = sum(c.duration for c in children)
        assert root.duration > 0
        assert all(c.start >= root.start - 1e-6 for c in children)
        assert all(c.end <= root.end + 1e-6 for c in children)
        assert covered / root.duration >= 0.90, (
            f"{covered / root.duration:.1%} of {root.duration * 1e3:.2f} ms: "
            f"{[(c.name, round(c.duration * 1e3, 3)) for c in children]}")


def test_waits_are_fields_of_the_flight_record(driven):
    device, _metrics, spans, rows = driven
    for row in rows:
        mine = [s for s in spans if s.trace_id == row.trace_id]
        span = {s.name: s for s in mine
                if s.name in WAITS_ALWAYS + WAITS_PIPELINED + ("settle",)}
        d = row.as_dict()
        for name in WAITS_ALWAYS + (
                WAITS_PIPELINED + ("settle",) if device else ()):
            assert d[name + "_s"] == pytest.approx(
                span[name].duration, abs=2e-3), name
        if not device:
            assert d["dispatch_wait_s"] == d["settle_wait_s"] == 0.0
            assert d["settle_s"] == 0.0
        # what the SLO tracker calls the queue is its three parts
        prevalidate = next(
            s for s in mine
            if s.name == "host_prep" and s.attrs.get("op") == "prevalidate")
        assert d["queue_wait_s"] == pytest.approx(
            d["collect_wait_s"] + d["pool_wait_s"] + prevalidate.duration,
            abs=5e-3)
        assert d["collect_wait_s"] > 0.0
        if device:
            # device_s keeps its meaning: dispatch (host) + settle
            assert d["device_s"] >= d["settle_s"] > 0.0


def test_sum_over_op_is_the_stage(driven):
    _device, metrics, spans, _rows = driven
    children = metrics.verify_stage_seconds.children()
    assert all(len(k) == 3 for k in children)
    assert all(k[2] in tracing.STAGE_OPS for k in children)
    for stage, parts in (("host_prep", None), ("feedback",
                                               {"deliver", "slasher_feed"})):
        ops = {k[2] for k in children if k[0] == stage}
        assert "" not in ops, "a split stage has no unsplit series"
        if parts:
            assert ops == parts
        by_metric = sum(c.sum for k, c in children.items() if k[0] == stage)
        by_span = sum(s.duration for s in spans if s.name == stage)
        assert by_metric == pytest.approx(by_span, rel=0.02, abs=1e-3)
        # no stage opens inside a span of its own name
        ids = {s.span_id for s in spans if s.name == stage}
        assert not [s for s in spans
                    if s.name == stage and s.parent_id in ids]


def test_unknown_op_reads_other():
    m = Metrics()
    with tracing.stage(NULL_TRACER, m, "host_prep", "attestation",
                       op="coffee_break"):
        pass
    assert ("host_prep", "attestation", "other") in (
        m.verify_stage_seconds.children())


def test_explicit_start_and_epoch_in_the_dump():
    tracer = Tracer()
    t0 = time.perf_counter() - 0.5
    span = tracer.span("collect_wait", start=t0)
    span.finish()
    assert 0.5 <= span.duration < 0.6
    other = tracer.chrome_trace()["otherData"]
    # `ts` 0 on the wall clock: within this process's lifetime
    assert 0 < time.time_ns() - other["epoch_time_ns"] < 3600e9


class Recorder:
    """Stands in for jax.profiler.TraceAnnotation."""

    names: "list[str]" = []

    def __init__(self, name, **_kw):
        self.name = name

    def __enter__(self):
        Recorder.names.append(self.name)
        return self

    def __exit__(self, *_exc):
        return False


def test_stages_reach_the_profilers_clock_only_in_a_session(
        genesis, monkeypatch):
    import jax

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    prof = profiler_mod.KernelProfiler()
    monkeypatch.setattr(profiler_mod, "_DEFAULT", prof)
    Recorder.names = []
    drive(genesis, Metrics(), Tracer(), device=True)
    assert Recorder.names == [], "written outside a capture session"
    prof.start(trace_dir=None, note="test")
    try:
        drive(genesis, Metrics(), Tracer(), device=True)
    finally:
        prof.stop()
    # a collection of the interpreter inside the session writes its own
    # `process/gc_gen<g>/b0` (runtime/profiler.py): not a stage
    seen = {n for n in Recorder.names if not n.startswith("process/gc_gen")}
    assert seen, "a capture session wrote no host span"
    for name in set(Recorder.names):
        assert trace_reduce.HOST_SPAN.match(name), name
    for name in seen:
        assert re.match(r"^attestation/[a-z_0-9]+/b\d+$", name), name
    for what in ("prevalidate", "g2_decompress", "pack_aggregate",
                 "upload_bytes", "settle", "execute", "readback",
                 "deliver", "slasher_feed"):
        assert any(n.split("/")[1] == what for n in seen), (what, seen)
    # a stage with no `items` of its own takes the enclosing span's
    buckets = {n.rsplit("/", 1)[1] for n in seen}
    assert "b0" not in buckets, seen
    Recorder.names = []
    drive(genesis, Metrics(), Tracer(), device=True)
    assert Recorder.names == [], "written after the session closed"


@pytest.mark.parametrize("path", ["native", "python"])
def test_one_decompress_stage_a_call_by_the_path_the_library_gives(
        genesis, monkeypatch, path):
    """`_device_dispatch` decodes its signatures in ONE `host_prep` /
    `g2_decompress` stage a call, first pass or probe, with the call's
    items on it, and the counter names the decoder that ran: the native
    one where the runtime library loaded, the Python loop where it did
    not. Nothing but the library chooses."""
    if path == "python":
        monkeypatch.setattr(native, "lib", None)
    elif native.lib is None:
        pytest.skip("no toolchain built the runtime library")
    other = {"native": "python", "python": "native"}[path]
    metrics, tracer = Metrics(), Tracer()

    def decompress_stages():
        return [s.attrs["items"] for s in tracer.finished_spans()
                if s.name == "host_prep"
                and s.attrs.get("op") == "g2_decompress"]

    def probe(verifier):
        first_passes = [len(p) for p in verifier.dispatched]
        assert first_passes and decompress_stages() == first_passes
        counter = metrics.signature_decompress_items
        assert counter.value(path) == sum(first_passes)
        batch = verifier.dispatched[0]
        half = batch[:max(1, len(batch) // 2)]
        settle = verifier._device_dispatch(half, parent=batch)
        assert settle() is True
        assert decompress_stages() == first_passes + [len(half)]
        assert counter.value(path) == sum(first_passes) + len(half)
        assert counter.value(other) == 0
        # an undecodable signature is the batch's verdict, in that stage
        bad = list(half[0])
        bad[1] = b"\x00" * 96
        assert verifier._device_dispatch([tuple(bad)])() is False
        assert decompress_stages() == first_passes + [len(half), 1]

    drive(genesis, metrics, tracer, device=True, then=probe)


def test_compile_scope_reads_by_phase():
    import jax
    import jax.numpy as jnp

    from grandine_tpu.tpu import bls  # noqa: F401  (subscribes the listeners)
    from grandine_tpu.tpu import compile_scope

    def phases():
        seconds, lookups = compile_scope.phase_totals()
        return seconds

    def tiny(salt):
        # a fresh function each time: nothing is served from jit's cache
        return jax.jit(lambda x: (x * salt + 1).sum())

    x = jnp.arange(8, dtype=jnp.int32)
    float(tiny(3)(x))  # jnp's own helpers compile now, not below
    before = phases()
    float(tiny(5)(x))
    assert phases() == before, "counted outside compiling()"
    with compile_scope.compiling():
        float(tiny(7)(x))
    after = phases()
    for phase in ("trace", "lower", "backend"):
        assert after[phase] > before[phase], phase
    assert set(after) == set(compile_scope.PHASES)
    # a duration JAX reports that holds earlier ones of its phase (a
    # jitted function traced inside another) replaces them: the union
    with compile_scope.compiling():
        t0 = phases()["trace"]
        compile_scope._on_duration(
            "/jax/core/compile/jaxpr_trace_duration", 0.001)
        time.sleep(0.002)
        compile_scope._on_duration(
            "/jax/core/compile/jaxpr_trace_duration", 0.010)
        assert phases()["trace"] - t0 == pytest.approx(0.010, abs=1e-6)
        compile_scope._on_duration("/jax/some/event/of/tomorrow", 5.0)
    assert set(phases()) == set(compile_scope.PHASES)
    # and the scrape shows the process's totals, on a fresh Metrics too
    text = Metrics().expose()
    line = next(ln for ln in text.splitlines() if ln.startswith(
        'verify_compile_phase_seconds_total{phase="trace"}'))
    assert float(line.split()[-1]) == pytest.approx(phases()["trace"])
    assert 'verify_compile_cache_total{result="hit"}' in text
    assert 'verify_compile_cache_total{result="miss"}' in text
    assert "jax" in sys.modules
