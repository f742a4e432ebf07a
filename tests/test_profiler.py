"""Kernel profiler: annotation-registry coverage of the dispatch
universe, bounded capture-session ring + start/stop contract, the debug
endpoint (filters, capture control, 503 unwired), the device timeline's
per-kernel busy seconds against calls whose readiness the test controls,
the ≤5% always-off overhead guard, and the capture-toggle recompile/
verdict regression test (a mid-soak start/stop must not perturb the
shape ledger). tests/test_device_timeline.py drives the timeline's idle
accounting.
"""

import os
import threading
import time

import pytest

from grandine_tpu.http_api.routing import ApiContext, build_router
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime.profiler import (
    HBM_FAMILIES,
    KERNEL_SCHEMES,
    SCHEMES,
    KernelProfiler,
    get_profiler,
    set_profiler,
)

REPO = os.path.join(os.path.dirname(__file__), "..")


class _Out:
    """A kernel call's output whose readiness the test controls: ready
    `after` seconds past the call's dispatch."""

    def __init__(self, after: float = 0.0) -> None:
        self.due = time.perf_counter() + after

    def block_until_ready(self):
        time.sleep(max(0.0, self.due - time.perf_counter()))
        return self


def _settled(p: KernelProfiler, calls: int, timeout: float = 5.0) -> None:
    """Until the watcher has stamped `calls` calls in all."""
    end = time.monotonic() + timeout
    while sum(r["calls"] for r in p.summary()["device_seconds"]) < calls:
        assert time.monotonic() < end, p.summary()
        time.sleep(0.002)


# ------------------------------------------------- annotation registry


def test_kernel_schemes_covers_manifest_dispatch_universe():
    """Every contract row in the shapes manifest must have a scheme
    entry — the same invariant the tools/shapes `profiler-scope` check
    enforces statically, asserted here against the live analysis."""
    from tools import shapes

    _findings, analysis = shapes.analyze(root=REPO, check_manifest=False)
    registered = {e.kernel for e in analysis.entries}
    assert registered, "shape analysis found no kernels"
    missing = registered - set(KERNEL_SCHEMES)
    assert not missing, f"manifest kernels missing KERNEL_SCHEMES: {missing}"


def test_profiler_scope_check_fires_on_missing_key(tmp_path):
    """The tools/shapes profiler-scope finding actually fires: drop one
    KERNEL_SCHEMES entry in a copied profiler source and the full-run
    analysis reports it by name."""
    from tools import shapes
    from tools.lint.core import Context

    src = open(os.path.join(REPO, shapes.PROFILER_PATH)).read()
    assert '"multi_verify_msm": "bls",' in src
    import shutil

    root = tmp_path / "repo"
    shutil.copytree(
        os.path.join(REPO, "grandine_tpu"), root / "grandine_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copytree(
        os.path.join(REPO, "tools"), root / "tools",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    (root / "grandine_tpu" / "runtime" / "profiler.py").write_text(
        src.replace('"multi_verify_msm": "bls",', "")
    )
    findings, _ = shapes.analyze(ctx=Context(str(root)))
    hits = [f for f in findings if f.rule == shapes.PROFILER_RULE]
    assert any("multi_verify_msm" in f.message for f in hits), (
        f"expected a profiler-scope finding, got {findings}"
    )


def test_scheme_registry_names_are_schemes():
    from grandine_tpu.tpu import schemes as S

    assert set(KERNEL_SCHEMES.values()) <= set(SCHEMES)
    for name in S.names():
        assert name in SCHEMES, f"scheme registry name {name!r} unlabeled"
        # each scheme's flight kernel label annotates under that scheme
        label = S.get(name).kernel_label(None)
        assert KERNEL_SCHEMES.get(label) == name, (
            f"flight label {label!r} -> {KERNEL_SCHEMES.get(label)}"
        )
    # the fused BLS label also annotates under bls
    assert KERNEL_SCHEMES["fast_aggregate_fused"] == "bls"


def test_register_kernel_and_scheme_of():
    p = KernelProfiler()
    assert p.scheme_of("multi_verify_msm") == "bls"
    assert p.scheme_of("span_update_grid") == "slasher"
    assert p.scheme_of("never_heard_of_it") == "other"
    p.register_kernel("experimental_msm", "bls")
    assert p.scheme_of("experimental_msm") == "bls"
    assert p.annotation_keys()["experimental_msm"] == "bls"
    with pytest.raises(ValueError):
        p.register_kernel("x", "not_a_scheme")


def test_annotate_counts_dispatches_and_is_null_when_off():
    import contextlib

    p = KernelProfiler()
    scope = p.annotate("multi_verify_msm", 37)
    assert isinstance(scope, contextlib.nullcontext)
    with scope:
        pass
    with p.annotate("multi_verify_msm", 64):
        pass
    assert p.summary()["dispatches"]["multi_verify_msm"] == 2


# --------------------------------------------------- capture sessions


def test_session_ring_bounds_and_start_stop_contract():
    p = KernelProfiler(capacity=2)
    with pytest.raises(RuntimeError):
        p.stop()  # nothing active
    for i in range(5):
        sess = p.start(note=f"s{i}")
        assert sess["id"] == i + 1 and sess["trace_dir"] is None
        if i == 0:
            with pytest.raises(RuntimeError):
                p.start()  # double start
        done = p.stop()
        assert done["stopped"] is not None
    ring = p.sessions()
    assert [s["id"] for s in ring] == [4, 5]  # bounded, newest last
    assert p.sessions_total == 5
    assert p.active_session() is None


def test_session_counts_calls_and_metric():
    m = Metrics()
    p = KernelProfiler(metrics=m)
    p.start(note="windowed")
    p.dispatched("multi_verify_msm", _Out(0.05), 8)
    _settled(p, 1)
    sess = p.stop()
    assert sess["calls"] == 1
    assert 0.05 <= sess["device_s"] < 0.5
    assert m.verify_profile_sessions.value == 1.0
    assert m.verify_device_seconds.labels(
        "multi_verify_msm", "bls"
    ).value == pytest.approx(sess["device_s"])


def test_update_hbm_families():
    class _Arr:
        def __init__(self, shape, dtype, nbytes):
            self.shape, self.dtype, self.nbytes = shape, dtype, nbytes

    m = Metrics()
    p = KernelProfiler(metrics=m)
    totals = p.update_hbm(live_arrays=[
        _Arr((1 << 20, 26), "int32", 104 << 20),   # registry plane
        _Arr((64, 26), "int32", 6656),             # batch operand limbs
        _Arr((64,), "bool", 64),                   # verdict mask
        _Arr((2,), "float32", 8),                  # other
    ])
    assert set(totals) == set(HBM_FAMILIES)
    assert totals["registry"] == 104 << 20
    assert totals["kernel_io"] == 6656 + 64
    assert totals["other"] == 8
    assert m.verify_device_hbm_bytes.labels(
        "registry"
    ).value == float(104 << 20)


# ----------------------------------------------------- debug endpoint


def _profile_ctx():
    clock = [100.0]
    p = KernelProfiler(clock=lambda: clock[0])
    p.dispatched("multi_verify_msm", _Out(0.01), 8)
    p.dispatched("ed25519_verify", _Out(0.0), 32)
    _settled(p, 2)
    return ApiContext(None, None, profiler=p), p, clock


def test_profile_endpoint_summary_and_filters():
    import json

    ctx, _p, _clock = _profile_ctx()
    router = build_router()
    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", None
    )
    assert status == 200
    data = payload["data"]
    kernels = {r["kernel"] for r in data["device_seconds"]}
    assert kernels == {"multi_verify_msm", "ed25519_verify"}
    assert data["sessions_total"] == 0 and data["active_session"] is None
    assert "coverage" not in data  # no estimator beside the timeline
    assert set(data["idle_seconds"]) <= {"other", "gc"}  # no phases named
    json.dumps(payload)

    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"scheme": "bls"}
    )
    rows = payload["data"]["device_seconds"]
    assert [r["kernel"] for r in rows] == ["multi_verify_msm"]

    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile",
        {"kernel": "ed25519_verify"},
    )
    data = payload["data"]
    assert [r["scheme"] for r in data["device_seconds"]] == ["ed25519"]
    assert list(data["dispatches"]) == []  # no annotate() ran here

    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"n": "nope"}
    )[0] == 400
    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"n": "-1"}
    )[0] == 400
    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"action": "eh"}
    )[0] == 400


def test_profile_endpoint_capture_control_and_unwired():
    ctx, p, _clock = _profile_ctx()
    router = build_router()
    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"action": "start"}
    )
    assert status == 200
    assert payload["data"]["session"]["id"] == 1
    # second start while active -> 409
    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"action": "start"}
    )[0] == 409
    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"action": "stop"}
    )
    assert status == 200
    assert payload["data"]["session"]["stopped"] is not None
    # stop with nothing active -> 409
    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/profile", {"action": "stop"}
    )[0] == 409
    assert p.sessions_total == 1

    bare = ApiContext(None, None)
    assert router.dispatch(
        bare, "GET", "/eth/v1/debug/grandine/profile", None
    )[0] == 503


# ------------------------------------------------- the device timeline


def test_timeline_busy_seconds_per_kernel_tile_the_calls():
    """Calls on the device back to back, each ready a known time after the
    one before: each kernel's busy seconds are its calls' stretches, and
    together they tile first dispatch -> last ready, with no idle time
    charged between them (each call was dispatched before the previous
    one was ready)."""
    p = KernelProfiler()
    script = [("multi_verify_msm", 0.04), ("agg_fast_verify_msm_idx", 0.06),
              ("ed25519_verify", 0.02), ("multi_verify_msm", 0.04)]
    t0 = time.perf_counter()
    due = 0.0
    for kernel, busy in script:
        due += busy
        out = _Out()
        out.due = t0 + due
        p.dispatched(kernel, out, 8)
    _settled(p, len(script))
    dev = p.device_seconds()
    assert sum(dev.values()) == pytest.approx(due, abs=0.02)
    assert dev[("multi_verify_msm", "bls")] == pytest.approx(0.08, abs=0.02)
    assert dev[("agg_fast_verify_msm_idx", "bls")] == pytest.approx(
        0.06, abs=0.02)
    assert dev[("ed25519_verify", "ed25519")] == pytest.approx(
        0.02, abs=0.02)
    rows = {(r["kernel"], r["scheme"]): r["calls"]
            for r in p.summary()["device_seconds"]}
    assert rows[("multi_verify_msm", "bls")] == 2
    assert p.idle_seconds() == {}


def test_a_host_value_or_a_failed_call_is_ready_at_once():
    """An output that is not an array (a host value), and one whose
    computation failed, are stamped ready where the watcher meets them;
    the watcher lives on."""

    class _Failed:
        def block_until_ready(self):
            raise RuntimeError("the computation failed")

    p = KernelProfiler()
    p.dispatched("multi_verify_msm", True, 4)
    p.dispatched("multi_verify_msm", (_Failed(), [_Out(0.01)]), 4)
    p.dispatched("multi_verify_msm", _Out(0.0), 4)
    _settled(p, 3)
    assert p.device_seconds()[("multi_verify_msm", "bls")] < 0.5


def test_dispatched_concurrent_with_capture_toggle():
    """Calls dispatched from worker threads while another thread flips
    capture on/off must neither race nor lose a call."""
    p = KernelProfiler(capacity=4)
    stop = threading.Event()
    errors, sent = [], [0, 0, 0]

    def writer(i):
        try:
            while not stop.is_set():
                p.dispatched("multi_verify_msm", _Out(), 8)
                sent[i] += 1
                time.sleep(0.0005)
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    def toggler():
        try:
            while not stop.is_set():
                p.start()
                p.stop()
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=writer, args=(i,), daemon=True)
               for i in range(3)] + [
        threading.Thread(target=toggler, daemon=True)
    ]
    for t in threads:
        t.start()
    time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join(2.0)
    assert not errors
    _settled(p, sum(sent))
    assert p.summary()["device_seconds"][0]["calls"] == sum(sent)
    assert len(p.sessions()) <= 4


# ------------------------------------------------------ overhead guard


def _profiled_workload(fl, rounds: int, prof=None) -> float:
    """The flight-commit path, optionally with a profiler riding along:
    16 sha256-staged batches per round, one annotate() scope per batch
    when a profiler rides along (the same per-batch cost the dispatch
    seams pay). Returns seconds."""
    import contextlib
    import hashlib

    payload = b"\x5a" * (1 << 14)
    t0 = time.perf_counter()
    for _ in range(rounds):
        for _b in range(16):
            scope = (prof.annotate("multi_verify", 64) if prof is not None
                     else contextlib.nullcontext())
            with scope:
                bf = fl.begin_batch("block", "multi_verify", 64)
                h = payload
                for _ in range(64):
                    h = hashlib.sha256(h).digest()
                bf.note_device(0.0001)
                bf.finish(True)
    return time.perf_counter() - t0


def test_always_off_overhead_within_5_percent():
    """Capture off: one annotate() per batch must cost ≤5% vs the bare
    recorder on the same synthetic workload — min-of-5 with a small
    epsilon, mirroring the flight/observability guards."""
    from grandine_tpu.runtime.flight import FlightRecorder

    plain = FlightRecorder(capacity=4096)
    hooked = FlightRecorder(capacity=4096)
    prof = KernelProfiler()

    _profiled_workload(plain, 1)  # warm both paths
    _profiled_workload(hooked, 1, prof)
    t_off = min(_profiled_workload(plain, 1) for _ in range(5))
    t_on = min(_profiled_workload(hooked, 1, prof) for _ in range(5))
    assert t_on <= t_off * 1.05 + 0.002, (
        f"profiled {t_on * 1e3:.2f}ms vs plain {t_off * 1e3:.2f}ms"
    )
    assert prof.summary()["dispatches"]["multi_verify"] >= 16 * 6


# ------------------------------- capture toggle is shape-ledger-neutral


def test_capture_toggle_verdicts_stable_no_kernel_witness():
    """Fast witness for the slow sealed-ledger cell below: flipping a
    capture session between identical dispatches through a truth-table
    backend (no jax kernels) changes no verdict and every dispatch —
    off, capturing, off again — still flows through annotate()."""
    from grandine_tpu.testing.chaos import KnownAnswerBackend

    truth = {b"w-%d" % i: i % 2 == 0 for i in range(4)}
    kab = KnownAnswerBackend(truth)
    prof = KernelProfiler()
    msgs = sorted(truth)

    def dispatch():
        with prof.annotate("fast_aggregate", len(msgs)):
            return [kab.fast_aggregate_verify_batch_async(
                [m], [None], [[None]]
            )() for m in msgs]

    before = dispatch()
    prof.start(note="no-kernel toggle witness")
    during = dispatch()
    prof.stop()
    after = dispatch()

    assert before == during == after == [True, False, True, False]
    assert prof.summary()["dispatches"]["fast_aggregate"] == 3
    assert prof.sessions_total == 1


@pytest.mark.slow
def test_capture_toggle_zero_recompiles_and_same_verdict():
    """Regression test for the tentpole's hard guarantee: starting and
    stopping a capture session between two identical device dispatches
    introduces ZERO post-warmup recompiles and does not change the
    verdict. The annotation scope wraps the jitted call — it must never
    create a novel trace-time shape."""
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime import warmup
    from grandine_tpu.tpu import bls as B

    B.reset_shape_tracking()
    prev = get_profiler()
    prof = set_profiler(KernelProfiler())
    try:
        m = Metrics()
        backend = B.TpuBlsBackend(metrics=m)
        warmup.warm_all(
            buckets=[("aggregate", 4)], backend=backend,
            metrics=m, seal=True, enable_cache=False,
        )
        assert B.warmup_declared()
        pk = A.PublicKey(G1)
        sig = A.Signature(hash_to_g2(b"capture-toggle"))
        msgs = [b"toggle-%d" % i for i in range(3)]
        before = backend.fast_aggregate_verify_batch(
            msgs, [sig] * 3, [[pk]] * 3
        )
        assert B.post_warmup_recompiles() == 0

        prof.start(note="mid-soak toggle")  # annotation-only session
        during = backend.fast_aggregate_verify_batch(
            msgs, [sig] * 3, [[pk]] * 3
        )
        prof.stop()
        after = backend.fast_aggregate_verify_batch(
            msgs, [sig] * 3, [[pk]] * 3
        )

        assert B.post_warmup_recompiles() == 0
        assert m.verify_recompiles.value == 0.0
        assert before == during == after
        # the dispatch seam annotated through the module default
        assert sum(prof.summary()["dispatches"].values()) >= 2
    finally:
        set_profiler(prev)
        B.reset_shape_tracking()
