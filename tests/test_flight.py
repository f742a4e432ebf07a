"""Flight recorder: ring wraparound and snapshot filters, SLO cause
attribution, bounded top-K origin table (space-saving eviction), fault
aggregation across retries, the duty-cycle integral, concurrent
record/snapshot safety, the debug endpoint, and the ≤5% always-on
recording overhead guard.
"""

import hashlib
import json
import threading
import time

from grandine_tpu.http_api.routing import ApiContext, build_router
from grandine_tpu.metrics import Metrics
from grandine_tpu.runtime.flight import (
    BATCH,
    BREAKER,
    CANARY,
    FlightRecorder,
    OriginTable,
    SLO_CAUSES,
    bucket_of,
)


def _batch(fl, lane="block", kernel="multi_verify", items=10,
           device_s=0.0, queue_wait_s=0.0, verdict=True, **kw):
    bf = fl.begin_batch(lane, kernel, items, queue_wait_s=queue_wait_s,
                        breaker_state=kw.get("breaker_state", ""))
    if device_s:
        bf.note_device(device_s)
    if kw.get("host_s"):
        bf.note_host(kw["host_s"])
    if kw.get("bisect_s"):
        bf.note_bisect(kw["bisect_s"], kw.get("bisect_depth", 1))
    bf.finish(verdict)
    return bf.record


# ------------------------------------------------------- ring + snapshot


def test_ring_wraparound_keeps_newest():
    fl = FlightRecorder(capacity=16)
    for i in range(40):
        _batch(fl, items=i + 1)
    recs = fl.snapshot()
    assert len(recs) == 16
    assert [r.seq for r in recs] == list(range(24, 40))  # oldest-first
    s = fl.summary()
    assert s["records_total"] == 40 and s["records"] == 16
    assert s["batches"] == 40


def test_snapshot_filters_lane_kind_and_n():
    fl = FlightRecorder(capacity=64)
    for _ in range(4):
        _batch(fl, lane="block")
    for _ in range(3):
        _batch(fl, lane="attestation", kernel="fast_aggregate_verify")
    fl.record_canary("tpu", passed=True, duration_s=0.01)
    fl.record_breaker("tpu", "open")

    assert len(fl.snapshot(lane="block")) == 4
    assert len(fl.snapshot(lane="attestation")) == 3
    assert len(fl.snapshot(kind=BATCH)) == 7
    assert len(fl.snapshot(kind=CANARY)) == 1
    assert len(fl.snapshot(kind=BREAKER)) == 1
    # n truncates to the NEWEST n after filtering
    tail = fl.snapshot(kind=BATCH, n=2)
    assert [r.lane for r in tail] == ["attestation", "attestation"]
    assert fl.snapshot(n=0) == []
    assert len(fl.snapshot(lane="block", n=99)) == 4
    # health-plane rows share the timeline, ordered after the batches
    all_recs = fl.snapshot()
    assert [r.kind for r in all_recs[-2:]] == [CANARY, BREAKER]


def test_records_are_json_ready():
    fl = FlightRecorder()
    _batch(fl, items=5)
    row = fl.snapshot()[0].as_dict()
    json.dumps(row)  # must not raise
    assert row["bucket"] == 8 and row["fill"] == 0.625


# ------------------------------------------------------- SLO attribution


def test_slo_cause_attribution_all_four():
    fl = FlightRecorder(slo_budgets={"block": 0.01})
    # breaker open + no device time: the batch never had a chance
    r1 = _batch(fl, queue_wait_s=0.02, breaker_state="open",
                host_s=0.005, verdict=True)
    # bisection dominates both exec and queue wait
    r2 = _batch(fl, device_s=0.004, bisect_s=0.02, verdict=False)
    # device execute dominates
    r3 = _batch(fl, device_s=0.02, queue_wait_s=0.001)
    # queue wait dominates a tiny execute
    r4 = _batch(fl, device_s=0.001, queue_wait_s=0.02)
    causes = [r.slo_cause for r in (r1, r2, r3, r4)]
    assert causes == ["breaker_open", "bisection", "device", "queue_wait"]
    assert all(r.slo_miss for r in (r1, r2, r3, r4))
    assert set(causes) <= set(SLO_CAUSES)
    misses = fl.slo_misses()
    assert sum(misses["block"].values()) == 4


def test_slo_within_budget_is_not_a_miss():
    m = Metrics()
    fl = FlightRecorder(metrics=m, slo_budgets={"block": 1.0})
    rec = _batch(fl, device_s=0.001)
    assert not rec.slo_miss and rec.slo_cause is None
    assert fl.slo_misses() == {}
    fl2 = FlightRecorder(metrics=m, slo_budgets={"block": 0.0001})
    _batch(fl2, device_s=0.01)
    assert m.verify_slo_miss.value("block", "device") == 1


# ------------------------------------------------- fill / waste / faults


def test_bucket_fill_and_padding_waste():
    assert [bucket_of(n) for n in (1, 2, 3, 9, 64, 65)] == [
        1, 2, 4, 16, 64, 128,
    ]
    m = Metrics()
    fl = FlightRecorder(metrics=m)
    _batch(fl, items=5, kernel="multi_verify")   # bucket 8, waste 3
    _batch(fl, items=8, kernel="multi_verify")   # bucket 8, waste 0
    s = fl.summary()
    assert s["padding_waste"]["multi_verify"] == 3
    assert abs(s["fill_ratio"]["multi_verify"] - (0.625 + 1.0) / 2) < 1e-9
    assert m.verify_padding_waste.value("multi_verify") == 3


def test_note_fault_primary_and_secondary_both_counted():
    fl = FlightRecorder()
    bf = fl.begin_batch("block", "multi_verify", 4)
    bf.note_fault("settle")
    bf.note_retry()
    bf.note_fault("watchdog")  # lands on the retry: secondary
    bf.finish(True)
    rec = fl.snapshot()[0]
    assert rec.fault == "settle" and rec.note == "also_watchdog"
    assert rec.retries == 1
    assert fl.summary()["faults"] == {"settle": 1, "watchdog": 1}


# ----------------------------------------------------------- origin table


def test_origin_table_space_saving_eviction():
    t = OriginTable(capacity=2)
    for _ in range(3):
        t.note_failure("peer:A")
    t.note_failure("peer:B")
    # table full: a NEW origin evicts the minimum (B, count 1) and
    # inherits its count +1, with the floor recorded as error
    t.note_failure("peer:C")
    rows = t.snapshot()
    assert len(t) == 2 and len(rows) == 2
    assert rows[0] == {"origin": "peer:A", "failures": 3, "error": 0}
    assert rows[1] == {"origin": "peer:C", "failures": 2, "error": 1}


def test_origin_table_heavy_hitter_survives_churn():
    t = OriginTable(capacity=4)
    for _ in range(100):
        t.note_failure("peer:hot")
    for i in range(50):  # adversarial one-shot churn
        t.note_failure(f"peer:churn{i}")
    assert len(t) == 4
    rows = t.snapshot()
    assert rows[0]["origin"] == "peer:hot"
    assert rows[0]["failures"] >= 100


def test_batch_flight_threads_origin_into_table():
    fl = FlightRecorder()
    bf = fl.begin_batch("attestation", "fast_aggregate_verify", 64)
    bf.note_fault("verdict")
    bf.note_origin_failure("peer:9000")
    bf.finish(False)
    assert fl.snapshot()[0].origin == "peer:9000"
    assert fl.origins.snapshot()[0]["origin"] == "peer:9000"
    assert fl.summary()["failing_origins"][0]["failures"] == 1


# ------------------------------------------------------------ duty cycle


def test_duty_cycle_and_occupancy_integrals():
    t = [0.0]
    fl = FlightRecorder(clock=lambda: t[0])
    fl.device_enter()          # depth 1 at t=0
    t[0] = 1.0
    fl.device_enter()          # depth 2 at t=1
    t[0] = 2.0
    fl.device_exit()           # depth 1 at t=2
    t[0] = 3.0
    fl.device_exit()           # idle at t=3
    t[0] = 4.0
    # busy 0..3 of 4s elapsed
    assert abs(fl.duty_cycle() - 0.75) < 1e-9
    m = Metrics()
    fl2 = FlightRecorder(metrics=m, clock=lambda: t[0])
    fl2.device_enter()
    t[0] = 5.0
    fl2.device_exit()
    assert m.verify_device_duty_cycle.value == 1.0


# ------------------------------------------------------------ concurrency


def test_concurrent_record_and_snapshot():
    fl = FlightRecorder(capacity=64)
    stop = threading.Event()
    errors = []

    def writer(lane):
        try:
            while not stop.is_set():
                _batch(fl, lane=lane)
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [
        threading.Thread(target=writer, args=(lane,), daemon=True)
        for lane in ("block", "attestation", "sync_message")
    ]
    for th in threads:
        th.start()
    deadline = time.monotonic() + 0.5
    snaps = 0
    while time.monotonic() < deadline:
        recs = fl.snapshot()
        seqs = [r.seq for r in recs]
        assert seqs == sorted(seqs), "snapshot must be ordered"
        assert len(seqs) == len(set(seqs)), "no duplicate slots"
        for r in fl.snapshot(lane="block", n=8):
            assert r.lane == "block"
        fl.summary()
        snaps += 1
    stop.set()
    for th in threads:
        th.join(2.0)
    assert not errors
    assert snaps > 10 and fl.summary()["batches"] > 10


# --------------------------------------------------------- debug endpoint


def _flight_ctx():
    fl = FlightRecorder(capacity=64)
    for _ in range(3):
        _batch(fl, lane="block")
    _batch(fl, lane="attestation", kernel="fast_aggregate_verify")
    fl.record_breaker("tpu", "open")
    return ApiContext(None, None, flight=fl), fl


def test_flight_endpoint_snapshot_and_filters():
    ctx, fl = _flight_ctx()
    router = build_router()
    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/flight", None
    )
    assert status == 200
    data = payload["data"]
    assert len(data["records"]) == 5
    assert data["summary"]["batches"] == 4
    assert "slo" in data and "origins" in data
    json.dumps(payload)  # endpoint payload is JSON-ready

    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/flight", {"lane": "block"}
    )
    assert [r["lane"] for r in payload["data"]["records"]] == ["block"] * 3

    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/flight",
        {"kind": "breaker", "n": "10"},
    )
    rows = payload["data"]["records"]
    assert len(rows) == 1 and rows[0]["note"] == "breaker_open"

    status, payload = router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/flight", {"n": "2"}
    )
    assert len(payload["data"]["records"]) == 2


def test_flight_endpoint_rejects_bad_n_and_unwired():
    ctx, _fl = _flight_ctx()
    router = build_router()
    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/flight", {"n": "nope"}
    )[0] == 400
    assert router.dispatch(
        ctx, "GET", "/eth/v1/debug/grandine/flight", {"n": "-1"}
    )[0] == 400
    bare = ApiContext(None, None)
    assert router.dispatch(
        bare, "GET", "/eth/v1/debug/grandine/flight", None
    )[0] == 503


# --------------------------------------------------------- overhead guard


def _recorded_workload(fl, rounds: int) -> float:
    """A batch-shaped CPU workload (16 batches of hashing) with the full
    per-batch recording sequence around each — the exact call pattern
    the scheduler's _flush/_complete path makes per batch — or bare
    when fl is None. Returns seconds."""
    payload = b"\x5a" * (1 << 17)
    t0 = time.perf_counter()
    for _ in range(rounds):
        for _b in range(16):
            if fl is not None:
                bf = fl.begin_batch("block", "multi_verify", 64,
                                    queue_wait_s=0.0001,
                                    breaker_state="closed")
                fl.device_enter()
            h = hashlib.sha256(payload).digest()
            for _ in range(8):
                h = hashlib.sha256(payload + h).digest()
            if fl is not None:
                fl.device_exit()
                bf.note_device(0.001)
                bf.finish(True)
    return time.perf_counter() - t0


def test_flight_recording_overhead_within_5_percent():
    """Recording is always-on (components build a private recorder when
    none is injected), so the per-batch record path must stay inside the
    same ≤5% envelope as the tracing/metrics instrumentation. Min-of-5
    each way with a small absolute epsilon against scheduler noise."""
    fl = FlightRecorder(capacity=4096, metrics=Metrics())
    _recorded_workload(fl, 1)     # warm both paths
    _recorded_workload(None, 1)
    t_off = min(_recorded_workload(None, 1) for _ in range(5))
    t_on = min(_recorded_workload(fl, 1) for _ in range(5))
    assert t_on <= t_off * 1.05 + 0.002, (
        f"recorded {t_on * 1e3:.2f}ms vs bare {t_off * 1e3:.2f}ms"
    )
    assert fl.summary()["batches"] >= 16 * 6
