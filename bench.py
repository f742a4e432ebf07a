"""Headline benchmark: device RLC batch BLS verification throughput.

Measures signatures/second through the MSM-backed grouped RLC verify kernel
(the 50k-validator attestation batch-verify plane, BASELINE.md config 2: N
signatures over BENCH_MSGS distinct attestation messages — the real shape
of gossip/block traffic) on whatever accelerator JAX finds (the driver
runs this on one real TPU chip). BENCH_GROUPED=0 falls back to the flat
(one-Miller-loop-per-signature) kernel; BENCH_LADDER=1 selects the older
per-signature-ladder kernels for comparison.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "sigs/s", "vs_baseline": N}

vs_baseline is measured throughput divided by an estimated single-core blst
`multi_verify` throughput of 1,600 sigs/s (≈0.6 ms/sig: one Miller loop plus
amortized G1/G2 RLC scalar muls and final exp — BASELINE.md §blst context).
The reference publishes no absolute number for this metric; the estimate is
the documented sizing anchor from BASELINE.md/SURVEY.md §6.

Honesty notes (VERDICT r3 #10):
  - Each timed iteration draws FRESH random RLC scalars, rebuilds the host
    MSM plan (that cost is on the clock), and forces the scalar result —
    fresh randomizers are what a real verifier draws per batch, so reused
    args would time a workload nobody runs.
  - Batch construction uses arithmetic-progression secret keys
    (sk_i = a + b·i mod r) so the host can build N valid (pk, sig) pairs
    with N point ADDS instead of device scalar-mul kernels. Prep needs no
    device compiles and the verified workload is identical — the kernel
    sees N distinct keys/signatures and fresh random scalars either way.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import sys
import time

from tools.perf import emit_bench_line, git_commit

import numpy as np

BLST_SINGLE_CORE_SIGS_PER_SEC = 1600.0


def build_batch(n: int, n_msgs: int = 8):
    """Host-only synthetic batch: n validators with distinct keys in
    arithmetic progression, n_msgs distinct attestation messages assigned
    cyclically (message of key i = i mod n_msgs). Returns flat REST-format
    point arrays (no scalars — the caller draws those per iteration)."""
    from grandine_tpu.crypto.constants import R
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.tpu import curve as C

    a = 0x1357_0000_DEAD_BEEF_1234_5678_9ABC_DEF0
    b = 0x2468_ACE0_2468_ACE0_2468_ACE1

    msgs = [b"bench-attestation-%d" % j for j in range(n_msgs)]
    hs = [hash_to_g2(m) for m in msgs]
    mx, my, _minf = C.g2_points_to_dev(hs)

    # pk_i = (a + b·i)·G: start + i·step, one host add per key
    pks = []
    acc = G1.mul(a)
    step = G1.mul(b)
    for _ in range(n):
        pks.append(acc)
        acc = acc + step
    # sig_i = (a + b·i)·H_{i mod M}: per message, walk i = j, j+M, j+2M, …
    sigs: list = [None] * n
    for j in range(n_msgs):
        sacc = hs[j].mul((a + b * j) % R)
        sstep = hs[j].mul((b * n_msgs) % R)
        for i in range(j, n, n_msgs):
            sigs[i] = sacc
            sacc = sacc + sstep

    pk_x, pk_y, pk_inf = C.g1_points_to_dev(pks)
    sig_x, sig_y, sig_inf = C.g2_points_to_dev(sigs)
    msg_x = np.ascontiguousarray(mx[np.arange(n) % n_msgs])
    msg_y = np.ascontiguousarray(my[np.arange(n) % n_msgs])
    msg_inf = np.zeros((n,), bool)
    return (
        pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf,
    )


def regroup_batch(args, n_msgs: int):
    """Reshape flat build_batch points (messages cyclic mod n_msgs) into the
    (M, K, …) layout of the grouped kernels. With grouped[j, kk] =
    flat[j + kk·M], the kernels' k-major flattening maps kernel-flat index f
    back to ORIGINAL flat index f — so per-iteration scalars stay in
    original order with group(f) = f mod M."""
    (pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf) = args
    n = len(pk_inf)
    assert n % n_msgs == 0
    k = n // n_msgs
    order = np.argsort(np.arange(n) % n_msgs, kind="stable")

    def grp(a):
        return np.ascontiguousarray(a[order].reshape((n_msgs, k) + a.shape[1:]))

    first = order.reshape(n_msgs, k)[:, 0]
    return (
        grp(pk_x), grp(pk_y), grp(pk_inf),
        grp(sig_x), grp(sig_y), grp(sig_inf),
        np.ascontiguousarray(msg_x[first]),
        np.ascontiguousarray(msg_y[first]),
        np.ascontiguousarray(msg_inf[first]),
    )


def draw_rlc(n: int, seed: int):
    """Fresh nonzero 32+32-bit RLC pairs, vectorized."""
    rng = np.random.default_rng(0xC0FFEE ^ (seed * 0x9E3779B9))
    r_lo = rng.integers(0, 1 << 32, size=n, dtype=np.uint64)
    r_hi = rng.integers(0, 1 << 32, size=n, dtype=np.uint64)
    r_lo = np.where((r_lo | r_hi) == 0, np.uint64(1), r_lo)
    return r_lo, r_hi


def _enable_compilation_cache() -> None:
    """Persistent XLA compilation cache: recompiling the pairing kernels
    costs minutes; cache entries make every bench/process after the first
    start in seconds (VERDICT r1 weak #2). One implementation shared
    with the startup warmer (runtime/warmup.py) so bench and node prime
    the same cache."""
    from grandine_tpu.runtime.warmup import enable_persistent_cache

    enable_persistent_cache()


def _lint_preflight() -> None:
    """Refuse to bench a tree that violates the verify-plane invariants
    (host sync on the dispatch path, inline gossip verify, …) or whose
    newest perf-ledger rows already regressed: the number would not
    describe the architecture this repo claims. BENCH_SKIP_LINT=1 skips
    the lint leg, BENCH_SKIP_PERF_CHECK=1 the ledger gate,
    BENCH_SKIP_RANGES=1 the limb-range certification leg; the runtime
    upload audit is not run here (it compiles kernels — invoke it via
    `python -m tools.lint --rules no-per-batch-upload`)."""
    import subprocess

    root = os.path.dirname(os.path.abspath(__file__))
    if os.environ.get("BENCH_SKIP_RANGES") != "1":
        # prove the limb-range theorems (and bound-certificate freshness)
        # before trusting any kernel number; regenerate a stale cert with
        # `python -m tools.ranges --write-cert`
        proc = subprocess.run(
            [sys.executable, "-m", "tools.ranges"], cwd=root
        )
        if proc.returncode != 0:
            print(
                "# bench aborted: limb-range certification failed "
                "(BENCH_SKIP_RANGES=1 overrides)",
                file=sys.stderr,
            )
            raise SystemExit(1)
    if os.environ.get("BENCH_SKIP_LINT") != "1":
        proc = subprocess.run([sys.executable, "-m", "tools.lint"], cwd=root)
        if proc.returncode != 0:
            # still emit the parseable zero line the harness looks for
            emit_bench_line(
                {
                    "metric": "bls_multi_verify_throughput",
                    "value": 0,
                    "unit": "sigs/s",
                    "vs_baseline": 0,
                },
                ledger=False,
            )
            print(
                "# bench aborted: grandine-lint preflight failed "
                "(BENCH_SKIP_LINT=1 overrides)",
                file=sys.stderr,
            )
            raise SystemExit(1)
    if os.environ.get("BENCH_SKIP_PERF_CHECK") != "1":
        proc = subprocess.run(
            [sys.executable, "-m", "tools.perf", "--check"], cwd=root
        )
        if proc.returncode != 0:
            print(
                "# bench aborted: tools/perf --check found a regression "
                "in the perf ledger (BENCH_SKIP_PERF_CHECK=1 overrides)",
                file=sys.stderr,
            )
            raise SystemExit(1)


def main() -> None:
    _lint_preflight()
    # default batch = 32,768: the measured throughput sweet spot (MSM cost
    # amortizes with batch size until ~64k, where memory pressure inverts
    # the curve); p50 batch latency ~1 s stays far inside the 4 s
    # attestation deadline, and a 50k-validator epoch generates ~1.6M
    # attestation signatures, so real traffic fills batches this size.
    n = int(os.environ.get("BENCH_N", "32768"))
    # 256 distinct messages per 32,768 signatures matches 50k-validator
    # traffic (~12 committees/slot + singles over the ~21 slots a 32k batch
    # spans — VERDICT r4 weak #2); the old flattering default was 64.
    n_msgs = int(os.environ.get("BENCH_MSGS", "256"))
    grouped = os.environ.get("BENCH_GROUPED", "1") != "0"
    try:
        import jax

        _enable_compilation_cache()

        from grandine_tpu.tpu import limbs as L
        from grandine_tpu.tpu import msm as M
        from grandine_tpu.tpu.bls import (
            grouped_multi_verify_msm_packed_kernel,
            multi_verify_msm_kernel,
            pick_msm_window,
            rlc_bits_host,
        )

        if grouped and n % n_msgs != 0:
            grouped = False  # ragged grouping: fall back to the flat kernel
        t_prep = time.time()
        flat = build_batch(n, n_msgs)
        args = regroup_batch(flat, n_msgs) if grouped else flat
        # The pubkey plane is REGISTRY data: a node keeps its validator
        # set's decompressed keys device-resident (uploaded once per epoch,
        # gathered by index per batch), so pk upload does not belong on the
        # per-batch clock. Message points are the distinct AttestationData
        # hashes (a few hundred rows — negligible either way). Signatures
        # are genuinely new per batch and stay on the clock: the bench
        # re-uploads them every iteration below.
        (pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
         msg_x, msg_y, msg_inf) = args
        host_pk = (pk_x, pk_y)  # kept for the registry-cold comparison
        t_pk = time.time()
        pk_x, pk_y = (jax.device_put(pk_x), jax.device_put(pk_y))
        for a in (pk_x, pk_y):
            a.block_until_ready()
        pk_upload_s = time.time() - t_pk  # the once-per-set registry cost
        pk_inf, msg_x, msg_y, msg_inf = (
            jax.device_put(a) for a in (pk_inf, msg_x, msg_y, msg_inf)
        )
        if grouped:
            # signatures upload as packed canonical words (52 B/coord vs
            # 104 B Montgomery limbs): transfer serializes with execution
            # on the per-batch clock, so sig bytes are batch latency
            stacked = np.stack(
                [sig_x[..., 0, :], sig_x[..., 1, :],
                 sig_y[..., 0, :], sig_y[..., 1, :]], axis=-2,
            )  # (M, K, 4, 26) Montgomery limbs
            flat_rows = stacked.reshape(-1, stacked.shape[-1])
            ints = [L.from_mont(row) for row in flat_rows]
            sig_packed = L.pack_fp_words_host(ints).reshape(
                stacked.shape[:-1] + (L.NWORDS,)
            )
            sig_np = (sig_packed, sig_inf)
        else:
            sig_np = (sig_x, sig_y, sig_inf)
        prep_s = time.time() - t_prep

        groups = (np.arange(n) % n_msgs) if grouped else None
        g2_w = pick_msm_window(n, 1)

        def make_plans(seed: int):
            r_lo, r_hi = draw_rlc(n, seed)
            inf = np.zeros(n, bool)
            g2_plan = M.plan_msm(r_lo, r_hi, inf, None, 1, window_bits=g2_w)
            if grouped:
                g1_w = pick_msm_window(n, n_msgs)
                g1_plan = M.plan_msm(
                    r_lo, r_hi, inf, groups, n_msgs, window_bits=g1_w
                )
                return g1_plan, g2_plan
            # flat kernel: G1 side still rides the GLV ladder on r_bits
            pairs = list(zip(r_lo.tolist(), r_hi.tolist()))
            return rlc_bits_host(pairs, n), g2_plan

        p1, p2 = make_plans(0)
        if grouped:
            fn = jax.jit(
                functools.partial(
                    grouped_multi_verify_msm_packed_kernel,
                    g1_windows=p1.windows, g1_wbits=p1.window_bits,
                    g2_windows=p2.windows, g2_wbits=p2.window_bits,
                )
            )
            call = lambda pl1, pl2: fn(
                pk_x, pk_y, pk_inf, *sig_np, msg_x, msg_y, msg_inf,
                *pl1.arrays, *pl2.arrays,
            )
        else:
            fn = jax.jit(
                functools.partial(
                    multi_verify_msm_kernel,
                    g2_windows=p2.windows, g2_wbits=p2.window_bits,
                )
            )
            call = lambda bits, pl2: fn(
                pk_x, pk_y, pk_inf, *sig_np, msg_x, msg_y, msg_inf,
                bits, *pl2.arrays,
            )

        t_compile = time.time()
        ok = bool(call(p1, p2))  # compile + first run
        compile_s = time.time() - t_compile
        if not ok:
            raise RuntimeError("kernel rejected a valid batch")

        # Fresh randomizers + fresh host plan EVERY iteration, and a fresh
        # SIGNATURE upload every iteration (production batches carry new
        # signatures; distinct buffers defeat any transfer caching). All
        # per-batch host work and host→device transfers are PIPELINED
        # against device execution: while batch i runs, the host builds
        # batch i+1's plan and enqueues its async uploads
        # (jax.device_put), then forces batch i — the overlap a
        # production verifier's two-deep dispatch queue gets.
        def upload(plans):
            pl1, pl2 = plans
            d1 = tuple(jax.device_put(a) for a in pl1.arrays)
            d2 = tuple(jax.device_put(a) for a in pl2.arrays)
            dsig = tuple(jax.device_put(np.copy(a)) for a in sig_np)
            return d1, d2, dsig

        if grouped:
            def dev_call(staged):
                d1, d2, dsig = staged
                return fn(
                    pk_x, pk_y, pk_inf, *dsig, msg_x, msg_y, msg_inf,
                    *d1, *d2,
                )
        else:
            def dev_call(staged):
                d1, d2, dsig = staged  # d1 = r_bits array
                return fn(
                    pk_x, pk_y, pk_inf, *dsig, msg_x, msg_y, msg_inf,
                    d1, *d2,
                )

            def upload(plans):  # noqa: F811 — flat-kernel variant
                bits, pl2 = plans
                return (
                    jax.device_put(bits),
                    tuple(jax.device_put(a) for a in pl2.arrays),
                    tuple(jax.device_put(np.copy(a)) for a in sig_np),
                )

        # Per-kernel device-time attribution for the run: a private
        # flight recorder + profiler pair (the same wiring node.py
        # gives the runtime) — each iteration's dispatch→settle delta
        # is reconciled through the flight record, and the summary
        # reports what fraction of the device-busy integral the
        # estimator attributed (`profiler_coverage`, acceptance ≥0.90)
        from grandine_tpu.runtime.flight import FlightRecorder
        from grandine_tpu.runtime.profiler import KernelProfiler

        bench_flight = FlightRecorder()
        bench_prof = KernelProfiler()
        bench_flight.profiler = bench_prof
        bench_kernel = (
            "grouped_multi_verify_msm" if grouped else "multi_verify_msm"
        )

        t0 = time.time()
        iters = 0
        latencies = []
        # per-batch stage breakdown, named like the runtime's
        # verify_stage_seconds histogram labels: host_prep = plan build,
        # upload_bytes = device_put enqueue, execute = dispatch + force
        # (the force also absorbs readback of the 1-bit verdict)
        stages = {"host_prep": [], "upload_bytes": [], "execute": []}
        staged = upload(make_plans(1))
        while True:
            iters += 1
            fl = bench_flight.begin_batch("firehose", bench_kernel, n)
            bench_flight.device_enter()
            t1 = time.time()
            pending = dev_call(staged)  # async dispatch, args resident
            t_disp = time.time()
            plans = make_plans(iters + 1)  # host plan ∥ device
            t_plan = time.time()
            staged = upload(plans)  # PCIe ∥ device
            t_up = time.time()
            ok = bool(pending)  # force the verdict
            t_force = time.time()
            bench_flight.device_exit()
            # dispatch→settle delta: the device owns the batch from the
            # async dispatch until the verdict forces (the host plan +
            # upload legs in between overlap device execution)
            fl.note_device(t_force - t1)
            fl.note_host(t_plan - t_disp)
            fl.finish(ok)
            latencies.append(t_force - t1)
            stages["host_prep"].append(t_plan - t_disp)
            stages["upload_bytes"].append(t_up - t_plan)
            stages["execute"].append((t_disp - t1) + (t_force - t_up))
            elapsed = time.time() - t0
            if elapsed > 15.0 or iters >= 30:
                break
        assert ok
        coverage = bench_prof.coverage(bench_flight)

        # Registry-COLD comparison: charge the pubkey plane (208 B/key of
        # affine G1 limbs) to every batch, serial with execution — what a
        # node without the device-resident registry pays. The delta
        # against the warm path is the registry's per-batch win.
        cold_lat = []
        for ci in range(3):
            plans = make_plans(1009 + ci)
            tc = time.time()
            cold_staged = upload(plans)
            cpk_x = jax.device_put(np.copy(host_pk[0]))
            cpk_y = jax.device_put(np.copy(host_pk[1]))
            cpk_x.block_until_ready()
            cpk_y.block_until_ready()
            if grouped:
                d1, d2, dsig = cold_staged
                pending = fn(
                    cpk_x, cpk_y, pk_inf, *dsig, msg_x, msg_y, msg_inf,
                    *d1, *d2,
                )
            else:
                bits, d2, dsig = cold_staged
                pending = fn(
                    cpk_x, cpk_y, pk_inf, *dsig, msg_x, msg_y, msg_inf,
                    bits, *d2,
                )
            assert bool(pending)
            cold_lat.append(time.time() - tc)
        cold_p50 = sorted(cold_lat)[len(cold_lat) // 2]
        cold_sigs_per_sec = n / cold_p50
        # once-per-set registry upload amortized over the run's signatures
        amortized_prep_us = pk_upload_s * 1e6 / (n * iters)

        # Headline = n / MEDIAN batch latency: the steady-state pipelined
        # throughput. A one-chip machine shares its host's cores, so
        # single round trips stall at random; the median is robust to
        # those transients while still charging every per-batch cost
        # (fresh randomizers, plan build, result force). The wall-clock mean over the whole
        # window is printed alongside for comparison.
        p50 = sorted(latencies)[len(latencies) // 2]
        sigs_per_sec = n / p50
        mean_sigs_per_sec = n * iters / elapsed
        emit_bench_line(
            {
                "metric": "bls_multi_verify_throughput",
                "value": round(sigs_per_sec, 1),
                "unit": "sigs/s",
                "vs_baseline": round(
                    sigs_per_sec / BLST_SINGLE_CORE_SIGS_PER_SEC, 3
                ),
            },
            config={"n": n, "n_msgs": n_msgs, "grouped": grouped},
        )
        print(
            f"# n={n} iters={iters} elapsed={elapsed:.2f}s "
            f"prep={prep_s:.1f}s compile+first={compile_s:.1f}s "
            f"p50_batch_latency={p50 * 1000:.0f}ms "
            f"wall_mean={mean_sigs_per_sec:.0f}sigs/s "
            f"registry_warm={sigs_per_sec:.0f}sigs/s "
            f"registry_cold={cold_sigs_per_sec:.0f}sigs/s "
            f"amortized_pk_prep={amortized_prep_us:.3f}us/sig "
            f"platform={jax.devices()[0].platform}",
            file=sys.stderr,
        )
        med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
        # firehose summary carries commit/host_cores like --devices, plus
        # the profiler's device-time attribution coverage
        emit_bench_line(
            {
                "metric": "bls_verify_stage_breakdown",
                "unit": "ms/batch (p50)",
                "value": {s: round(med(v) * 1000, 2)
                          for s, v in stages.items()},
                "compile_s": round(compile_s, 2),
                "profiler_coverage": (
                    round(coverage, 4) if coverage is not None else None
                ),
                "commit": git_commit(),
                "host_cores": os.cpu_count(),
            },
            stream=sys.stderr,
            config={"n": n, "n_msgs": n_msgs, "grouped": grouped},
        )
    except Exception as e:  # still emit a parseable line on failure
        emit_bench_line(
            {
                "metric": "bls_multi_verify_throughput",
                "value": 0,
                "unit": "sigs/s",
                "vs_baseline": 0,
            },
            ledger=False,
        )
        print(f"# bench failed: {e!r}", file=sys.stderr)
        raise


def bench_verify_scheduler() -> None:
    """Verify-scheduler mixed-workload diagnostics: per-lane throughput
    and p50/p95 enqueue→settle latency with HIGH-lane (block,
    sync_contribution) jobs riding concurrently with a LOW-lane
    sync-message firehose.

    The device is replaced by a synthetic model (fixed per-call dispatch
    latency + per-signature cost) so this measures the SCHEDULER —
    queueing, deadline coalescing, cross-lane overlap, settle pipeline —
    not BLS crypto (benched above). The headline check: under load, the
    sync_message lane coalesces submissions into few device calls
    (target ≥8 sigs/call), while HIGH lanes keep flushing on their own
    short deadlines instead of queueing behind the firehose."""
    import threading

    from grandine_tpu.runtime.verify_scheduler import (
        VerifyItem,
        VerifyScheduler,
    )

    call_latency_s = float(os.environ.get("BENCH_SCHED_CALL_MS", "2")) / 1e3
    per_sig_s = float(os.environ.get("BENCH_SCHED_SIG_US", "20")) / 1e6
    n_sync = int(os.environ.get("BENCH_SCHED_SYNC", "2000"))
    n_high = int(os.environ.get("BENCH_SCHED_HIGH", "200"))

    class _ModelDeviceScheduler(VerifyScheduler):
        """_device_dispatch swapped for the synthetic device model; the
        dispatcher/completion pipeline underneath is the real thing."""

        def _device_dispatch(self, lane, items):
            n = len(items)
            self.device_calls.append((lane.name, n))

            def settle() -> bool:
                time.sleep(call_latency_s + per_sig_s * n)
                return True

            return settle

    sched = _ModelDeviceScheduler(use_device=True)
    sched.device_calls = []
    item = VerifyItem(b"\x11" * 32, b"\x22" * 96, public_keys=("bench",))
    tickets: "dict[str, list]" = {
        "sync_message": [], "block": [], "sync_contribution": [],
    }
    lock = threading.Lock()

    def producer(lane: str, jobs: int, items_per_job: int) -> None:
        mine = []
        for _ in range(jobs):
            mine.append(sched.submit(lane, [item] * items_per_job))
        with lock:
            tickets[lane].extend(mine)

    t0 = time.time()
    threads = [
        threading.Thread(target=producer, args=("sync_message", n_sync // 4, 1))
        for _ in range(4)
    ] + [
        # attestation-style aggregates: one multi-key item per job
        threading.Thread(target=producer, args=("block", n_high, 1)),
        threading.Thread(target=producer, args=("sync_contribution", n_high, 1)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sched.flush(120.0)
    wall_s = time.time() - t0
    sched.stop()

    def q(xs, frac):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(frac * len(xs)))]

    calls: "dict[str, list]" = {}
    for lane, n in sched.device_calls:
        calls.setdefault(lane, []).append(n)
    report = {}
    for lane, ts in tickets.items():
        lat = [
            (t.settled_at - t.enqueued_at) for t in ts
            if t.settled_at is not None
        ]
        if not lat:
            continue
        lane_calls = calls.get(lane, [])
        report[lane] = {
            "jobs": len(ts),
            "p50_ms": round(q(lat, 0.50) * 1e3, 2),
            "p95_ms": round(q(lat, 0.95) * 1e3, 2),
            "jobs_per_s": round(len(ts) / wall_s, 0),
            "device_calls": len(lane_calls),
            "sigs_per_call": round(
                sum(lane_calls) / max(1, len(lane_calls)), 1
            ),
        }
    sync_coalesce = report.get("sync_message", {}).get("sigs_per_call", 0)
    emit_bench_line(
        {
            "metric": "verify_scheduler_mixed_workload",
            "unit": "ms (enqueue→settle)",
            "value": report,
            "wall_s": round(wall_s, 2),
            "sync_sigs_per_call": sync_coalesce,
            "sync_coalescing_ok": bool(sync_coalesce >= 8),
        },
        stream=sys.stderr,
    )
    print(
        f"# verify-scheduler bench: synthetic device model "
        f"(call={call_latency_s * 1e3:.1f}ms + {per_sig_s * 1e6:.0f}us/sig); "
        f"measures lane scheduling, not crypto",
        file=sys.stderr,
    )
    # the scheduler's own flight recorder saw every batch above
    emit_bench_line(
        {
            "metric": "verify_flight_summary",
            "value": sched.flight.summary(),
        },
        stream=sys.stderr,
        ledger=False,
    )


def _fuzz_schedules(seeds) -> dict:
    """Run the deterministic schedule fuzzer and emit its one parseable
    JSON line: seeds, preemption-point count, trace hashes (equal seeds
    reproduce equal hashes), and the violation count (must be 0)."""
    from grandine_tpu.testing.schedule_fuzz import run_fuzz

    report = run_fuzz(seeds=tuple(seeds))
    emit_bench_line({
        "metric": "schedule_fuzz",
        "seeds": report["seeds"],
        "scenarios": report["scenarios"],
        "steps": report["steps"],
        "switches": report["switches"],
        "preemption_points": report["preemption_points"],
        "violations": len(report["violations"]),
        "traces": report["traces"],
    }, ledger=False)
    for v in report["violations"]:
        print(f"# schedule-fuzz violation: {v}", file=sys.stderr)
    return report


def bench_fuzz_schedules() -> None:
    """`--fuzz-schedules` / BENCH_FUZZ=1: the dynamic half of the
    thread-affinity contract. Every `# lint: atomic=` annotation in the
    runtime sources is backed by a schedule-fuzz scenario
    (grandine_tpu/testing/schedule_fuzz.COVERAGE); this entry point runs
    all scenarios under BENCH_FUZZ_SEEDS (default "0,1,2") and exits
    non-zero on any interleaving that breaks an invariant, deadlocks,
    or raises. No accelerator: pure host-thread interleaving."""
    _lint_preflight()
    seeds = [
        int(s) for s in
        os.environ.get("BENCH_FUZZ_SEEDS", "0,1,2").split(",") if s.strip()
    ]
    report = _fuzz_schedules(seeds)
    raise SystemExit(1 if report["violations"] else 0)


def bench_chaos() -> None:
    """Chaos soak for the verify plane's health supervisor (`--chaos` /
    BENCH_CHAOS=1): a seeded FaultPlan injects all five fault kinds
    (dispatch raise, settle raise, hang, wrong verdict, slow settle)
    over a KnownAnswerBackend while a mixed HIGH+LOW workload runs
    through the real scheduler. The headline check: every ticket
    settles, every verdict matches the fault-free truth table, and the
    breaker demonstrably opens/probes/re-closes. No accelerator needed —
    the device is a truth-table stub; this soaks the SUPERVISOR.

    The soak also audits the flight recorder's TIMELINE: every injected
    fault kind must leave a matching fault record (batch or canary),
    every SLO miss must carry a cause that an independent copy of the
    attribution rule agrees with, and the breaker records must trace a
    legal CLOSED→OPEN→HALF_OPEN→CLOSED walk.

    A second, fault-free soak segment replays part of the workload
    through the scheduler's FUSED single-dispatch path (the backend
    advertises `fuse_subgroup`) and asserts the fusion contract: zero
    standalone subgroup dispatches, zero post-warmup recompiles, fused
    kernel labels in flight, exact verdicts. `soak_ok` covers both.

    Knobs: BENCH_CHAOS_SEED, BENCH_CHAOS_JOBS, BENCH_CHAOS_RATE (total
    fault probability split evenly over the five kinds)."""
    import threading

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.runtime import health as _health
    from grandine_tpu.runtime import verify_scheduler as vs
    from grandine_tpu.runtime.flight import (
        BATCH,
        BREAKER,
        FlightRecorder,
        SLO_CAUSES,
    )
    from grandine_tpu.testing.chaos import (
        ChaosBackend,
        FAULT_KINDS,
        FaultPlan,
        KnownAnswerBackend,
    )
    from grandine_tpu.transition.genesis import interop_secret_key

    seed = int(os.environ.get("BENCH_CHAOS_SEED", "7"))
    n_jobs = int(os.environ.get("BENCH_CHAOS_JOBS", "400"))
    rate = float(os.environ.get("BENCH_CHAOS_RATE", "0.15"))

    # schedule-fuzz preflight: don't soak a supervisor whose concurrent
    # structures fail their fuzzed invariants under ANY interleaving —
    # the soak's own pass would not mean what it claims. Reuses the
    # chaos seed so the soak and its preflight vary together.
    if os.environ.get("BENCH_SKIP_FUZZ") != "1":
        if _fuzz_schedules(seeds=(seed,))["violations"]:
            print(
                "# chaos soak aborted: schedule-fuzz preflight found "
                "violations (BENCH_SKIP_FUZZ=1 overrides)",
                file=sys.stderr,
            )
            raise SystemExit(1)

    # one REAL signature's bytes reused for every item: the scheduler's
    # host prep decompresses each signature (and rejects infinity), but
    # the truth-table backend and host path judge by message only
    sk = interop_secret_key(0)
    sig_bytes = sk.sign(b"chaos-bench").to_bytes()
    pk = sk.public_key()

    # all-valid truth: a wrong_verdict flip can then only turn
    # valid->invalid, which host bisection corrects — the soak's
    # verdict-equivalence invariant holds for EVERY seed (a corrupt
    # device validating a truly-invalid batch is uncatchable per-batch;
    # that failure mode is the canary probe's job, tests/test_chaos.py)
    messages = [b"chaos-msg-%03d" % i + b"\x00" * 18 for i in range(64)]
    truth: "dict[bytes, bool]" = {m: True for m in messages}
    good_msg = b"canary-good" + b"\x00" * 21
    bad_msg = b"canary-bad" + b"\x00" * 22
    truth[good_msg] = True  # bad_msg absent -> False
    canary_sig = A.Signature(A.g2_from_bytes(sig_bytes, subgroup_check=False))
    specimens = [
        _health.CanarySpecimen(good_msg, canary_sig, [pk], expected=True),
        _health.CanarySpecimen(bad_msg, canary_sig, [pk], expected=False),
    ]

    plan = FaultPlan(seed=seed, rates={k: rate / 5.0 for k in FAULT_KINDS})
    chaos = ChaosBackend(KnownAnswerBackend(truth), plan, slow_s=0.02)
    # SLO budgets tightened to 5ms so every fault-lengthened batch trips
    # a miss with an attributable cause (production budgets would
    # swallow a 20ms slow-settle without a trace)
    flight = FlightRecorder(
        capacity=8192,
        slo_budgets={"sync_message": 0.005, "block": 0.005},
    )
    supervisor = _health.BackendHealthSupervisor(
        settle_timeout_s=0.2,  # hangs cost 200ms, not the 5s default
        probe=_health.make_canary_probe(chaos, specimens, timeout_s=0.2),
        backoff_initial_s=0.05,
        backoff_max_s=0.4,
        flight=flight,
        rng=__import__("random").Random(seed),
    )
    sched = vs.VerifyScheduler(
        backend=chaos, use_device=True, health=supervisor, flight=flight
    )
    # the host path (degradation target + bisection leaf) answers from
    # the same truth table -- the fault-free expectation is exact
    real_host_check = vs.host_check_item
    vs.host_check_item = lambda item: truth.get(bytes(item.message), False)

    # steady-state shape discipline: the soak models a node whose warmup
    # already sealed the manifest — the truth-table backend dispatches no
    # real kernels, so ANY post-seal recompile means a fault-injection
    # path (bisection, degradation, canary) silently formed a novel
    # device shape (tools/shapes contract)
    from grandine_tpu.tpu import bls as B

    B.reset_shape_tracking()
    B.declare_warmup_complete()

    tickets: "list[tuple]" = []
    lock = threading.Lock()
    rng_jobs = __import__("random").Random(seed ^ 0xCAFE)
    job_specs = [
        (
            "sync_message" if rng_jobs.random() < 0.75 else "block",
            [rng_jobs.choice(messages)
             for _ in range(rng_jobs.randrange(1, 4))],
        )
        for _ in range(n_jobs)
    ]

    def producer(specs) -> None:
        mine = []
        for lane, msgs in specs:
            items = [
                vs.VerifyItem(m, sig_bytes, public_keys=(pk,)) for m in msgs
            ]
            expected = all(truth[m] for m in msgs)
            mine.append((sched.submit(lane, items), expected))
        with lock:
            tickets.extend(mine)

    # mid-soak profiler capture toggle: an operator flipping the debug
    # profile endpoint on a live node must not perturb the verify plane.
    # The session is annotation-only (no trace dir) and the recompile
    # gate below (`verify_recompiles_total == 0`) now also certifies
    # that the toggle introduced zero novel device shapes and — via the
    # verdict-equivalence check — zero verdict changes.
    from grandine_tpu.runtime.profiler import KernelProfiler

    soak_prof = KernelProfiler()
    flight.profiler = soak_prof

    t0 = time.time()
    threads = [
        threading.Thread(target=producer, args=(job_specs[i::4],))
        for i in range(4)
    ]
    try:
        for t in threads:
            t.start()
        soak_prof.start(note="chaos mid-soak capture toggle")
        for t in threads:
            t.join()
        sched.flush(120.0)
        soak_prof.stop()
    finally:
        sched.stop()
        chaos.release_hangs()
    wall_s = time.time() - t0

    unsettled = sum(1 for tk, _ in tickets if not tk.done())
    mismatches = sum(
        1 for tk, expected in tickets
        if tk.done() and not tk.dropped and tk.ok is not expected
    )
    dropped = sum(1 for tk, _ in tickets if tk.dropped)
    br = supervisor.breaker.stats
    agg = {
        k: sum(st[k] for st in sched.stats.values())
        for k in ("batches", "device_faults", "breaker_skips", "retries")
    }
    # ---- deterministic fault→record probes: one scripted single-job
    # plane per fault kind, the fault landed on the batch's VERIFY seam
    # call (call 0 is the subgroup check), asserting the matching flight
    # entry and — for slow_settle — the SLO-miss cause. The random soak
    # above cannot carry this mapping: an injection landing on a retry
    # of an already-faulted batch or inside bisection descent leaves
    # only aggregate (or by-design zero) evidence.
    problems: "list[str]" = []
    probe_fault_of = {
        "raise_dispatch": "dispatch",
        "raise_settle": "settle",
        "hang": "watchdog",
        "wrong_verdict": "verdict",
        "slow_settle": None,
    }

    def probe_kind(kind: str) -> None:
        plan_k = FaultPlan(script=[None, kind])
        chaos_k = ChaosBackend(KnownAnswerBackend(truth), plan_k,
                               slow_s=0.02)
        fl_k = FlightRecorder(slo_budgets={"block": 0.0005})
        sup_k = _health.BackendHealthSupervisor(
            settle_timeout_s=0.2,
            probe=_health.make_canary_probe(chaos_k, specimens,
                                            timeout_s=0.2),
            backoff_initial_s=0.01,
            backoff_max_s=0.05,
            flight=fl_k,
            rng=__import__("random").Random(seed),
        )
        s_k = vs.VerifyScheduler(
            backend=chaos_k, use_device=True, health=sup_k, flight=fl_k
        )
        try:
            tk = s_k.submit("block", [
                vs.VerifyItem(messages[0], sig_bytes, public_keys=(pk,))
            ])
            s_k.flush(30.0)
        finally:
            s_k.stop()
            chaos_k.release_hangs()
        recs = fl_k.snapshot(kind=BATCH)
        if not tk.done() or tk.ok is not True:
            problems.append(f"{kind}: probe ticket did not settle True")
            return
        want = probe_fault_of[kind]
        if want is not None:
            if not any(r.fault == want for r in recs):
                problems.append(
                    f"{kind}: no batch record with fault {want!r}"
                )
            return
        slowed = [
            r for r in recs
            if r.device_s >= 0.02 * 0.9 and r.fault is None
        ]
        if not slowed:
            problems.append("slow_settle: no fault-free slowed record")
        elif not any(
            r.slo_miss and r.slo_cause == "device" for r in slowed
        ):
            problems.append(
                "slow_settle: slowed batch did not miss SLO as 'device'"
            )

    for fault_kind in FAULT_KINDS:
        probe_kind(fault_kind)

    recompiles = B.post_warmup_recompiles()

    # ---- fused-path soak: the same truth-table plane through the
    # scheduler's FUSED single-dispatch path (backend advertises
    # fuse_subgroup). Asserts the fusion contract under soak: zero
    # standalone subgroup dispatches, zero post-warmup recompiles on
    # the fused path, fused kernel labels in flight, verdicts exact.
    fused_problems: "list[str]" = []
    kab_fused = KnownAnswerBackend(truth)
    kab_fused.fuse_subgroup = True
    sub_dispatches: "list[int]" = []
    _plain_sub = kab_fused.g2_subgroup_check_batch_async

    def _counting_sub(points):
        sub_dispatches.append(len(points))
        return _plain_sub(points)

    kab_fused.g2_subgroup_check_batch_async = _counting_sub
    B.reset_shape_tracking()
    B.declare_warmup_complete()
    fl_fused = FlightRecorder(capacity=4096)
    s_fused = vs.VerifyScheduler(
        backend=kab_fused, use_device=True, flight=fl_fused
    )
    fused_tickets: "list[tuple]" = []
    try:
        for lane, msgs in job_specs[:128]:
            f_items = [
                vs.VerifyItem(m, sig_bytes, public_keys=(pk,)) for m in msgs
            ]
            fused_tickets.append(
                (s_fused.submit(lane, f_items), all(truth[m] for m in msgs))
            )
        s_fused.flush(60.0)
    finally:
        s_fused.stop()
    fused_recompiles = B.post_warmup_recompiles()
    fused_mismatches = sum(
        1 for tk, expected in fused_tickets
        if not tk.done() or tk.dropped or tk.ok is not expected
    )
    fused_labels = {r.kernel for r in fl_fused.snapshot(kind=BATCH)}
    if sub_dispatches:
        fused_problems.append(
            f"fused path dispatched {len(sub_dispatches)} standalone "
            f"subgroup checks"
        )
    if fused_recompiles:
        fused_problems.append(
            f"fused path recompiled {fused_recompiles}x post-warmup"
        )
    if fused_mismatches:
        fused_problems.append(
            f"fused path verdict mismatches: {fused_mismatches}"
        )
    if fused_labels - {"fast_aggregate_fused"}:
        fused_problems.append(
            f"non-fused kernel labels on fused path: {sorted(fused_labels)}"
        )
    fused_ok = not fused_problems

    vs.host_check_item = real_host_check

    # ---- soak flight audit: the recorder must EXPLAIN the random soak
    batches = flight.snapshot(kind=BATCH)
    breaker_walk = [r.breaker_state for r in flight.snapshot(kind=BREAKER)]
    # every SLO miss carries a cause the attribution rule (re-derived
    # here as an independent oracle) agrees with
    slo_missed = [r for r in batches if r.slo_miss]
    if not slo_missed:
        problems.append("5ms budgets produced zero SLO misses")
    for r in slo_missed:
        exec_s = r.device_s + r.host_s
        if r.breaker_state == "open" and r.device_s == 0.0:
            want = "breaker_open"
        elif r.bisect_s > exec_s and r.bisect_s > r.queue_wait_s:
            want = "bisection"
        elif exec_s >= r.queue_wait_s:
            want = "device"
        else:
            want = "queue_wait"
        if r.slo_cause not in SLO_CAUSES:
            problems.append(f"slo cause {r.slo_cause!r} outside enum")
            break
        if r.slo_cause != want:
            problems.append(
                f"slo cause {r.slo_cause!r} != expected {want!r}"
            )
            break
    # breaker transitions in the timeline must be a legal walk from
    # CLOSED, and must cover the traversal the stats counters claim
    legal = {
        "closed": {"open"},
        "open": {"half_open"},
        "half_open": {"closed", "open"},
    }
    prev = "closed"
    for s in breaker_walk:
        if s not in legal.get(prev, ()):
            problems.append(f"illegal breaker transition {prev}->{s}")
            break
        prev = s
    if br["opens"] > 0 and "open" not in breaker_walk:
        problems.append("breaker opened but no OPEN flight record")
    if br["closes"] > 0 and not (
        "half_open" in breaker_walk and "closed" in breaker_walk
    ):
        problems.append("breaker re-closed but walk lacks half_open/closed")
    flight_ok = not problems

    soak_ok = (
        unsettled == 0 and mismatches == 0 and recompiles == 0
        and flight_ok and fused_ok
    )
    emit_bench_line(
        {
            "metric": "verify_chaos_soak",
            "unit": "faults survived",
            "value": sum(plan.injected.values()),
            "seed": seed,
            "jobs": n_jobs,
            "wall_s": round(wall_s, 2),
            "injected": plan.injected,
            "seam_calls": plan.calls,
            "breaker": {
                "opens": br["opens"], "closes": br["closes"],
                "probes_passed": br["probes_passed"],
                "probes_failed": br["probes_failed"],
                "faults": br["faults"],
            },
            "scheduler": agg,
            "dropped": dropped,
            "unsettled": unsettled,
            "verdict_mismatches": mismatches,
            "verify_recompiles_total": recompiles,
            "flight_ok": flight_ok,
            "flight_problems": problems,
            "fused_path": {
                "jobs": len(fused_tickets),
                "subgroup_dispatches": len(sub_dispatches),
                "verify_recompiles_total": fused_recompiles,
                "verdict_mismatches": fused_mismatches,
                "ok": fused_ok,
                "problems": fused_problems,
            },
            "soak_ok": soak_ok,
        },
        config={"seed": seed, "jobs": n_jobs},
    )
    emit_bench_line(
        {
            "metric": "verify_flight_summary",
            "value": flight.summary(),
        },
        ledger=False,
    )
    print(
        f"# chaos soak: {sum(plan.injected.values())} faults over "
        f"{plan.calls} seam calls; breaker opened {br['opens']}x, "
        f"re-closed {br['closes']}x; {recompiles} steady-state "
        f"recompiles; fused path {fused_recompiles} recompiles / "
        f"{len(sub_dispatches)} subgroup dispatches over "
        f"{len(fused_tickets)} jobs; flight timeline "
        + ("consistent; OK" if soak_ok else
           f"problems={problems + fused_problems}; FAILED (see "
           "verdict_mismatches / verify_recompiles_total / "
           "flight_problems / fused_path)"),
        file=sys.stderr,
    )
    if not soak_ok:
        raise SystemExit(1)


def bench_adversarial() -> None:
    """Adversarial isolation soak (runs with `--chaos`): REAL device
    kernels, real BLS signatures, a trickle of forged ones. BENCH_CONFIG4
    measured the pre-isolation collapse — 1.5% forged cut firehose
    throughput 121→13 atts/s and pushed item p50 0.7s→56s, because a
    poisoned batch fell back to linear host bisection. With the
    on-device fault localizer (runtime/isolation.py) a failed batch
    costs O(log n) warm device passes plus host checks of only the
    named-bad leaves, so adversarial traffic is a bounded tax.

    Gates (exit 1 on miss): forged-phase throughput >= 0.5x clean,
    forged-phase p50 <= 5x clean, ZERO steady-state recompiles, and no
    failed batch exceeding the ceil(log2(bucket))+1 device-pass bound.
    Verdicts are also checked against ground truth — forged tickets
    False, honest True. Knobs: BENCH_ADV_ITEMS, BENCH_ADV_FORGED_PCT."""
    import statistics

    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime import health as _health
    from grandine_tpu.runtime import isolation as iso
    from grandine_tpu.runtime import verify_scheduler as vs
    from grandine_tpu.runtime.thread_pool import Priority
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.transition.genesis import interop_secret_key

    n_items = int(os.environ.get("BENCH_ADV_ITEMS", "96"))
    forged_pct = float(os.environ.get("BENCH_ADV_FORGED_PCT", "1.5"))
    batch = 8  # small lane: bucket 8 compiles fast on the CPU platform

    sk = interop_secret_key(0)
    pk = sk.public_key()
    metrics = Metrics()
    backend = B.TpuBlsBackend(metrics=metrics)

    # warm every shape both phases can form (the aggregate+subgroup
    # verify buckets, and the localization ladder for full and tail
    # batches), then seal: the soak models a post-warmup node, so any
    # recompile after this point is a gate failure. The ledger resets
    # BEFORE warming — the warm shapes must stay on it, or their first
    # live dispatch would count as a phantom recompile.
    B.reset_shape_tracking()
    sig_w = sk.sign(b"adv-warm")
    h_w = hash_to_g2(b"adv-warm")
    for b in (4, batch):
        msgs = [b"adv-warm-%d" % i for i in range(b)]
        backend.fast_aggregate_verify_batch(msgs, [sig_w] * b, [[pk]] * b)
        backend.g2_subgroup_check_batch([h_w] * b)
        for g in iso.ladder(b):
            backend.rlc_partition_verify(msgs, [sig_w] * b, [[pk]] * b, g)
    B.declare_warmup_complete()

    def run_phase(tag: str, forged_idx: "set[int]"):
        sched = vs.VerifyScheduler(
            backend=backend,
            lanes=(vs.LaneConfig("adv", Priority.LOW, batch, 0.005, 4096,
                                 shed=False),),
            use_device=True,
            metrics=metrics,
            # generous watchdog: the soak gates ISOLATION economics, and
            # the CPU-emulated kernels here can blow the 5s production
            # default without that meaning anything about localization
            health=_health.BackendHealthSupervisor(
                metrics=metrics, settle_timeout_s=60.0
            ),
        )
        tickets = []
        t0 = time.time()
        try:
            for i in range(n_items):
                msg = b"adv-%s-%04d" % (tag.encode(), i)
                signed = msg if i not in forged_idx else b"forged-" + msg
                item = vs.VerifyItem(
                    msg, sk.sign(signed).to_bytes(), public_keys=(pk,)
                )
                tickets.append((sched.submit("adv", [item]),
                                i not in forged_idx))
            sched.flush(600.0)
        finally:
            sched.stop()
        wall = time.time() - t0
        lat = [tk.settled_at - tk.enqueued_at for tk, _ in tickets]
        wrong = sum(1 for tk, expect in tickets if tk.ok is not expect)
        return {
            "throughput": n_items / wall,
            "p50_s": statistics.median(lat),
            "wall_s": wall,
            "verdict_mismatches": wrong,
        }

    clean = run_phase("clean", set())
    n_forged = max(2, round(n_items * forged_pct / 100.0))
    step = n_items // n_forged
    forged = run_phase(
        "adv", {i * step + step // 2 for i in range(n_forged)}
    )

    recompiles = B.post_warmup_recompiles()
    invalid_batches = metrics.verify_lane_batches.labels(
        "adv", "invalid"
    ).value
    passes = {
        k: metrics.verify_isolation_passes.labels(k).value
        for k in ("rlc_partition", "g2_subgroup", "host")
    }
    device_passes = passes["rlc_partition"] + passes["g2_subgroup"]
    pass_bound = invalid_batches * iso.max_device_passes(batch)
    throughput_ratio = forged["throughput"] / max(clean["throughput"], 1e-9)
    p50_ratio = forged["p50_s"] / max(clean["p50_s"], 1e-9)

    soak_ok = (
        clean["verdict_mismatches"] == 0
        and forged["verdict_mismatches"] == 0
        and recompiles == 0
        and invalid_batches > 0
        and device_passes <= pass_bound
        and throughput_ratio >= 0.5
        and p50_ratio <= 5.0
    )
    emit_bench_line(
        {
            "metric": "verify_adversarial_soak",
            "unit": "x clean throughput under forgery",
            "value": round(throughput_ratio, 3),
            "items_per_phase": n_items,
            "forged_pct": forged_pct,
            "forged_items": n_forged,
            "clean": {k: round(v, 4) if isinstance(v, float) else v
                      for k, v in clean.items()},
            "forged": {k: round(v, 4) if isinstance(v, float) else v
                       for k, v in forged.items()},
            "p50_ratio": round(p50_ratio, 3),
            "invalid_batches": invalid_batches,
            "isolation_passes": passes,
            "device_pass_bound": pass_bound,
            "verify_recompiles_total": recompiles,
            "soak_ok": soak_ok,
        },
        config={"items_per_phase": n_items, "forged_pct": forged_pct},
    )
    print(
        f"# adversarial soak: {n_forged} forged of {n_items} "
        f"({forged_pct}%): throughput {throughput_ratio:.2f}x clean "
        f"(gate >=0.5), p50 {p50_ratio:.2f}x (gate <=5), "
        f"{int(device_passes)} device localization passes over "
        f"{int(invalid_batches)} failed batches (bound "
        f"{int(pass_bound)}), {recompiles} recompiles; "
        + ("OK" if soak_ok else "FAILED"),
        file=sys.stderr,
    )
    if not soak_ok:
        raise SystemExit(1)


def bench_coldstart_child(mode: str) -> None:
    """One simulated node restart (child process of bench_coldstart).

    Timeline: import + backend init (startup), optional manifest warmup,
    then the FIRST live batch — the serve stall is what a validator
    waiting on a fresh restart actually experiences. `nowarm` seals the
    ledger without warming (a node that declared ready unwarmed), so its
    first batch both stalls AND counts as a steady-state recompile —
    demonstrating exactly what `verify_recompiles_total` catches."""
    t0 = time.time()
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.runtime import warmup
    from grandine_tpu.tpu import bls as B

    warmup.enable_persistent_cache()
    backend = B.TpuBlsBackend()
    startup_s = time.time() - t0

    buckets = [("aggregate", 4)]
    extra = os.environ.get("BENCH_COLDSTART_BUCKETS")
    if extra:  # e.g. "aggregate:8,subgroup:64" widens the warmed set
        buckets += [
            (k, int(b)) for k, b in
            (pair.split(":") for pair in extra.split(","))
        ]
    warmup_s = 0.0
    if mode == "warm":
        t1 = time.time()
        warmup.warm_all(
            buckets=buckets, backend=backend, seal=True, enable_cache=False
        )
        warmup_s = time.time() - t1
    else:
        B.declare_warmup_complete()

    pk = A.PublicKey(G1)
    sig = A.Signature(hash_to_g2(b"coldstart"))
    t2 = time.time()
    backend.fast_aggregate_verify_batch(
        [b"cold-%d" % i for i in range(3)], [sig] * 3, [[pk]] * 3
    )
    serve_stall_s = time.time() - t2
    emit_bench_line({
        "mode": mode,
        "startup_s": round(startup_s, 3),
        "warmup_s": round(warmup_s, 3),
        "serve_stall_s": round(serve_stall_s, 3),
        # warmup overlaps checkpoint sync in the real node
        # (warm_in_background), so restart-to-first-verified-batch is
        # startup + the stall the first batch sees, not + warmup
        "restart_to_first_verified_batch_s": round(
            startup_s + serve_stall_s, 3
        ),
        "post_warmup_recompiles": B.post_warmup_recompiles(),
    }, ledger=False)  # parent re-emits the headline; child line is IPC


def bench_coldstart() -> None:
    """`--coldstart`: process-restart-to-first-verified-batch, with and
    without the manifest warmup, against one shared fresh persistent
    cache (the warm child runs first and primes it — the restart
    scenario where a previous process life already compiled). Prints one
    parseable JSON line; exits 1 unless warm is strictly faster with
    zero post-warmup recompiles.

    This parent must NOT import JAX: a chip belongs to one process at a
    time, and each child needs it (tests/test_perf_ledger.py asserts the
    module imports without JAX). The children run one after the other.
    The shared cache is a FIXED sub-directory of the program's cache
    directory, emptied first — the path is part of the cache key, so a
    temporary name could never be hit again."""
    import shutil
    import subprocess

    from grandine_tpu.runtime.warmup import jit_cache_dir

    _lint_preflight()
    cache_dir = os.path.join(jit_cache_dir(), "coldstart")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir)
    env = {
        **os.environ,
        "JAX_COMPILATION_CACHE_DIR": cache_dir,
        "BENCH_SKIP_LINT": "1",
        "BENCH_SKIP_RANGES": "1",  # parent preflight already certified
    }

    def run_child(mode: str) -> dict:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--coldstart-child", mode],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        wall = time.time() - t0
        report = None
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                report = json.loads(line)
                break
            except (json.JSONDecodeError, ValueError):
                continue
        if proc.returncode != 0 or report is None:
            print(proc.stdout, file=sys.stderr)
            print(proc.stderr, file=sys.stderr)
            raise SystemExit(f"coldstart child {mode!r} failed")
        report["child_wall_s"] = round(wall, 3)
        return report

    warm = run_child("warm")
    nowarm = run_child("nowarm")
    warm_rtfb = warm["restart_to_first_verified_batch_s"]
    nowarm_rtfb = nowarm["restart_to_first_verified_batch_s"]
    ok = (
        warm_rtfb < nowarm_rtfb
        and warm["post_warmup_recompiles"] == 0
        and nowarm["post_warmup_recompiles"] > 0
    )
    emit_bench_line({
        "metric": "coldstart_restart_to_first_verified_batch",
        "unit": "s",
        "value": warm_rtfb,
        "vs_nowarm": nowarm_rtfb,
        "warm": warm,
        "nowarm": nowarm,
        "warm_faster": warm_rtfb < nowarm_rtfb,
        "post_warmup_recompiles": warm["post_warmup_recompiles"],
        "coldstart_ok": ok,
    })
    print(
        f"# coldstart: warm {warm_rtfb:.3f}s vs nowarm {nowarm_rtfb:.3f}s "
        f"to first verified batch (warm paid {warm['warmup_s']:.1f}s "
        f"warmup overlapped with sync); "
        + ("OK" if ok else "FAILED"),
        file=sys.stderr,
    )
    if not ok:
        raise SystemExit(1)


def _build_replay_chain(n_blocks: int, n_validators: int):
    """Signature-dense minimal-preset chain plus the per-block signature
    sets it generates, collected ONCE with a CollectingVerifier — the
    state transition is identical work on both sides of the comparison,
    so it runs off the verify clock."""
    from grandine_tpu.consensus.verifier import CollectingVerifier
    from grandine_tpu.runtime.replay import _WindowSink
    from grandine_tpu.transition.combined import custom_state_transition
    from grandine_tpu.transition.genesis import interop_genesis_state
    from grandine_tpu.types.config import Config
    from grandine_tpu.validator.duties import produce_attestations, produce_block

    cfg = Config.minimal()
    genesis = interop_genesis_state(n_validators, cfg)
    state, chain, atts = genesis, [], []
    for slot in range(1, n_blocks + 1):
        blk, state = produce_block(
            state, slot, cfg, attestations=atts,
            full_sync_participation=True,
        )
        chain.append(blk)
        atts = produce_attestations(state, cfg, slot=slot)
    sink = _WindowSink()
    verifier = CollectingVerifier(sink)
    slices, cur = [], genesis
    for blk in chain:
        lo = len(sink.items)
        cur = custom_state_transition(cur, blk, cfg, verifier)
        slices.append((lo, len(sink.items)))
    return cfg, sink.items, slices


def bench_replay() -> None:
    """`--replay`: cross-block bulk signature verification (ONE device
    batch per window, the BulkReplayPipeline dispatch shape) vs the
    legacy per-block `verify_block_batch` shape (a FRESH verifier and
    one dispatch per block) over one identical pre-collected signature
    workload. Prints one parseable JSON line
    (metric `replay_bulk_vs_perblock`)."""
    _lint_preflight()
    # Default 44 blocks ≈ 218 sig-sets → 0.85 fill of the 256-lane
    # multi_verify bucket.  At exactly 32 blocks (158 sig-sets) the pow-2
    # padding drops fill to 0.62 and the bulk rate with it — the reported
    # window/sigsets fields make the fill visible.
    n_blocks = int(os.environ.get("BENCH_REPLAY_BLOCKS", "44"))
    n_validators = int(os.environ.get("BENCH_REPLAY_VALIDATORS", "64"))
    window = int(os.environ.get("BENCH_REPLAY_WINDOW", str(n_blocks)))
    use_device = os.environ.get("BENCH_REPLAY_DEVICE", "1") != "0"
    reps = int(os.environ.get("BENCH_REPLAY_REPS", "3"))
    if use_device:
        _enable_compilation_cache()

    t_prep = time.time()
    cfg, items, slices = _build_replay_chain(n_blocks, n_validators)
    prep_s = time.time() - t_prep

    from grandine_tpu.consensus.verifier import MultiVerifier, TpuVerifier
    from grandine_tpu.runtime.replay import BulkReplayPipeline

    pipe = BulkReplayPipeline(cfg, use_device=use_device, window_size=window)

    def run_bulk() -> None:
        # flight-instrumented like BulkReplayPipeline.replay: the bench
        # drives _dispatch_batch directly, so it files its own records
        for b_lo in range(0, len(slices), window):
            b_hi = min(b_lo + window, len(slices))
            i_lo, i_hi = slices[b_lo][0], slices[b_hi - 1][1]
            fl = pipe.flight.begin_batch(
                "replay", "multi_verify" if use_device else "host",
                i_hi - i_lo,
            )
            t_d = time.time()
            ok = pipe._dispatch_batch(items[i_lo:i_hi])()
            (fl.note_device if use_device else fl.note_host)(
                time.time() - t_d
            )
            fl.finish(ok)
            if not ok:
                raise SystemExit("bulk replay batch rejected valid blocks")

    def run_per_block() -> None:
        for i_lo, i_hi in slices:
            v = TpuVerifier() if use_device else MultiVerifier()
            for it in items[i_lo:i_hi]:
                v.verify_aggregate(it.message, it.signature, it.resolve_keys())
            v.finish()

    def timed(fn) -> float:
        fn()  # warm pass: compiles + caches off the clock
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            fn()
            best = min(best, time.time() - t0)
        return best

    bulk_s = timed(run_bulk)
    base_s = timed(run_per_block)
    bulk_rate = len(items) / bulk_s if bulk_s else 0.0
    base_rate = len(items) / base_s if base_s else 0.0
    speedup = bulk_rate / base_rate if base_rate else 0.0
    target_met = window < 32 or speedup >= 5.0
    emit_bench_line({
        "metric": "replay_bulk_vs_perblock",
        "unit": "sigsets/s",
        "value": round(bulk_rate, 1),
        "per_block": round(base_rate, 1),
        "speedup": round(speedup, 2),
        "blocks": n_blocks,
        "window": window,
        "sigsets": len(items),
        "device": use_device,
        "prep_s": round(prep_s, 1),
        "target_met": target_met,
    }, config={"blocks": n_blocks, "window": window,
               "device": use_device})
    print(
        f"# replay: bulk {bulk_rate:.1f} vs per-block {base_rate:.1f} "
        f"sigsets/s ({speedup:.2f}x) over {n_blocks} blocks, "
        f"window {window}, device={use_device}",
        file=sys.stderr,
    )
    emit_bench_line(
        {
            "metric": "verify_flight_summary",
            "value": pipe.flight.summary(),
        },
        stream=sys.stderr,
        ledger=False,
    )
    if os.environ.get("BENCH_REPLAY_STRICT") == "1" and not target_met:
        raise SystemExit(1)


# ------------------------------------------------------------------ mainnet

#: mainnet spec constants the --mainnet soak derives its arrival rates
#: from (README.md "Mainnet scale" reproduces this table)
MAINNET_SLOTS_PER_EPOCH = 32
MAINNET_SECONDS_PER_SLOT = 12.0
MAINNET_COMMITTEES_PER_SLOT = 64
MAINNET_AGGREGATORS_PER_COMMITTEE = 16
MAINNET_SYNC_COMMITTEE_SIZE = 512
MAINNET_SYNC_SUBNETS = 4
MAINNET_MAX_BLOBS = 6


def derive_mainnet_rates(validators: int) -> "dict[str, float]":
    """Per-topic full-mix arrival rates (events/second), derived from the
    spec constants above — the --mainnet soak's drive table.

      block              1 proposal / slot
      blob_header        MAX_BLOBS sidecar headers / slot (worst case)
      aggregate          committees × aggregators / slot (the attestation
                         firehose: 64 × 16 = 1024 aggregates/slot)
      sync_message       SYNC_COMMITTEE_SIZE messages / slot
      sync_contribution  subnets × aggregators / slot
      slasher_indices    every validator attests once per epoch and every
                         attesting index is one span update:
                         V / (SLOTS_PER_EPOCH × SECONDS_PER_SLOT)
      slashing / exit / bls_change / quarantine
                         administrative trickle lanes at nominal rates
                         (gossip arrival is sparse; the spec only caps
                         per-block inclusion) — driven to keep the lanes
                         warm, not as a throughput claim
    """
    per_slot = MAINNET_SECONDS_PER_SLOT
    return {
        "block": 1.0 / per_slot,
        "blob_header": MAINNET_MAX_BLOBS / per_slot,
        "aggregate": (
            MAINNET_COMMITTEES_PER_SLOT * MAINNET_AGGREGATORS_PER_COMMITTEE
        ) / per_slot,
        "sync_message": MAINNET_SYNC_COMMITTEE_SIZE / per_slot,
        "sync_contribution": (
            MAINNET_SYNC_SUBNETS * MAINNET_AGGREGATORS_PER_COMMITTEE
        ) / per_slot,
        "slashing": 0.1,
        "exit": 0.1,
        "bls_change": 0.1,
        "quarantine": 0.5,
        "slasher_indices": validators / (
            MAINNET_SLOTS_PER_EPOCH * per_slot
        ),
    }


def bench_mainnet() -> None:
    """`--mainnet`: full-mix soak at mainnet-derived arrival rates.

    Drives every scheduler lane plus a bulk-replay lane and the slasher
    span plane CONCURRENTLY for BENCH_MAINNET_SECONDS, against a
    registry built at BENCH_MAINNET_VALIDATORS keys (default scaled down
    for a 1-core CPU host; 1<<20 on real hardware), then gates on:

      * per-lane p50/p95 enqueue→settle vs the flight recorder's SLO
        budgets (× BENCH_MAINNET_SLO_SCALE),
      * ZERO post-warmup recompiles (the span-update grid kernel is
        warmed and the shape ledger sealed before the soak),
      * slasher keep-up — span-update throughput ≥ the derived
        attestation-index arrival rate at the soak's scale,
      * the batched slasher path ≥10× the per-validator reference loop
        on one 512-index aggregate (the PR's headline diagnostic),
      * registry churn uploads O(new): appends within capacity upload
        exactly the new rows' bytes and never reallocate the mirror.

    The scheduler lanes ride the synthetic device model (measuring
    scheduling under mainnet rates, not BLS crypto — benched elsewhere);
    the slasher span merges are REAL jax dispatches through the sealed
    shape ledger, so the zero-recompile gate has teeth. Time is
    compressed: a slot lasts BENCH_MAINNET_SLOT_S seconds (default 1.2,
    i.e. 10× compression) and every arrival rate scales up with it.
    Emits ONE parseable JSON line (metric `mainnet_soak`); gate failures
    exit 1 unless BENCH_MAINNET_STRICT=0."""
    _lint_preflight()
    import threading

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.runtime.flight import (
        DEFAULT_SLO_BUDGETS,
        FlightRecorder,
    )
    from grandine_tpu.runtime.verify_scheduler import (
        VerifyItem,
        VerifyScheduler,
    )
    from grandine_tpu.slasher import Slasher
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import limbs as L
    from grandine_tpu.tpu import spans as SP
    from grandine_tpu.tpu.registry import (
        MAINNET_CAPACITY,
        DevicePubkeyRegistry,
    )

    n_validators = int(
        os.environ.get("BENCH_MAINNET_VALIDATORS", str(1 << 12))
    )
    soak_s = float(os.environ.get("BENCH_MAINNET_SECONDS", "10"))
    slot_s = float(os.environ.get("BENCH_MAINNET_SLOT_S", "1.2"))
    slo_scale = float(os.environ.get("BENCH_MAINNET_SLO_SCALE", "1"))
    strict = os.environ.get("BENCH_MAINNET_STRICT", "1") == "1"
    _enable_compilation_cache()

    scale = n_validators / float(MAINNET_CAPACITY)
    compress = MAINNET_SECONDS_PER_SLOT / slot_s
    rates_mainnet = derive_mainnet_rates(MAINNET_CAPACITY)
    #: the soak's driven rates: topic rates are validator-count
    #: independent (committee structure is fixed); the slasher index
    #: stream scales with the validator set; everything speeds up by the
    #: time-compression factor
    arrival_idx_s = (
        derive_mainnet_rates(n_validators)["slasher_indices"] * compress
    )

    # ---- registry at scale + the O(new) churn segment
    t_prep = time.time()
    churn_batch, churn_batches = 64, 8
    base_count = n_validators - churn_batch * churn_batches
    a = 0x1357_0000_DEAD_BEEF_1234_5678_9ABC_DEF0
    b = 0x2468_ACE0_2468_ACE0_2468_ACE1
    acc = G1.mul(a)
    step = G1.mul(b)
    pubkeys = []
    for _ in range(n_validators):
        pubkeys.append(A.PublicKey(acc).to_bytes())
        acc = acc + step
    registry = DevicePubkeyRegistry()
    registry.ensure(tuple(pubkeys[:base_count]))
    stats0 = dict(registry.stats)
    for i in range(churn_batches):
        registry.ensure(tuple(pubkeys[: base_count + (i + 1) * churn_batch]))
    churn_rows = churn_batch * churn_batches
    churn_uploaded = (
        registry.stats["uploaded_bytes"] - stats0["uploaded_bytes"]
    )
    row_bytes = L.NLIMBS * 4 * 2
    churn_ok = (
        churn_uploaded == churn_rows * row_bytes
        and registry.stats["host_grows"] == stats0["host_grows"]
    )
    pk_tuple = tuple(pubkeys)
    prep_s = time.time() - t_prep

    # ---- warm the span grid, then SEAL: the soak must not compile
    B.reset_shape_tracking()
    plane = SP.SpanPlane()
    t_warm = time.time()
    for wb in (256, 512, 1024, 2048, 4096):
        plane.update(
            np.full((wb, SP.SPAN_GRID_EPOCHS), SP.INT32_UNSET, np.int32),
            np.zeros((wb, SP.SPAN_GRID_EPOCHS), np.int32),
            np.full((wb,), 8, np.int32),
            np.full((wb,), 9, np.int32),
            0,
        )
    warm_s = time.time() - t_warm
    B.declare_warmup_complete()

    slasher = Slasher(span_plane=plane)
    flight = FlightRecorder()
    call_latency_s = float(os.environ.get("BENCH_SCHED_CALL_MS", "2")) / 1e3
    per_sig_s = float(os.environ.get("BENCH_SCHED_SIG_US", "20")) / 1e6

    class _ModelDeviceScheduler(VerifyScheduler):
        """Real queueing/coalescing/settle pipeline over a synthetic
        device (fixed call latency + per-signature cost)."""

        def _device_dispatch(self, lane, items):
            n = len(items)

            def settle() -> bool:
                time.sleep(call_latency_s + per_sig_s * n)
                return True

            return settle

    sched = _ModelDeviceScheduler(use_device=True, flight=flight)
    item = VerifyItem(b"\x11" * 32, b"\x22" * 96, public_keys=("bench",))
    lane_names = (
        "block", "blob_header", "sync_contribution", "sync_message",
        "slashing", "exit", "bls_change", "quarantine",
    )
    tickets: "dict[str, list]" = {n: [] for n in lane_names}
    tickets_lock = threading.Lock()
    stop_evt = threading.Event()

    def lane_producer(lane: str, rate_per_s: float) -> None:
        interval = 1.0 / rate_per_s
        mine = []
        nxt = time.time()
        while not stop_evt.is_set():
            mine.append(sched.submit(lane, [item]))
            nxt += interval
            delay = nxt - time.time()
            if delay > 0:
                stop_evt.wait(delay)
        with tickets_lock:
            tickets[lane].extend(mine)

    # ---- slasher feed: one permutation per epoch (each validator
    # attests once per epoch — the rates make this exactly self-
    # consistent: arrival_idx_s × one compressed epoch = n_validators)
    committee = max(1, n_validators // (
        MAINNET_SLOTS_PER_EPOCH * MAINNET_COMMITTEES_PER_SLOT
    ))
    window_s = 0.5
    rng = np.random.default_rng(0x3A1A57E5)
    slasher_stats = {"indices": 0, "busy_s": 0.0, "hits": 0, "windows": 0}

    def slasher_feed() -> None:
        epoch = 8
        perm = rng.permutation(n_validators)
        cursor = 0
        carry = 0.0
        while not stop_evt.is_set():
            t_w0 = time.time()
            want = arrival_idx_s * window_s + carry
            n_idx = int(want)
            carry = want - n_idx
            atts = []
            taken = 0
            while taken < n_idx:
                if cursor >= n_validators:
                    epoch += 1
                    perm = rng.permutation(n_validators)
                    cursor = 0
                k = min(committee, n_idx - taken, n_validators - cursor)
                ids = perm[cursor : cursor + k]
                cursor += k
                taken += k
                atts.append(
                    (ids, epoch - 1, epoch, rng.bytes(32))
                )
            if atts:
                fl = flight.begin_batch(
                    "slasher", "span_update_grid", taken
                )
                t0 = time.time()
                hits = slasher.on_attestations_bulk(atts)
                d = time.time() - t0
                fl.note_device(d)
                fl.finish(True)
                slasher_stats["indices"] += taken
                slasher_stats["busy_s"] += d
                slasher_stats["hits"] += sum(len(h) for h in hits)
                slasher_stats["windows"] += 1
            delay = window_s - (time.time() - t_w0)
            if delay > 0:
                stop_evt.wait(delay)

    # ---- bulk-replay lane: backfill windows riding the same flight
    # timeline, re-checking registry coverage each window (identity-hit
    # fast path — the 2^20 mirror is what makes this free)
    def replay_feed() -> None:
        while not stop_evt.is_set():
            t_w0 = time.time()
            registry.ensure(pk_tuple)
            fl = flight.begin_batch("replay", "multi_verify", 256)
            t0 = time.time()
            time.sleep(call_latency_s + per_sig_s * 256)
            fl.note_device(time.time() - t0)
            fl.finish(True)
            delay = 1.0 - (time.time() - t_w0)
            if delay > 0:
                stop_evt.wait(delay)

    threads = [
        threading.Thread(
            target=lane_producer,
            args=(ln, rates_mainnet[ln] * compress),
            name=f"lane-{ln}",
        )
        for ln in lane_names
    ] + [
        threading.Thread(target=slasher_feed, name="slasher-feed"),
        threading.Thread(target=replay_feed, name="replay-feed"),
    ]
    # mid-soak profiler capture toggle: flipped on halfway through and
    # off before shutdown, while the slasher lane issues REAL jax span
    # dispatches through the sealed shape ledger — so the
    # zero-recompiles gate below certifies the annotation scopes leave
    # the ledger untouched (and verdicts are asserted unchanged by the
    # lanes' own checks)
    from grandine_tpu.runtime.profiler import KernelProfiler, set_profiler

    soak_prof = set_profiler(KernelProfiler())
    flight.profiler = soak_prof

    t_soak0 = time.time()
    for t in threads:
        t.start()
    time.sleep(soak_s / 2.0)
    soak_prof.start(note="mainnet mid-soak capture toggle")
    time.sleep(soak_s / 2.0)
    stop_evt.set()
    for t in threads:
        t.join()
    sched.flush(60.0)
    soak_prof.stop()
    wall_s = time.time() - t_soak0
    sched.stop()

    # ---- per-lane latency vs SLO
    def q(xs, frac):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(frac * len(xs)))]

    lanes_report: "dict[str, dict]" = {}
    for ln in lane_names:
        lat = [
            t.settled_at - t.enqueued_at
            for t in tickets[ln]
            if t.settled_at is not None
        ]
        if not lat:
            continue
        budget_s = DEFAULT_SLO_BUDGETS[ln] * slo_scale
        p95 = q(lat, 0.95)
        lanes_report[ln] = {
            "jobs": len(lat),
            "p50_ms": round(q(lat, 0.50) * 1e3, 2),
            "p95_ms": round(p95 * 1e3, 2),
            "slo_ms": round(budget_s * 1e3, 1),
            "ok": bool(p95 <= budget_s),
        }
    for ln in ("slasher", "replay"):
        recs = flight.snapshot(lane=ln)
        lat = [r.total_s() for r in recs]
        if not lat:
            continue
        budget_s = DEFAULT_SLO_BUDGETS[ln] * slo_scale
        p95 = q(lat, 0.95)
        lanes_report[ln] = {
            "jobs": len(lat),
            "p50_ms": round(q(lat, 0.50) * 1e3, 2),
            "p95_ms": round(p95 * 1e3, 2),
            "slo_ms": round(budget_s * 1e3, 1),
            "ok": bool(p95 <= budget_s),
        }
    lanes_ok = bool(lanes_report) and all(
        r["ok"] for r in lanes_report.values()
    )

    # ---- slasher keep-up + the batched-vs-reference diagnostic
    busy = slasher_stats["busy_s"]
    span_rate = slasher_stats["indices"] / busy if busy > 0 else 0.0
    keep_up = span_rate >= arrival_idx_s
    backlog_ok = (
        slasher_stats["indices"] >= 0.9 * arrival_idx_s * soak_s
    )

    def _time_512(method_name: str) -> float:
        # dense committee (two full vchunks) attesting deep into a fresh
        # 4096-epoch history: the min-span walk visits every chunk below
        # the source, which is the steady-state cost the batched path
        # amortizes across rows
        ids = np.arange(512, dtype=np.uint64)
        best = float("inf")
        for _ in range(3):
            sl = Slasher()
            fn = getattr(sl, method_name)
            t0 = time.perf_counter()
            fn(ids, 4000, 4001, b"\xaa" * 32)
            best = min(best, time.perf_counter() - t0)
        return best

    ref_s = _time_512("on_attestation_reference")
    bat_s = _time_512("on_attestation")
    speedup = ref_s / bat_s if bat_s > 0 else 0.0
    speedup_ok = speedup >= 10.0

    recompiles = B.post_warmup_recompiles()
    gates = {
        "lanes_slo": lanes_ok,
        "zero_recompiles": recompiles == 0,
        "slasher_keep_up": bool(keep_up and backlog_ok),
        "batched_speedup_10x": bool(speedup_ok),
        "registry_churn_o_new": bool(churn_ok),
    }
    ok = all(gates.values())

    emit_bench_line({
        "metric": "mainnet_soak",
        "unit": "mixed",
        "value": round(span_rate, 1),
        "ok": ok,
        "gates": gates,
        "validators": n_validators,
        "scale": round(scale, 6),
        "time_compression": round(compress, 2),
        "soak_s": round(wall_s, 2),
        "lanes": lanes_report,
        "slasher": {
            "indices": slasher_stats["indices"],
            "windows": slasher_stats["windows"],
            "hits": slasher_stats["hits"],
            "span_update_per_s": round(span_rate, 1),
            "arrival_per_s_scaled": round(arrival_idx_s, 2),
            "arrival_per_s_mainnet": round(
                rates_mainnet["slasher_indices"], 1
            ),
            "batched_vs_reference_512": round(speedup, 2),
            "reference_512_ms": round(ref_s * 1e3, 1),
            "batched_512_ms": round(bat_s * 1e3, 1),
        },
        "registry": {
            "count": registry.count,
            "capacity": registry.capacity,
            "mainnet_capacity": MAINNET_CAPACITY,
            "host_mb": round(
                (registry._hx.nbytes + registry._hy.nbytes) / 1e6, 2
            ),
            "device_mb": round(
                registry.capacity * row_bytes / 1e6, 2
            ),
            "churn_rows": churn_rows,
            "churn_uploaded_bytes": churn_uploaded,
            "host_grows_during_churn": (
                registry.stats["host_grows"] - stats0["host_grows"]
            ),
        },
        "recompiles_post_warmup": recompiles,
        "profiler_capture_sessions": soak_prof.sessions_total,
        "warm_s": round(warm_s, 1),
        "prep_s": round(prep_s, 1),
    }, config={"validators": n_validators,
               "time_compression": round(compress, 2)})
    print(
        f"# mainnet soak: {n_validators} validators "
        f"(scale {scale:.4f} of 2^20), {compress:.0f}x time compression, "
        f"{wall_s:.1f}s wall; span updates {span_rate:.0f}/s vs scaled "
        f"arrival {arrival_idx_s:.1f}/s (mainnet "
        f"{rates_mainnet['slasher_indices']:.0f}/s); batched slasher "
        f"{speedup:.1f}x reference on 512 indices; "
        f"recompiles={recompiles}",
        file=sys.stderr,
    )
    emit_bench_line(
        {
            "metric": "verify_flight_summary",
            "value": flight.summary(),
        },
        stream=sys.stderr,
        ledger=False,
    )
    if strict and not ok:
        raise SystemExit(1)


def bench_overload() -> None:
    """`--overload` / BENCH_OVERLOAD=1: brownout-ladder overload soak.

    Drives the verify scheduler's HIGH lanes at mainnet-derived rates
    and a sheddable LOW lane at BENCH_OVERLOAD_ARRIVAL_X (default 4x)
    times its derived mainnet arrival — deliberately past the synthetic
    device's service rate — with a live BrownoutController, then gates
    on the overload-control contract:

      * the ladder walks NORMAL→…→CRITICAL under load and back to
        NORMAL after the burst stops, with ZERO flap (exactly one
        up-walk followed by exactly one down-walk),
      * HIGH-lane p95 enqueue→settle stays within its SLO budget
        (× BENCH_OVERLOAD_SLO_SCALE) THROUGH the overload — the point
        of shedding LOW traffic is that HIGH traffic never degrades,
      * every shed on the flight timeline is attributed: cause
        "expired" (deadline budget ran out before dispatch) or
        "brownout" (overload-control drop), and both kinds occur,
      * ZERO post-warmup recompiles — no overload actuator (queue
        shrink, host routing, door shedding) may touch the shape
        ledger.

    The device is the synthetic model from the mainnet soak (fixed call
    latency + per-signature cost) plus a synthetic HOST twin so the B3
    route-to-host leg costs host-shaped time instead of running real
    BLS on bench bytes. A side probe submits already-expired HIGH-lane
    tickets to pin the deadline-budget path: each must shed with
    cause="expired" before any dispatch. Emits ONE ledger-gated JSON
    line (metric `verify_overload_soak`: worst HIGH-lane p95 ms); gate
    failures exit 1 unless BENCH_OVERLOAD_STRICT=0."""
    _lint_preflight()
    import threading

    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime.brownout import LEVELS, BrownoutController
    from grandine_tpu.runtime.flight import (
        DEFAULT_SLO_BUDGETS,
        FlightRecorder,
    )
    from grandine_tpu.runtime.isolation import AdmissionController
    from grandine_tpu.runtime.verify_scheduler import (
        VerifyItem,
        VerifyScheduler,
    )
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu.registry import MAINNET_CAPACITY

    soak_s = float(os.environ.get("BENCH_OVERLOAD_SECONDS", "8"))
    arrival_x = float(os.environ.get("BENCH_OVERLOAD_ARRIVAL_X", "4"))
    slot_s = float(os.environ.get("BENCH_OVERLOAD_SLOT_S", "1.2"))
    slo_scale = float(os.environ.get("BENCH_OVERLOAD_SLO_SCALE", "1"))
    recovery_s = float(os.environ.get("BENCH_OVERLOAD_RECOVERY_S", "0.6"))
    strict = os.environ.get("BENCH_OVERLOAD_STRICT", "1") == "1"
    _enable_compilation_cache()

    compress = MAINNET_SECONDS_PER_SLOT / slot_s
    rates_mainnet = derive_mainnet_rates(MAINNET_CAPACITY)

    # no kernels are dispatched here (the device is the synthetic model
    # below) — sealing the EMPTY shape ledger turns the zero-recompile
    # gate into "the overload plane itself never triggers a compile"
    B.reset_shape_tracking()
    B.declare_warmup_complete()

    call_latency_s = float(
        os.environ.get("BENCH_OVERLOAD_CALL_MS", "20")) / 1e3
    per_sig_s = float(
        os.environ.get("BENCH_OVERLOAD_SIG_US", "1500")) / 1e6
    host_sig_s = float(
        os.environ.get("BENCH_OVERLOAD_HOST_SIG_US", "200")) / 1e6

    metrics = Metrics()
    flight = FlightRecorder(capacity=1 << 16, metrics=metrics)

    class _ModelOverloadScheduler(VerifyScheduler):
        """The mainnet soak's synthetic device model plus a synthetic
        host twin — B3 routing must cost host-shaped time, not run
        real BLS on bench bytes."""

        def _device_dispatch(self, lane, items):
            n = len(items)

            def settle() -> bool:
                time.sleep(call_latency_s + per_sig_s * n)
                return True

            return settle

        def _host_check_all(self, lane, items):
            time.sleep(host_sig_s * len(items))
            return [True] * len(items)

    sched = _ModelOverloadScheduler(
        use_device=True, flight=flight, metrics=metrics,
        merge_window_s=0.005,
    )
    admission = AdmissionController()
    ctrl = BrownoutController(
        sched,
        flight=flight,
        admission=admission,
        metrics=metrics,
        interval_s=0.1,
        recovery_window_s=recovery_s,
    )

    item = VerifyItem(b"\x11" * 32, b"\x22" * 96, public_keys=("bench",))
    high_lanes = ("block", "blob_header")
    burst_lane = "sync_message"
    tickets: "dict[str, list]" = {ln: [] for ln in high_lanes + (burst_lane,)}
    tickets_lock = threading.Lock()
    stop_evt = threading.Event()   # whole soak
    burst_evt = threading.Event()  # overload phase only
    expired_probes = [0]

    def lane_producer(lane: str, rate_per_s: float, until) -> None:
        interval = 1.0 / rate_per_s
        mine = []
        nxt = time.time()
        budget_s = DEFAULT_SLO_BUDGETS[lane] * slo_scale
        while not until.is_set():
            # every ticket carries its end-to-end deadline budget,
            # stamped at submit — expiry (not just queue overflow) is a
            # live shedding path during the burst
            mine.append(
                sched.submit(lane, [item], deadline_s=4.0 * budget_s)
            )
            nxt += interval
            delay = nxt - time.time()
            if delay > 0:
                until.wait(delay)
        with tickets_lock:
            tickets[lane].extend(mine)

    def expired_probe() -> None:
        # already-expired HIGH-lane tickets: each must shed with
        # cause="expired" BEFORE any dispatch — the deadline budget
        # applies even on lanes brownout shedding never touches
        while not burst_evt.is_set():
            sched.submit("blob_header", [item], deadline_s=0.0)
            expired_probes[0] += 1
            burst_evt.wait(0.25)

    threads = [
        threading.Thread(
            target=lane_producer,
            args=(ln, rates_mainnet[ln] * compress * arrival_x, stop_evt),
            name=f"lane-{ln}",
        )
        for ln in high_lanes
    ] + [
        threading.Thread(
            target=lane_producer,
            args=(
                burst_lane,
                rates_mainnet[burst_lane] * compress * arrival_x,
                burst_evt,
            ),
            name=f"lane-{burst_lane}",
        ),
        threading.Thread(target=expired_probe, name="expired-probe"),
    ]

    t0 = time.time()
    t0_mono = time.monotonic()  # transition stamps use the ctrl clock
    ctrl.start()
    for t in threads:
        t.start()
    # phase A: the burst runs for half the soak; phase B: drain + the
    # hysteretic walk back to NORMAL (bounded, not assumed — the gate
    # fails if recovery never lands)
    time.sleep(soak_s / 2.0)
    burst_evt.set()
    recovered_by = t0 + soak_s * 3.0
    while time.time() < recovered_by and ctrl.level != LEVELS[0]:
        time.sleep(0.05)
    time.sleep(2 * ctrl.interval_s)  # a couple of clean ticks at NORMAL
    stop_evt.set()
    for t in threads:
        t.join()
    sched.flush(60.0)
    wall_s = time.time() - t0

    end_level = ctrl.level
    transitions = ctrl.transitions()
    ctrl.stop()
    sched.stop()

    # ---- HIGH-lane latency vs SLO (LOW-lane latency rides along,
    # reported but ungated: shedding it is the design)
    def q(xs, frac):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(frac * len(xs)))]

    lanes_report: "dict[str, dict]" = {}
    for ln in high_lanes + (burst_lane,):
        lat = [
            t.settled_at - t.enqueued_at
            for t in tickets[ln]
            if t.settled_at is not None and not t.dropped
        ]
        if not lat:
            continue
        budget_s = DEFAULT_SLO_BUDGETS[ln] * slo_scale
        p95 = q(lat, 0.95)
        lanes_report[ln] = {
            "jobs": len(lat),
            "dropped": sum(1 for t in tickets[ln] if t.dropped),
            "p50_ms": round(q(lat, 0.50) * 1e3, 2),
            "p95_ms": round(p95 * 1e3, 2),
            "slo_ms": round(budget_s * 1e3, 1),
            "ok": bool(p95 <= budget_s),
        }
    high_ok = all(
        lanes_report[ln]["ok"] for ln in high_lanes if ln in lanes_report
    ) and all(ln in lanes_report for ln in high_lanes)
    worst_p95_ms = max(
        (lanes_report[ln]["p95_ms"] for ln in high_lanes
         if ln in lanes_report),
        default=float("inf"),
    )

    # ---- ladder shape: one clean up-walk, one clean down-walk
    idx = {lv: i for i, lv in enumerate(LEVELS)}
    steps = [idx[to] - idx[frm] for _, frm, to in transitions]
    n_up = len(LEVELS) - 1
    reached_critical = any(to == LEVELS[-1] for _, _, to in transitions)
    recovered = end_level == LEVELS[0]
    zero_flap = (
        len(steps) == 2 * n_up
        and all(s == 1 for s in steps[:n_up])
        and all(s == -1 for s in steps[n_up:])
    )

    # ---- shed attribution on the flight timeline
    shed_recs = [r for r in flight.snapshot() if r.note == "shed"]
    shed_causes = {r.slo_cause for r in shed_recs}
    shed_jobs = sum(
        st.get("shed", 0) for st in sched.stats.values()
    )
    misses = flight.slo_misses()
    expired_n = sum(c.get("expired", 0) for c in misses.values())
    brownout_n = sum(c.get("brownout", 0) for c in misses.values())
    sheds_attributed = (
        bool(shed_recs)
        and shed_causes <= {"expired", "brownout"}
        and "expired" in shed_causes
        and "brownout" in shed_causes
    )

    recompiles = B.post_warmup_recompiles()
    gates = {
        "reached_critical": bool(reached_critical),
        "recovered_normal": bool(recovered),
        "zero_flap": bool(zero_flap),
        "high_lanes_slo": bool(high_ok),
        "sheds_attributed": bool(sheds_attributed),
        "zero_recompiles": recompiles == 0,
    }
    ok = all(gates.values())

    emit_bench_line({
        "metric": "verify_overload_soak",
        "unit": "ms",
        "value": worst_p95_ms,
        "ok": ok,
        "gates": gates,
        "arrival_x": arrival_x,
        "time_compression": round(compress, 2),
        "soak_s": round(wall_s, 2),
        "lanes": lanes_report,
        "ladder": [
            [round(ts - t0_mono, 2), frm, to]
            for ts, frm, to in transitions
        ],
        "end_level": end_level,
        "sheds": {
            "jobs": shed_jobs,
            "records": len(shed_recs),
            "expired": expired_n,
            "brownout": brownout_n,
            "expired_probes": expired_probes[0],
        },
        "recompiles_post_warmup": recompiles,
    }, config={"arrival_x": arrival_x, "seconds": soak_s,
               "recovery_s": recovery_s})
    print(
        f"# overload soak: {arrival_x:.0f}x burst for {soak_s / 2:.1f}s, "
        f"{wall_s:.1f}s wall; ladder "
        + " ".join(f"{frm}->{to}" for _, frm, to in transitions)
        + f"; HIGH worst p95 {worst_p95_ms:.0f}ms; "
        f"sheds {shed_jobs} (expired {expired_n}, brownout {brownout_n}); "
        f"recompiles={recompiles}; " + ("OK" if ok else "FAILED"),
        file=sys.stderr,
    )
    emit_bench_line(
        {
            "metric": "verify_flight_summary",
            "value": flight.summary(),
        },
        stream=sys.stderr,
        ledger=False,
    )
    if strict and not ok:
        raise SystemExit(1)


def bench_multichip_child(n_devices: int) -> None:
    """One `--devices` sweep point, run by bench_multichip in a FRESH
    process: on the CPU platform the virtual device count comes from
    XLA_FLAGS=--xla_force_host_platform_device_count, which XLA parses
    once per process before the first backend call, so every count needs
    its own interpreter. Prints one JSON line with this count's raw
    multi_verify and firehose throughput (or a {"skipped": ...} line
    when the platform can't supply the devices)."""
    import re

    platform = os.environ.get("BENCH_MC_PLATFORM", "cpu")
    if platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        found = re.findall(
            r"xla_force_host_platform_device_count=(\d+)", flags
        )
        if not found or int(found[-1]) < n_devices:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n_devices}"
            ).strip()

    import jax

    if platform == "cpu":
        # the child is a fresh process and this precedes its first
        # backend call, so the switch needs nothing cleared
        jax.config.update("jax_platforms", "cpu")
    _enable_compilation_cache()

    from grandine_tpu.tpu.mesh import VerifyMesh

    try:
        vmesh = VerifyMesh.build(n_devices, platform=platform)
    except ValueError as exc:
        emit_bench_line({"devices": n_devices, "skipped": str(exc)},
                        ledger=False)
        return

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.tpu.bls import (
        TpuBlsBackend,
        multi_verify_kernel,
        rlc_bits_host,
        sharded_multi_verify,
    )
    from grandine_tpu.tpu.registry import DevicePubkeyRegistry

    n = int(os.environ.get("BENCH_MC_N", "256"))
    iters = int(os.environ.get("BENCH_MC_ITERS", "3"))
    report = {
        "devices": n_devices,
        "mesh": vmesh.describe(),
        "platform": platform,
        "n": n,
    }

    # ---- raw multi_verify: the flat RLC kernel, batch axis sharded.
    # Identical 9-array + r_bits signature at every count — N=1 runs the
    # plain jitted kernel, N>1 the registered shard_map factory; same
    # math, the sharding is the only delta (the apples-to-apples pair).
    args = build_batch(n, n_msgs=8)
    if vmesh.is_single:
        fn = jax.jit(multi_verify_kernel)
        dev_args = tuple(jax.device_put(a) for a in args)
        put = jax.device_put
    else:
        sharding = vmesh.batch_sharding()
        fn = sharded_multi_verify(vmesh.mesh)
        dev_args = tuple(jax.device_put(a, sharding) for a in args)
        put = lambda a: jax.device_put(a, sharding)  # noqa: E731

    def one_iter(seed: int) -> float:
        # fresh RLC bits per iteration (what a verifier draws per batch),
        # staged OFF the clock: the timed phase is
        # dispatch + verdict force — the device phase whose scaling the
        # sweep exists to measure (host plan cost is count-invariant)
        r_lo, r_hi = draw_rlc(n, seed)
        bits = put(rlc_bits_host(list(zip(r_lo.tolist(), r_hi.tolist())), n))
        bits.block_until_ready()
        t0 = time.time()
        ok = bool(fn(*dev_args, bits))
        dt = time.time() - t0
        if not ok:
            raise SystemExit("multichip flat kernel rejected a valid batch")
        return dt

    t0 = time.time()
    one_iter(0)  # compile + first run
    report["mv_compile_s"] = round(time.time() - t0, 1)
    lat = sorted(one_iter(i + 1) for i in range(iters))
    p50 = lat[len(lat) // 2]
    report["multi_verify_p50_s"] = round(p50, 4)
    report["multi_verify_sigs_per_s"] = round(n / p50, 1)

    # ---- firehose: indexed aggregate verify through the backend against
    # the row-sharded device registry (the gossip-lane production path:
    # host hashing + committee gather + sharded MSM verify, end to end)
    b = int(os.environ.get("BENCH_MC_FIREHOSE_B", "64"))
    sks = [
        A.SecretKey.keygen(bytes([9, i % 256, i >> 8]) + b"\x29" * 29)
        for i in range(b)
    ]
    registry = DevicePubkeyRegistry(mesh=vmesh)
    registry.ensure([sk.public_key().to_bytes() for sk in sks])
    backend = TpuBlsBackend(mesh=vmesh)
    msgs = [b"mc-firehose-%d" % i for i in range(b)]
    sigs = [sk.sign(m) for sk, m in zip(sks, msgs)]
    committees = [[i] for i in range(b)]

    def fire() -> float:
        # messages/signatures fixed across iterations; the RLC
        # randomizers are drawn fresh inside every call, so no two
        # executions are identical
        t0 = time.time()
        ok = backend.fast_aggregate_verify_batch_indexed(
            msgs, sigs, committees, registry
        )
        dt = time.time() - t0
        if not ok:
            raise SystemExit("multichip firehose rejected a valid batch")
        return dt

    t0 = time.time()
    fire()  # compile + first run
    report["fh_compile_s"] = round(time.time() - t0, 1)
    flat = sorted(fire() for _ in range(iters))
    p50 = flat[len(flat) // 2]
    report["firehose_b"] = b
    report["firehose_p50_s"] = round(p50, 4)
    report["firehose_sigs_per_s"] = round(b / p50, 1)
    emit_bench_line(report, ledger=False)  # parent aggregates the sweep


def bench_fused_kernels() -> None:
    """`--fused` / BENCH_FUSED=1: lever-by-lever fused-verify bench.

    Prints one parseable `verify_fused_kernels` JSON line per lever
    configuration plus a summary line. Backend levers (subgroup fusion,
    buffer donation) measure the multi_verify path end to end: an
    UNFUSED config pays the honest two-pass cost (RLC verify + the
    standalone ψ-ladder subgroup dispatch) while a fused config folds
    membership into the single pairing dispatch; per-batch device
    dispatch counts come from the backend's own kernel-call counters.
    The merge lever runs the real scheduler over two lanes with
    identical workloads and counts seam dispatches with the merge
    window closed vs open (job/batch shapes chosen so both land in the
    same compile bucket — the lever isolates DISPATCH count, not shape
    changes).

    Honesty notes: buffer donation is a no-op on the CPU backend (XLA
    declines it; `donation_effective` reports the truth), and the
    throughput target is a TPU figure — on CPU the summary reports
    `target_met` honestly alongside `dispatches_halved`, which is the
    CPU-checkable half of the claim. BENCH_FUSED_N sizes the backend
    lever batch (default 64; the driver runs 32768 on the chip)."""
    _lint_preflight()
    import warnings

    import jax

    _enable_compilation_cache()
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime import verify_scheduler as vs
    from grandine_tpu.runtime.thread_pool import Priority
    from grandine_tpu.tpu.bls import TpuBlsBackend

    n = int(os.environ.get("BENCH_FUSED_N", "64"))
    platform = jax.devices()[0].platform
    target_sigs_per_sec = 1.3 * 83_300.0  # 1.3x the BENCH_r05 headline

    # host prep (off the clock): n distinct keys/messages, valid sigs
    sks = [A.SecretKey(0x1357_0000_DEAD_BEEF + 0x2468_ACE1 * i)
           for i in range(n)]
    msgs = [b"fused-bench-%d" % i for i in range(n)]
    pks = [sk.public_key() for sk in sks]
    sigs = [sk.sign(m) for sk, m in zip(sks, msgs)]
    sig_pts = [s.point for s in sigs]

    def measure(fn, warm=1, budget_s=5.0, min_iters=3):
        for _ in range(warm):
            assert fn()
        lat = []
        t0 = time.time()
        while len(lat) < min_iters or (
            time.time() - t0 < budget_s and len(lat) < 30
        ):
            t1 = time.time()
            assert fn()
            lat.append(time.time() - t1)
        return sorted(lat)[len(lat) // 2]

    def total_kernel_calls(m):
        return sum(
            c.value for c in m.device_kernel_calls.children().values()
        )

    results = {}
    for fused, donate in ((False, False), (True, False), (True, True)):
        m = Metrics()
        batches = [0]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # donate-on-cpu warning
            backend = TpuBlsBackend(
                fuse_subgroup=fused, donate_buffers=donate, metrics=m
            )

            if fused:
                def one_batch(backend=backend, batches=batches):
                    batches[0] += 1
                    return bool(backend.multi_verify(msgs, sigs, pks))
            else:
                def one_batch(backend=backend, batches=batches):
                    batches[0] += 1
                    ok = bool(backend.multi_verify(msgs, sigs, pks))
                    return ok and bool(
                        backend.g2_subgroup_check_batch(sig_pts).all()
                    )

            p50 = measure(one_batch)
            calls = total_kernel_calls(m)
        dispatches_per_batch = calls / max(1, batches[0])
        lever = {
            "fused": fused, "donate": donate, "merge": False,
            "sigs_per_sec": round(n / p50, 1),
            "p50_batch_latency_ms": round(p50 * 1000, 2),
            "dispatches_per_batch": round(dispatches_per_batch, 2),
            "donation_effective": donate and platform != "cpu",
        }
        results[(fused, donate)] = lever
        # per-lever lines stay out of the ledger: one metric name, many
        # lever configs — the summary line below is the gated number
        emit_bench_line({
            "metric": "verify_fused_kernels", "unit": "sigs/s",
            "value": lever["sigs_per_sec"], "n": n,
            "platform": platform, **lever,
        }, ledger=False)

    # merge lever: real fused+donating backend behind the scheduler;
    # same workload with the merge window closed then open. Jobs are
    # 2 items with max_batch=2, so an unmerged batch (2 items) and a
    # merged pair (4 items) bucket identically to 4 — one compiled
    # shape, and the dispatch-count delta is purely the merge.
    class _CountingSeam:
        def __init__(self, inner):
            self._inner = inner
            self.dispatches = 0

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def fast_aggregate_verify_batch_async(self, *a, **kw):
            self.dispatches += 1
            return self._inner.fast_aggregate_verify_batch_async(*a, **kw)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        merge_backend = _CountingSeam(
            TpuBlsBackend(fuse_subgroup=True, donate_buffers=True)
        )
        n_jobs = int(os.environ.get("BENCH_FUSED_MERGE_JOBS", "8"))
        sched_items = [
            vs.VerifyItem(m, s.to_bytes(), public_keys=(pk,))
            for m, s, pk in zip(msgs, sigs, pks)
        ]

        for merge_on in (False, True):
            lanes = (
                vs.LaneConfig("attestation", Priority.LOW, 2, 0.05,
                              4096, False),
                vs.LaneConfig("sync_message", Priority.LOW, 2, 0.08,
                              4096, False),
            )
            sched = vs.VerifyScheduler(
                backend=merge_backend, lanes=lanes, use_device=True,
                merge_window_s=5.0 if merge_on else 0.0,
            )
            d0 = merge_backend.dispatches
            tickets = []
            t0 = time.time()
            try:
                for j in range(n_jobs):
                    pair = sched_items[(2 * j) % n:(2 * j) % n + 2]
                    tickets.append(sched.submit("attestation", pair))
                    tickets.append(sched.submit("sync_message", pair))
                sched.flush(600.0)
            finally:
                sched.stop()
            wall = time.time() - t0
            assert all(t.done() and t.ok for t in tickets), \
                "merge lever: a valid batch failed"
            merged = sum(
                st["merged"] for st in sched.stats.values()
            )
            lever = {
                "fused": True, "donate": True, "merge": merge_on,
                "sigs_per_sec": round(4 * n_jobs / wall, 1),
                "seam_dispatches": merge_backend.dispatches - d0,
                "merged_batches": merged,
                "jobs": 2 * n_jobs,
                "donation_effective": platform != "cpu",
            }
            results[("merge", merge_on)] = lever
            emit_bench_line({
                "metric": "verify_fused_kernels", "unit": "sigs/s",
                "value": lever["sigs_per_sec"], "n": 4 * n_jobs,
                "platform": platform, **lever,
            }, ledger=False)

    best = results[(True, True)]["sigs_per_sec"]
    halved = (
        results[(True, False)]["dispatches_per_batch"]
        <= results[(False, False)]["dispatches_per_batch"] / 2
    )
    merge_reduced = (
        results[("merge", True)]["seam_dispatches"]
        < results[("merge", False)]["seam_dispatches"]
    )
    emit_bench_line({
        "metric": "verify_fused_kernels_summary", "unit": "sigs/s",
        "value": best, "n": n, "platform": platform,
        "target_sigs_per_sec": round(target_sigs_per_sec, 1),
        "target_met": best >= target_sigs_per_sec,
        "dispatches_halved": halved,
        "merge_reduces_dispatches": merge_reduced,
    }, config={"n": n})
    print(
        f"# fused levers: unfused "
        f"{results[(False, False)]['sigs_per_sec']} -> fused "
        f"{results[(True, False)]['sigs_per_sec']} -> fused+donate "
        f"{best} sigs/s at n={n}; dispatches/batch "
        f"{results[(False, False)]['dispatches_per_batch']} -> "
        f"{results[(True, False)]['dispatches_per_batch']}; merge "
        f"{results[('merge', False)]['seam_dispatches']} -> "
        f"{results[('merge', True)]['seam_dispatches']} dispatches "
        f"for the same two-lane workload ({platform}; the throughput "
        f"target is a TPU figure)",
        file=sys.stderr,
    )
    if not (halved and merge_reduced):
        raise SystemExit(1)


def bench_multichip() -> None:
    """`--devices`: per-device-count scaling sweep over {1, 2, 4, 8}
    (BENCH_MC_DEVICES overrides), one fresh child process per count,
    covering the raw flat multi_verify kernel and the indexed firehose.
    Prints one parseable `multichip_scaling` JSON line with per-count
    sigs/s and parallel efficiency vs the single-device number.

    Honesty note: on the default CPU mesh the "devices" are XLA virtual
    host devices TIMESHARING the machine's physical cores — with fewer
    cores than mesh shards the sweep measures core contention plus
    sharded-dispatch overhead, not interconnect scaling, and efficiency
    lands well under 1/N. The >1.5x-at-4-devices figure is informational
    (reported as target_met) and expects >=4 physical cores or a real
    multi-chip platform.

    This parent must NOT import JAX: a chip belongs to one process at a
    time, and each child needs its devices. The children run one after
    the other."""
    import subprocess

    _lint_preflight()
    counts = [
        int(c)
        for c in os.environ.get("BENCH_MC_DEVICES", "1,2,4,8").split(",")
    ]
    env = {**os.environ, "BENCH_SKIP_LINT": "1",
           "BENCH_SKIP_RANGES": "1"}  # parent preflight already certified
    results: "dict[int, dict]" = {}
    for c in counts:
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--devices-child", str(c)],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        wall = time.time() - t0
        report = None
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                report = json.loads(line)
                break
            except (json.JSONDecodeError, ValueError):
                continue
        if proc.returncode != 0 or report is None:
            print(proc.stdout, file=sys.stderr)
            print(proc.stderr, file=sys.stderr)
            raise SystemExit(f"multichip child devices={c} failed")
        if "skipped" in report:
            print(
                f"# multichip: devices={c} skipped: {report['skipped']}",
                file=sys.stderr,
            )
            continue
        report["child_wall_s"] = round(wall, 1)
        results[c] = report
        print(
            f"# multichip: devices={c} multi_verify "
            f"{report['multi_verify_sigs_per_s']} sigs/s, firehose "
            f"{report['firehose_sigs_per_s']} sigs/s "
            f"(child {wall:.0f}s incl {report['mv_compile_s']}s + "
            f"{report['fh_compile_s']}s compile)",
            file=sys.stderr,
        )
    if 1 not in results:
        raise SystemExit("multichip sweep needs the single-device baseline")

    def table(key: str) -> dict:
        base = results[1][key]
        out = {}
        for c in sorted(results):
            v = results[c][key]
            out[str(c)] = {
                "sigs_per_s": v,
                "speedup": round(v / base, 3) if base else 0.0,
                "efficiency": round(v / (c * base), 3) if base else 0.0,
            }
        return out

    mv = table("multi_verify_sigs_per_s")
    fh = table("firehose_sigs_per_s")
    cores = os.cpu_count() or 1
    top = max(results)
    speedup4 = mv.get("4", {}).get("speedup", 0.0)
    emit_bench_line({
        "metric": "multichip_scaling",
        "unit": "sigs/s",
        "value": results[top]["multi_verify_sigs_per_s"],
        "devices": sorted(results),
        "n": results[top]["n"],
        "multi_verify": mv,
        "firehose": fh,
        "speedup_4dev_multi_verify": speedup4,
        "target_4dev_speedup": 1.5,
        "target_met": speedup4 > 1.5,
        "host_cores": cores,
        "platform": results[top].get("platform", "cpu"),
    }, config={"devices": sorted(results), "n": results[top]["n"]})
    print(
        f"# multichip: {cores} host core(s) behind the "
        f"{results[top].get('platform', 'cpu')} mesh — virtual device "
        f"shards timeshare those cores, so efficiency reflects core "
        f"contention + dispatch overhead, not interconnect scaling; "
        f"4-dev multi_verify speedup {speedup4}x (informational target "
        f">1.5x expects >=4 physical cores or a real multi-chip platform)",
        file=sys.stderr,
    )


def bench_schemes() -> None:
    """`--schemes` / BENCH_SCHEMES=1: the multi-scheme device plane —
    BLS, ed25519, and blob-KZG batches through their table-built
    backends on a sealed shape ledger, one `multi_scheme_plane` line.

    Knobs: BENCH_SCHEMES_N (ed25519 items/batch, default 15 — kernel
    point rows 1+2n land on the bucket-32 ladder), BENCH_SCHEMES_BLOBS
    (blobs/batch, default 4), BENCH_SCHEMES_WIDTH (field elements per
    blob, default 8), BENCH_SCHEMES_ITERS (timed rounds, default 3).

    All material prep happens BEFORE the ledger seals: computing a KZG
    commitment or proof dispatches the kzg_msm kernel, so blob
    generation is itself warmup. After the seal each lane runs a good
    and a forged batch per round — same shapes, opposite verdicts —
    and every device verdict must match the scheme's host twin. Zero
    post-warmup recompiles is the gate.
    """
    _lint_preflight()

    import statistics

    from grandine_tpu.crypto import ed25519 as HE
    from grandine_tpu.kzg import eip4844 as KZ
    from grandine_tpu.kzg.setup import dev_setup
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime.verify_scheduler import VerifyItem
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import schemes
    from grandine_tpu.transition.genesis import interop_secret_key

    n_ed = int(os.environ.get("BENCH_SCHEMES_N", "15"))
    n_blobs = int(os.environ.get("BENCH_SCHEMES_BLOBS", "4"))
    width = int(os.environ.get("BENCH_SCHEMES_WIDTH", "8"))
    iters = int(os.environ.get("BENCH_SCHEMES_ITERS", "3"))
    n_bls = 4  # smallest aggregate bucket: coexistence, not BLS perf

    metrics = Metrics()
    bls_be = schemes.get("bls").make_backend(metrics=metrics)
    ed_be = schemes.get("ed25519").make_backend(metrics=metrics)
    kzg_be = schemes.get("blob_kzg").make_backend(metrics=metrics)

    # the ledger resets BEFORE material prep — warm shapes must stay on
    # it, or their first live dispatch would count as a recompile
    B.reset_shape_tracking()

    sk = interop_secret_key(0)
    pk = sk.public_key()
    bls_msgs = [b"schemes-bls-%d" % i for i in range(n_bls)]
    bls_sigs = [sk.sign(m) for m in bls_msgs]
    bls_keys = [[pk]] * n_bls
    bls_items = [
        VerifyItem(m, s.to_bytes(), public_keys=(pk,))
        for m, s in zip(bls_msgs, bls_sigs)
    ]

    ed_good = []
    for i in range(n_ed):
        esk = bytes([i + 1]) * 32
        msg = b"schemes-ed-%04d" % i
        ed_good.append(VerifyItem(
            msg, HE.sign(esk, msg),
            public_keys=(HE.secret_to_public(esk),),
        ))
    mid = ed_good[n_ed // 2]
    ed_forged = list(ed_good)
    ed_forged[n_ed // 2] = VerifyItem(
        mid.message + b"!", mid.signature, public_keys=mid.public_keys
    )

    setup = dev_setup(width)
    rng = np.random.default_rng(14)
    kzg_good = []
    for _ in range(n_blobs):
        blob = b"".join(
            int(rng.integers(0, 2**61)).to_bytes(32, "big")
            for _ in range(width)
        )
        c = KZ.blob_to_kzg_commitment(blob, setup)  # kzg_msm dispatch
        p = KZ.compute_blob_kzg_proof(blob, c, setup)
        kzg_good.append(VerifyItem(blob, p, public_keys=(c,)))
    tampered = bytearray(kzg_good[-1].message)
    tampered[-1] ^= 1  # low byte of the last field element: stays canonical
    kzg_forged = list(kzg_good)
    kzg_forged[-1] = VerifyItem(
        bytes(tampered), kzg_good[-1].signature,
        public_keys=kzg_good[-1].public_keys,
    )

    def ed_run(items) -> bool:
        status, prep = ed_be.prepare(items)
        if status != "ok":
            raise SystemExit(f"ed25519 prepare: {status}")
        return ed_be.verify_batch_async(prep)()

    def kzg_run(items) -> bool:
        status, prep = kzg_be.prepare(items)
        if status != "ok":
            raise SystemExit(f"blob_kzg prepare: {status}")
        return kzg_be.verify_blobs_async(prep)()

    def bls_run(forged: bool) -> bool:
        msgs = ([b"forged-" + m for m in bls_msgs] if forged else bls_msgs)
        return bls_be.fast_aggregate_verify_batch(msgs, bls_sigs, bls_keys)

    # one good dispatch per lane compiles every timed shape, then seal
    if not (bls_run(False) and ed_run(ed_good) and kzg_run(kzg_good)):
        raise SystemExit("multi-scheme warmup batch rejected")
    B.declare_warmup_complete()

    lanes: "dict[str, dict]" = {}
    verdicts_ok = True
    for name, n_items, good, forged in (
        ("bls", n_bls, lambda: bls_run(False), lambda: bls_run(True)),
        ("ed25519", n_ed, lambda: ed_run(ed_good),
         lambda: ed_run(ed_forged)),
        ("blob_kzg", n_blobs, lambda: kzg_run(kzg_good),
         lambda: kzg_run(kzg_forged)),
    ):
        walls = []
        for _ in range(iters):
            t0 = time.time()
            ok = good()
            walls.append(time.time() - t0)
            verdicts_ok = verdicts_ok and ok is True
            verdicts_ok = verdicts_ok and forged() is False
        p50 = statistics.median(walls)
        lanes[name] = {
            "items": n_items,
            "p50_s": round(p50, 4),
            "items_per_s": round(n_items / p50, 2),
        }

    # the host twins must agree with every post-seal device verdict
    host = {
        "bls": schemes.get("bls").host_check,
        "ed25519": schemes.get("ed25519").host_check,
        "blob_kzg": schemes.get("blob_kzg").host_check,
    }
    host_agreement = (
        all(host["bls"](it) for it in bls_items)
        and all(host["ed25519"](it) for it in ed_good)
        and not all(host["ed25519"](it) for it in ed_forged)
        and all(host["blob_kzg"](it) for it in kzg_good)
        and not all(host["blob_kzg"](it) for it in kzg_forged)
    )

    recompiles = B.post_warmup_recompiles()
    plane_ok = verdicts_ok and host_agreement and recompiles == 0
    emit_bench_line({
        "metric": "multi_scheme_plane",
        "unit": "ed25519 verifications/s post-warmup",
        "value": lanes["ed25519"]["items_per_s"],
        "iters": iters,
        "lanes": lanes,
        "verdicts_ok": verdicts_ok,
        "host_agreement": host_agreement,
        "post_warmup_recompiles": recompiles,
        "plane_ok": plane_ok,
    }, config={"iters": iters})
    print(
        f"# multi-scheme plane: bls {lanes['bls']['items_per_s']}/s, "
        f"ed25519 {lanes['ed25519']['items_per_s']}/s, "
        f"blob_kzg {lanes['blob_kzg']['items_per_s']} blobs/s over "
        f"{iters} rounds; host agreement "
        f"{'yes' if host_agreement else 'NO'}, {recompiles} recompiles; "
        + ("OK" if plane_ok else "FAILED"),
        file=sys.stderr,
    )
    if not plane_ok:
        raise SystemExit(1)


def bench_compressed() -> None:
    """`--compressed` / BENCH_COMPRESSED=1: compressed-ingest e2e bench.

    Measures the PREP-INCLUSIVE wall rate from raw 96-byte wire
    signatures to a settled verdict, for both ingest paths:

      host leg:       per-item pure-Python G2 decompress (the
                      BENCH_r05 host-prep bottleneck: ~47.6s of Fq2
                      sqrt against 12.5s of device time) + the
                      uncompressed multi_verify kernel;
      compressed leg: raw bytes straight into multi_verify_compressed —
                      decompression happens inside the fused kernel,
                      host prep is a (b, 96) row stack.

    The ledger-gated metric is `bls_compressed_e2e_throughput` (the
    compressed leg, sigs/s); the host leg and the speedup ride along as
    fields. The host parse skips its redundant subgroup check (the
    fused kernel performs membership either way), so the reported
    speedup is a floor. Zero post-warmup recompiles is part of the
    verdict: both legs must run entirely on the warm manifest.

    Knobs: BENCH_COMPRESSED_N (batch, default 64),
    BENCH_COMPRESSED_ITERS (timed rounds, default 3)."""
    _lint_preflight()

    import statistics

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import schemes

    n = int(os.environ.get("BENCH_COMPRESSED_N", "64"))
    iters = int(os.environ.get("BENCH_COMPRESSED_ITERS", "3"))

    metrics = Metrics()
    backend = schemes.get("bls").make_backend(metrics=metrics)
    B.reset_shape_tracking()

    sks = [A.SecretKey(0x5EED_0001 + 0x1111 * i) for i in range(n)]
    pks = [sk.public_key() for sk in sks]
    msgs = [b"compressed-bench-%d" % i for i in range(n)]
    sig_bytes = [A.g2_to_bytes(sk.sign(m).point)
                 for sk, m in zip(sks, msgs)]
    forged = list(sig_bytes)
    forged[n // 2] = sig_bytes[(n // 2 + 1) % n]

    def host_leg() -> bool:
        sigs = [A.Signature(A.g2_from_bytes(sb, subgroup_check=False))
                for sb in sig_bytes]
        return bool(backend.multi_verify(msgs, sigs, pks))

    def compressed_leg() -> bool:
        return bool(backend.multi_verify_compressed(msgs, sig_bytes, pks))

    # one dispatch per leg compiles every timed shape, then seal
    if not (host_leg() and compressed_leg()):
        raise SystemExit("compressed-ingest warmup batch rejected")
    B.declare_warmup_complete()

    legs = {}
    verdicts_ok = True
    for name, fn in (("host", host_leg), ("compressed", compressed_leg)):
        walls = []
        for _ in range(iters):
            t0 = time.time()
            ok = fn()
            walls.append(time.time() - t0)
            verdicts_ok = verdicts_ok and ok is True
        p50 = statistics.median(walls)
        legs[name] = {
            "p50_s": round(p50, 4),
            "sigs_per_sec": round(n / p50, 1),
        }
    # forged batch must fail on the compressed path (same warm shape)
    verdicts_ok = verdicts_ok and (
        backend.multi_verify_compressed(msgs, forged, pks) is False
    )

    recompiles = B.post_warmup_recompiles()
    speedup = (
        legs["compressed"]["sigs_per_sec"] / legs["host"]["sigs_per_sec"]
    )
    plane_ok = verdicts_ok and recompiles == 0
    emit_bench_line({
        "metric": "bls_compressed_e2e_throughput",
        "unit": "sigs/s",
        "value": legs["compressed"]["sigs_per_sec"],
        "n": n,
        "iters": iters,
        "legs": legs,
        "speedup_vs_host_prep": round(speedup, 2),
        "verdicts_ok": verdicts_ok,
        "post_warmup_recompiles": recompiles,
        "plane_ok": plane_ok,
    }, config={"n": n, "iters": iters})
    print(
        f"# compressed ingest: {legs['compressed']['sigs_per_sec']} "
        f"sigs/s e2e vs host-prep {legs['host']['sigs_per_sec']} sigs/s "
        f"({speedup:.2f}x), {recompiles} post-warmup recompiles; "
        + ("OK" if plane_ok else "FAILED"),
        file=sys.stderr,
    )
    if not plane_ok:
        raise SystemExit(1)


def bench_signing() -> None:
    """`--signing` / BENCH_SIGNING=1: device signing plane duty bench.

    Per-slot duty load for an operator with BENCH_SIGNING_KEYS (default
    4096) keys: every key signs one attestation, a sync-committee
    subset signs the head root, and the slot's committee aggregates are
    constructed on device (`g2_aggregate_groups` + the G1 pubkey
    twin) — all through the SigningPlane with the release gate ON.

    The ledger-gated metric is `signing_plane` (released signatures/s
    through the gated plane). The gate asserts the subsystem's promise,
    not just its speed: every released signature byte-identical to the
    host `sk.sign` anchor, a scripted wrong-signature device fault
    (ChaosBackend) releasing ZERO bad signatures (the batch degrades to
    host re-sign and the breaker hears a verdict fault), zero missed
    deadlines (no dropped tickets), and zero post-warmup recompiles.

    Knobs: BENCH_SIGNING_KEYS (default 4096, rounded down to a full
    lane batch), BENCH_SIGNING_ITERS (timed rounds, default 3)."""
    _lint_preflight()

    import statistics

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime.sign_plane import (
        SignLaneConfig,
        SigningPlane,
    )
    from grandine_tpu.runtime.thread_pool import Priority
    from grandine_tpu.testing.chaos import ChaosBackend, FaultPlan
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import schemes

    n_keys = int(os.environ.get("BENCH_SIGNING_KEYS", "4096"))
    iters = int(os.environ.get("BENCH_SIGNING_ITERS", "3"))
    if n_keys >= 512:
        batch = 512
        n_keys = (n_keys // batch) * batch
    else:
        batch = max(4, 1 << (max(4, n_keys).bit_length() - 1))
        n_keys = (max(4, n_keys) // batch) * batch
    n_sync = min(batch, n_keys)
    span = min(64, n_keys)  # committee width for aggregate construction

    metrics = Metrics()
    backend = schemes.get("bls").make_backend(metrics=metrics)
    B.reset_shape_tracking()

    # full-batch lane policy: a long deadline makes every flush a FULL
    # bucket (n_keys is a batch multiple), so the timed rounds replay
    # exactly the warmed shapes
    lanes = (
        SignLaneConfig("attestation", Priority.HIGH, batch, 2.0,
                       2 * n_keys + 16, shed=False),
        SignLaneConfig("sync_message", Priority.HIGH, batch, 2.0,
                       2 * n_keys + 16, shed=False),
        SignLaneConfig("other", Priority.LOW, batch, 2.0,
                       2 * n_keys + 16, shed=True),
    )
    sks = [A.SecretKey(0x51c_0001 + 0x2222 * i) for i in range(n_keys)]
    pks = [sk.public_key() for sk in sks]
    att_roots = [
        hashlib.sha256(b"att-duty-%d" % i).digest() for i in range(n_keys)
    ]
    sync_root = hashlib.sha256(b"sync-duty-head-root").digest()

    # host anchors (the differential twin) — timed as the host leg
    t0 = time.time()
    anchors = [sk.sign(r).to_bytes() for sk, r in zip(sks, att_roots)]
    sync_anchors = [
        sks[i].sign(sync_root).to_bytes() for i in range(n_sync)
    ]
    host_wall = time.time() - t0
    host_rate = (n_keys + n_sync) / host_wall

    def duty_round(plane) -> "tuple[list, list, int]":
        tickets = [
            plane.submit(r, sk, duty_kind="attestation", public_key=pk)
            for r, sk, pk in zip(att_roots, sks, pks)
        ]
        sync_tickets = [
            plane.submit(sync_root, sks[i], duty_kind="sync_message",
                         public_key=pks[i])
            for i in range(n_sync)
        ]
        missed = 0
        out, sync_out = [], []
        for bucket, src in ((out, tickets), (sync_out, sync_tickets)):
            for t in src:
                try:
                    bucket.append(t.result(600.0))
                except (TimeoutError, RuntimeError):
                    missed += 1
                    bucket.append(None)
        return out, sync_out, missed

    plane = SigningPlane(
        backend=backend, lanes=lanes, metrics=metrics,
        settle_timeout_s=600.0,
    )
    # warm round compiles every timed shape (sign bucket + release-gate
    # multi_verify), then the aggregate-construction kernels, then seal
    warm_out, warm_sync, warm_missed = duty_round(plane)
    sig_groups = [
        [A.Signature(A.g2_from_bytes(sb, subgroup_check=False))
         for sb in anchors[i:i + span]]
        for i in range(0, n_keys, span)
    ]
    pk_groups = [pks[i:i + span] for i in range(0, n_keys, span)]
    B.g2_aggregate_groups(sig_groups, metrics)
    B.g1_aggregate_groups(pk_groups, metrics)
    B.declare_warmup_complete()

    identical = warm_out == anchors and warm_sync == sync_anchors
    missed_total = warm_missed

    walls = []
    for _ in range(iters):
        t0 = time.time()
        out, sync_out, missed = duty_round(plane)
        walls.append(time.time() - t0)
        identical = identical and out == anchors and (
            sync_out == sync_anchors
        )
        missed_total += missed
    p50 = statistics.median(walls)
    plane_rate = (n_keys + n_sync) / p50

    # aggregate-construction leg: device vs host twin, byte-identical
    t0 = time.time()
    dev_aggs = B.g2_aggregate_groups(sig_groups, metrics)
    dev_pk_aggs = B.g1_aggregate_groups(pk_groups, metrics)
    agg_wall = time.time() - t0
    agg_ok = (
        [a.to_bytes() for a in dev_aggs]
        == [A.Signature.aggregate(g).to_bytes() for g in sig_groups]
        and [a.to_bytes() for a in dev_pk_aggs]
        == [A.PublicKey.aggregate(g).to_bytes() for g in pk_groups]
    )

    # release-gate overhead: one ungated round against the same warm
    # shapes (the gate is the only difference)
    ungated = SigningPlane(
        backend=backend, lanes=lanes, metrics=metrics,
        settle_timeout_s=600.0, release_gate=False,
    )
    t0 = time.time()
    out, sync_out, missed = duty_round(ungated)
    ungated_wall = time.time() - t0
    identical = identical and out == anchors and sync_out == sync_anchors
    missed_total += missed
    gate_overhead = max(0.0, p50 / max(ungated_wall, 1e-9) - 1.0)

    # scripted wrong-signature device fault: the FIRST batch of this
    # plane's dispatches is corrupted; the release gate must degrade it
    # to host re-sign — zero bad signatures released
    chaos_plane = SigningPlane(
        backend=ChaosBackend(
            backend, FaultPlan(script=["wrong_signature"])
        ),
        lanes=lanes, metrics=metrics, settle_timeout_s=600.0,
    )
    out, sync_out, missed = duty_round(chaos_plane)
    chaos_ok = out == anchors and sync_out == sync_anchors
    missed_total += missed
    chaos_stats = chaos_plane.stats()
    gate_failures = sum(
        st["gate_failures"] for st in chaos_stats.values()
    )
    chaos_ok = chaos_ok and gate_failures >= 1

    for p in (plane, ungated, chaos_plane):
        p.stop()

    recompiles = B.post_warmup_recompiles()
    plane_ok = (
        identical and agg_ok and chaos_ok
        and missed_total == 0 and recompiles == 0
    )
    emit_bench_line({
        "metric": "signing_plane",
        "unit": "sigs/s",
        "value": round(plane_rate, 1),
        "keys": n_keys,
        "sync_members": n_sync,
        "iters": iters,
        "p50_s": round(p50, 4),
        "host_sigs_per_sec": round(host_rate, 1),
        "device_vs_host": round(plane_rate / host_rate, 2),
        "release_gate_overhead": round(gate_overhead, 3),
        "aggregate_groups": len(sig_groups),
        "aggregate_wall_s": round(agg_wall, 4),
        "aggregates_ok": agg_ok,
        "chaos_gate_failures": gate_failures,
        "chaos_ok": chaos_ok,
        "missed_deadlines": missed_total,
        "signatures_identical": identical,
        "post_warmup_recompiles": recompiles,
        "plane_ok": plane_ok,
    }, config={"keys": n_keys, "iters": iters})
    print(
        f"# signing plane: {plane_rate:.1f} sigs/s gated "
        f"(host {host_rate:.1f}, {plane_rate / host_rate:.2f}x), "
        f"gate overhead {gate_overhead * 100:.1f}%, "
        f"{gate_failures} chaos gate catch(es), "
        f"{missed_total} missed deadlines, "
        f"{recompiles} post-warmup recompiles; "
        + ("OK" if plane_ok else "FAILED"),
        file=sys.stderr,
    )
    if not plane_ok:
        raise SystemExit(1)


if __name__ == "__main__":
    if "--devices-child" in sys.argv:
        bench_multichip_child(
            int(sys.argv[sys.argv.index("--devices-child") + 1])
        )
    elif "--coldstart-child" in sys.argv:
        bench_coldstart_child(
            sys.argv[sys.argv.index("--coldstart-child") + 1]
        )
    elif "--devices" in sys.argv or os.environ.get("BENCH_MULTICHIP") == "1":
        bench_multichip()
    elif "--coldstart" in sys.argv or os.environ.get("BENCH_COLDSTART") == "1":
        bench_coldstart()
    elif "--fuzz-schedules" in sys.argv or os.environ.get("BENCH_FUZZ") == "1":
        bench_fuzz_schedules()
    elif "--fused" in sys.argv or os.environ.get("BENCH_FUSED") == "1":
        bench_fused_kernels()
    elif "--chaos" in sys.argv or os.environ.get("BENCH_CHAOS") == "1":
        bench_chaos()
        if os.environ.get("BENCH_SKIP_ADVERSARIAL") != "1":
            bench_adversarial()
    elif "--replay" in sys.argv or os.environ.get("BENCH_REPLAY") == "1":
        bench_replay()
    elif "--mainnet" in sys.argv or os.environ.get("BENCH_MAINNET") == "1":
        bench_mainnet()
    elif "--overload" in sys.argv or os.environ.get("BENCH_OVERLOAD") == "1":
        bench_overload()
    elif "--schemes" in sys.argv or os.environ.get("BENCH_SCHEMES") == "1":
        bench_schemes()
    elif (
        "--compressed" in sys.argv
        or os.environ.get("BENCH_COMPRESSED") == "1"
    ):
        bench_compressed()
    elif "--signing" in sys.argv or os.environ.get("BENCH_SIGNING") == "1":
        bench_signing()
    elif os.environ.get("BENCH_SCHED_ONLY") == "1":
        bench_verify_scheduler()
    else:
        main()
        if os.environ.get("BENCH_SCHED", "1") != "0":
            bench_verify_scheduler()
