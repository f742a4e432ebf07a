"""Diagnostic: how much of the bench's batch latency is ARGUMENT UPLOAD
(host→device transfer of the per-iteration MSM plan arrays) vs device
execution?

Runs the fused grouped kernel twice per distinct plan set:
  A. numpy args every call (the bench's shape: upload on the clock)
  B. jax.device_put'd args (pre-uploaded; only dispatch+execute on clock)

The A−B gap is the transfer cost a device-side plan builder (or packed
plan encoding) would recover. Each iteration draws a distinct plan, as
each real batch does.

Usage: [BENCH_N=32768] [BENCH_MSGS=256] python tools/device_residency_probe.py
"""

import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import bench


def main() -> None:
    n = int(os.environ.get("BENCH_N", "32768"))
    m = int(os.environ.get("BENCH_MSGS", "256"))
    iters = int(os.environ.get("PROBE_ITERS", "8"))
    import jax

    bench._enable_compilation_cache()
    from grandine_tpu.tpu import msm as M
    from grandine_tpu.tpu.bls import (
        grouped_multi_verify_msm_kernel,
        pick_msm_window,
    )

    flat = bench.build_batch(n, m)
    args = bench.regroup_batch(flat, m)
    groups = np.arange(n) % m
    inf = np.zeros(n, bool)
    g1_w = pick_msm_window(n, m)
    g2_w = pick_msm_window(n, 1)

    plans = []
    for i in range(iters):
        r_lo, r_hi = bench.draw_rlc(n, i)
        p1 = M.plan_msm(r_lo, r_hi, inf, groups, m, window_bits=g1_w)
        p2 = M.plan_msm(r_lo, r_hi, inf, None, 1, window_bits=g2_w)
        plans.append((p1, p2))

    fn = jax.jit(
        functools.partial(
            grouped_multi_verify_msm_kernel,
            g1_windows=plans[0][0].windows, g1_wbits=plans[0][0].window_bits,
            g2_windows=plans[0][1].windows, g2_wbits=plans[0][1].window_bits,
        )
    )

    def run(p1, p2):
        return bool(fn(*args, *p1, *p2))

    nbytes = sum(a.nbytes for p in plans[:1] for plan in p for a in plan.arrays)
    print(f"plan bytes/iter: {nbytes/1e6:.1f} MB "
          f"(+ points {sum(np.asarray(a).nbytes for a in args)/1e6:.1f} MB, "
          f"uploaded once)", file=sys.stderr)

    # compile + warm with plan 0
    t0 = time.time()
    assert run(plans[0][0].arrays, plans[0][1].arrays)
    print(f"compile+first {time.time()-t0:.1f}s", file=sys.stderr)

    # A: numpy args (upload on the clock)
    lat_a = []
    for p1, p2 in plans:
        t0 = time.time()
        assert run(p1.arrays, p2.arrays)
        lat_a.append(time.time() - t0)

    # B: device-resident args
    dev = [
        (tuple(jax.device_put(a) for a in p1.arrays),
         tuple(jax.device_put(a) for a in p2.arrays))
        for p1, p2 in plans
    ]
    for d1, d2 in dev[:1]:
        run(d1, d2)  # warm any relayout
    lat_b = []
    for d1, d2 in dev:
        t0 = time.time()
        assert run(d1, d2)
        lat_b.append(time.time() - t0)

    # C: points AND plans device-resident (pure device execution + dispatch)
    dev_args = tuple(jax.device_put(np.asarray(a)) for a in args)

    def run_c(d1, d2):
        return bool(fn(*dev_args, *d1, *d2))

    run_c(*dev[0])  # warm
    lat_c = []
    for d1, d2 in dev:
        t0 = time.time()
        assert run_c(d1, d2)
        lat_c.append(time.time() - t0)

    def stats(xs):
        xs = sorted(xs)
        return f"p50={xs[len(xs)//2]*1000:.0f}ms min={xs[0]*1000:.0f}ms"

    print(f"A numpy-args          {stats(lat_a)}", file=sys.stderr)
    print(f"B device-plans        {stats(lat_b)}", file=sys.stderr)
    print(f"C device-plans+points {stats(lat_c)}", file=sys.stderr)


if __name__ == "__main__":
    main()
