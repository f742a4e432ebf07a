"""MSM window/bucket calibration sweep (ROADMAP lever d).

The analytic op model in `bls.pick_msm_window` predicts the cheapest
Pippenger window width; this module MEASURES it. For each probed
(n_points, n_groups) shape it times the real MSM device graph —
`expand_glv_points` + `msm_bucket_scan` over the same plan arrays the
verify kernels use — once per candidate window, and records the fastest.

The winning table persists next to the shape manifest as
`tools/shapes/msm_tune.json` ({"windows": {"<n>:<g>": w}}), where
`bls.load_msm_tuning` picks it up ahead of the analytic model and
`runtime/warmup.py` loads it before warming, so the warmed kernel plans
and the steady-state plans agree (a tuned window only helps if the
warmup compiled THAT window's shapes).

Probe cost is dominated by XLA compiles (shapes × windows programs), so
the default sweep is deliberately small; `python -m tools.shapes
--autotune` runs it and reports per-cell timings.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from grandine_tpu.tpu import bls as B
from grandine_tpu.tpu import curve as C
from grandine_tpu.tpu import limbs as L
from grandine_tpu.tpu import msm as M

#: candidate Pippenger window widths (matches pick_msm_window's scan)
WINDOWS = (4, 5, 6, 7, 8)

#: default probed (n_points, n_groups) cells — pow-2 bucket shapes the
#: dispatch plane actually produces (flat multi_verify G2 MSM and the
#: grouped aggregate G1 MSM's widest tier-1 shapes)
DEFAULT_SHAPES = ((64, 1), (256, 1), (64, 16))


def _probe_field_rows(n: int, seed: int) -> "np.ndarray":
    """(n, NLIMBS) int32 host rows of deterministic pseudo-random Fp
    elements in Montgomery form. The MSM graph's op count and memory
    traffic do not depend on point VALIDITY, only on shapes — arbitrary
    field elements time identically to curve points."""
    rng = np.random.RandomState(seed)
    rows = np.zeros((n, L.NLIMBS), np.int32)
    for i in range(n):
        v = int.from_bytes(rng.bytes(48), "big") % L.P
        rows[i] = [int(d) for d in L.to_mont(v)]
    return rows


def _probe_fn(windows: int, wbits: int, n_groups: int):
    """The jitted MSM probe body: GLV expansion + bucket scan, identical
    structure to the verify kernels' G1 MSM stage."""

    def probe(px, py, inf, pidx, valid, flush, gidx, gvalid):
        x, y = B._g1_in(px, py)
        n = inf.shape[0]
        ex, ey, live = M.expand_glv_points(
            x, y, jnp.asarray(inf), B._g1_endo(n), C.FP_OPS
        )
        acc = M.msm_bucket_scan(
            ex, ey, live, pidx, valid, flush, gidx, gvalid,
            windows=windows, window_bits=wbits, n_groups=n_groups,
            ops=C.FP_OPS,
        )
        # one limb plane is enough to force the whole scan
        return acc[0][0]

    return probe


def time_window(n_points: int, n_groups: int, wbits: int,
                repeats: int = 3, seed: int = 7) -> float:
    """Best-of-`repeats` wall seconds for one (shape, window) cell,
    compile excluded (first call pays it, timing starts after)."""
    rng = np.random.RandomState(seed)
    r_lo = rng.randint(1, 1 << 31, size=n_points).astype(np.uint64)
    r_hi = rng.randint(1, 1 << 31, size=n_points).astype(np.uint64)
    inf = np.zeros(n_points, bool)
    groups = (
        None if n_groups == 1
        else np.arange(n_points, dtype=np.int64) % n_groups
    )
    plan = M.plan_msm(r_lo, r_hi, inf, groups, n_groups, window_bits=wbits)
    px = _probe_field_rows(n_points, seed)
    py = _probe_field_rows(n_points, seed + 1)
    fn = jax.jit(_probe_fn(plan.windows, plan.window_bits, plan.n_groups))
    args = [jax.device_put(a) for a in
            (px, py, inf) + tuple(plan.arrays)]
    fn(*args).block_until_ready()  # compile
    best = None
    for _ in range(max(1, int(repeats))):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def sweep(shapes=DEFAULT_SHAPES, windows=WINDOWS, repeats: int = 3,
          verbose=print) -> "dict[str, int]":
    """Measure every (shape, window) cell; return the winning window per
    shape keyed exactly as `pick_msm_window` looks them up."""
    table: "dict[str, int]" = {}
    for n_points, n_groups in shapes:
        n_b = B._bucket(n_points)
        g_b = B._bucket(max(1, n_groups), lo=1)
        key = "%d:%d" % (n_b, g_b)
        best_w, best_t = None, None
        for w in windows:
            dt = time_window(n_b, g_b, w, repeats=repeats)
            if verbose is not None:
                verbose("  msm %s w=%d: %.4fs" % (key, w, dt))
            if best_t is None or dt < best_t:
                best_w, best_t = w, dt
        table[key] = int(best_w)
        if verbose is not None:
            verbose("  msm %s -> w=%d" % (key, best_w))
    return table


def write_tuning(table: "dict[str, int]", path=None) -> str:
    """Persist the table where `bls.load_msm_tuning` reads it, and drop
    the in-process cache so this process sees it immediately."""
    path = path or B.msm_tune_path()
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(
            {
                "platform": jax.default_backend(),
                "windows": {k: int(v) for k, v in sorted(table.items())},
            },
            fh, indent=2, sort_keys=True,
        )
        fh.write("\n")
    os.replace(tmp, path)
    B.set_msm_tuning(None)
    return path


def autotune(shapes=DEFAULT_SHAPES, windows=WINDOWS, repeats: int = 3,
             path=None, verbose=print) -> "dict[str, int]":
    """Full lever-d cycle: sweep, persist, reload."""
    table = sweep(shapes=shapes, windows=windows, repeats=repeats,
                  verbose=verbose)
    out = write_tuning(table, path=path)
    if verbose is not None:
        verbose("wrote %d tuned windows -> %s" % (len(table), out))
    return table


__all__ = [
    "WINDOWS",
    "DEFAULT_SHAPES",
    "time_window",
    "sweep",
    "write_tuning",
    "autotune",
]
