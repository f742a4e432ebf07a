"""Device multi-scalar multiplication (Pippenger) for the RLC signature plane.

Replaces per-signature GLV double-and-add ladders wherever only SUMS of
rᵢ·Pᵢ are needed — the Σ rᵢ·sigᵢ side of every RLC batch verify, and the
per-message-group Σᵢ∈ⱼ rᵢ·pkᵢ side of the grouped kernel. A ladder computes
N scalar muls at ~96 point-ops each; Pippenger buckets the whole batch per
scalar window so the total is ~(windows · 2N) point additions — several
times less field work at headline batch sizes. Reference counterpart:
blst's Pippenger-backed `verify_multiple_aggregate_signatures`
(bls/src/signature.rs:96-129).

TPU-first formulation (no data-dependent control flow on device):
  - The HOST knows the RLC scalars (the verifier draws them), so all
    data-dependent structure — GLV digit extraction, bucket membership,
    sort order — is computed on host as static-shape int32 index arrays
    (`MsmPlan`). The device only gathers, scans, and reduces.
  - Scalars are split GLV-style: rᵢ = r0ᵢ + r1ᵢ·λ, so the expanded batch is
    2N points (Pᵢ and φPᵢ) with 32-bit scalars, cut into W windows of w
    bits. Zero digits are dropped at plan time (they contribute nothing).
  - Bucket accumulation is a SORTED-LANE SEGMENTED SCAN: expanded entries
    are sorted by (section, digit) key — section = group·W + window — and
    dealt contiguously into T lanes of exactly S slots (no alignment
    padding). One lax.scan of S steps runs a width-T complete addition per
    step, emitting its post-add accumulator every step and resetting at
    host-marked segment boundaries. Buckets that span lanes flush in ≤J
    pieces; a host-built gather reassembles (section, digit) bucket sums
    and a J-step scan folds the pieces.
  - Bucket weighting Σ d·S_d uses the suffix-sum identity (Σ_{d≥1} U_d with
    U_d = Σ_{e≥d} S_e), run as a Hillis-Steele suffix over the digit axis;
    window recombination is a Horner scan (w doubles + 1 complete add per
    window) batched over groups.

Complete additions are used throughout (points are adversary-supplied:
duplicates and ∞ must be handled), with Z=0 encoding ∞ so invalid/padding
slots are algebraically neutral — no masks in the hot loop.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from grandine_tpu.tpu import curve as C
from grandine_tpu.tpu import limbs as L

#: Scan lane count T (bucket-accumulation width). More lanes = fewer
#: sequential scan steps (S = ceil(2NW / T)), BUT the montmul inner scan
#: carries 27 column accumulators of width (products × T) that must live
#: in VMEM: at T=32768 with ~8 stacked products that carry is ~28 MB and
#: SPILLS (measured 5× slower end-to-end on v5e: 391 ms at 8192 vs
#: 2100 ms at 32768, by a probe script that predates PERF_LEDGER.jsonl).
#: 8192 keeps the carry ~5 MB — comfortably resident.
MSM_LANES = int(os.environ.get("GT_MSM_LANES", "8192"))


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b <<= 1
    return b


@dataclass(frozen=True)
class MsmPlan:
    """Static-shape device plan for one MSM batch (host-built, numpy).

    Shapes: point_idx/valid/flush (S, T); gather_idx/gather_valid
    (J, n_groups·W, B). point_idx indexes the EXPANDED point array
    (e < N → r0-slot of point e; e ≥ N → r1/φ-slot of point e−N).
    """

    point_idx: np.ndarray
    valid: np.ndarray
    flush: np.ndarray
    gather_idx: np.ndarray
    gather_valid: np.ndarray
    n_groups: int
    windows: int
    window_bits: int

    @property
    def arrays(self):
        return (
            self.point_idx, self.valid, self.flush,
            self.gather_idx, self.gather_valid,
        )


def plan_msm(
    r_lo,
    r_hi,
    inf_mask,
    group_of_point=None,
    n_groups: int = 1,
    window_bits: int = 8,
    lanes: "int | None" = None,
    j_min: int = 2,
) -> MsmPlan:
    """Build the device plan for Σᵢ (r0ᵢ + r1ᵢ·λ)·Pᵢ (per group).

    r_lo/r_hi: (N,) 32-bit GLV scalar halves. inf_mask: (N,) bool — points
    at infinity contribute nothing and are dropped here. group_of_point:
    (N,) ints (None → all group 0). All numpy-vectorized; the only
    per-batch host cost is one argsort of the expanded entries.
    """
    r_lo = np.asarray(r_lo, dtype=np.uint64)
    r_hi = np.asarray(r_hi, dtype=np.uint64)
    n = r_lo.shape[0]
    w = window_bits
    W = (32 + w - 1) // w
    B = 1 << w
    if group_of_point is None:
        group_of_point = np.zeros(n, dtype=np.int64)
    else:
        group_of_point = np.asarray(group_of_point, dtype=np.int64)
    inf_mask = np.asarray(inf_mask, dtype=bool)

    # expanded scalars (2N,) and their point groups
    scal = np.concatenate([r_lo, r_hi])
    grp = np.concatenate([group_of_point, group_of_point])
    live = ~np.concatenate([inf_mask, inf_mask])

    # digits (2N, W); drop zero digits and ∞ points
    shifts = (np.arange(W, dtype=np.uint64) * np.uint64(w))[None, :]
    digits = (scal[:, None] >> shifts) & np.uint64(B - 1)
    keep = (digits != 0) & live[:, None]
    e_idx, e_win = np.nonzero(keep)  # entry → (expanded point, window)
    e_dig = digits[e_idx, e_win].astype(np.int64)
    e_sec = grp[e_idx] * W + e_win  # section = group·W + window
    key = e_sec * B + e_dig

    order = np.argsort(key, kind="stable")
    k_sorted = key[order]
    E = order.shape[0]

    # T lanes × S slots; lane t owns sorted ranks [t·S, (t+1)·S). S is a
    # static function of the UNPRUNED total so jit shapes don't depend on
    # the random scalars.
    T = int(lanes if lanes is not None else MSM_LANES)
    total = 2 * n * W
    while T > 256 and total < 8 * T:
        T //= 2
    S = max(1, -(-total // T))

    point_idx = np.zeros((S, T), dtype=np.int32)
    valid = np.zeros((S, T), dtype=bool)
    flush = np.zeros((S, T), dtype=bool)
    rank = np.arange(E)
    rs, rt = rank % S, rank // S
    point_idx[rs, rt] = e_idx[order].astype(np.int32)
    valid[rs, rt] = True
    # a rank flushes when the next rank starts a new key or a new lane
    last = np.empty(E, dtype=bool)
    if E:
        last[:-1] = (k_sorted[1:] != k_sorted[:-1]) | (rt[1:] != rt[:-1])
        last[-1] = True
    flush[rs, rt] = last

    # pieces: flush ranks ascending are grouped by key; the j-th flush of a
    # key is that bucket's piece j
    fr = rank[last] if E else rank[:0]
    fkey = k_sorted[fr]
    m = fr.shape[0]
    pos = np.arange(m)
    first_of_key = np.empty(m, dtype=bool)
    if m:
        first_of_key[0] = True
        first_of_key[1:] = fkey[1:] != fkey[:-1]
    first_pos = np.maximum.accumulate(np.where(first_of_key, pos, 0)) if m else pos
    piece_j = pos - first_pos
    # J is a compile-time shape, so batch-to-batch variation would trigger
    # multi-minute recompiles mid-verify. Floor it with a DATA-INDEPENDENT
    # prediction (4× the mean bucket occupancy, in lanes-spanned units)
    # that dominates the realized max for all but astronomically unlikely
    # draws; j_min guards the smallest shapes.
    mean_bucket = total / max(1, n_groups * W * B)
    # a bucket of c entries spans ≤ ceil(c/S)+1 lanes; c concentrates at
    # mean + O(√mean) (binomial), so mean + 6√mean + 8 covers ~every draw
    tail_bucket = mean_bucket + 6.0 * mean_bucket ** 0.5 + 8.0
    predicted = int(-(-tail_bucket // S)) + 1
    actual = int(piece_j.max()) + 1 if m else 1
    J = _next_pow2(max(j_min, predicted, actual))

    n_sec = n_groups * W
    gather_idx = np.zeros((J, n_sec, B), dtype=np.int32)
    gather_valid = np.zeros((J, n_sec, B), dtype=bool)
    fsec, fdig = fkey // B, fkey % B
    # emit slot of rank r in the (S, T) scan output = (r % S)·T + (r // S)
    gather_idx[piece_j, fsec, fdig] = ((fr % S) * T + fr // S).astype(np.int32)
    gather_valid[piece_j, fsec, fdig] = True

    return MsmPlan(
        point_idx=point_idx,
        valid=valid,
        flush=flush,
        gather_idx=gather_idx,
        gather_valid=gather_valid,
        n_groups=n_groups,
        windows=W,
        window_bits=w,
    )


# --- device side ------------------------------------------------------------


def _sel3(ops, cond, a, b):
    return tuple(ops.select(cond, x, y) for x, y in zip(a, b))


def _point_inf(ops, shape):
    one = ops.make_one(shape)
    return (one, one, ops.make_zero(shape))


def _gather(e, idx):
    """Gather a field element's batch (device axis 1 of every limb array)
    by a flat int32 index array."""
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=1), e)


def _reduce_last_axis(p, size: int, ops):
    """Sum a point batch over its LAST batch axis (size must be a power of
    two) via the fixed-shape roll tree; returns points indexed at 0."""
    assert size & (size - 1) == 0

    def body(_, carry):
        y, s = carry
        rolled = tuple(
            jax.tree.map(lambda a: jnp.roll(a, -s, axis=-1), e) for e in y
        )
        y = C.point_add_complete(y, rolled, ops)
        return (y, s // 2)

    levels = size.bit_length() - 1
    y, _ = lax.fori_loop(0, levels, body, (p, jnp.int32(size // 2)))
    return tuple(jax.tree.map(lambda a: a[..., 0], e) for e in y)


def msm_bucket_scan(
    px, py, p_live,
    point_idx, valid, flush, gather_idx, gather_valid,
    windows: int, window_bits: int, n_groups: int, ops,
):
    """Σᵢ rᵢ·Pᵢ per group on device, driven by an MsmPlan's index arrays.

    px/py: affine coordinates of the EXPANDED point array (batch E, limb
    form); p_live (E,) bool marks real points. Returns (n_groups,) Jacobian
    points (groups in index order).
    """
    S, T = point_idx.shape
    J, n_sec, B = gather_idx.shape
    assert n_sec == n_groups * windows

    # 1. gather scan operands into sorted-lane order (S, T)
    flat = jnp.asarray(point_idx.reshape(-1))
    gx = _gather(px, flat)
    gy = _gather(py, flat)
    glive = jnp.take(jnp.asarray(p_live), flat) & jnp.asarray(
        valid.reshape(-1)
    )

    def to_scan_layout(e):
        # leaves (26, S·T) → (S, 26, T) so lax.scan slices rows
        return jax.tree.map(
            lambda a: jnp.moveaxis(a.reshape(a.shape[0], S, T), 1, 0), e
        )

    gx, gy = to_scan_layout(gx), to_scan_layout(gy)
    glive_st = glive.reshape(S, T)

    inf_T = _point_inf(ops, (T,))
    one_T, zero_T = inf_T[0], inf_T[2]

    def step(acc, xs):
        sx, sy, lv, fl = xs
        pt = (sx, sy, ops.select(lv, one_T, zero_T))  # Z=0 ⇒ ∞ (neutral)
        new = C.point_add_complete(acc, pt, ops)
        nxt = _sel3(ops, fl, inf_T, new)
        return nxt, new

    _, emits = lax.scan(
        step, inf_T, (gx, gy, glive_st, jnp.asarray(flush))
    )
    # emits leaves (S, 26, T) → flat emit axis (26, S·T), index = s·T + t
    emits = tuple(
        jax.tree.map(
            lambda a: jnp.moveaxis(a, 0, 1).reshape(a.shape[1], S * T), e
        )
        for e in emits
    )

    # 2. reassemble bucket sums: gather pieces, fold over J
    gidx = jnp.asarray(gather_idx.reshape(-1))
    pieces = tuple(
        jax.tree.map(
            lambda a: jnp.moveaxis(
                jnp.take(a, gidx, axis=1).reshape(a.shape[0], J, n_sec, B),
                1, 0,
            ),
            e,
        )
        for e in emits
    )
    gv = jnp.asarray(gather_valid)
    inf_secB = _point_inf(ops, (n_sec, B))

    def fold(acc, xs):
        pc, vmask = xs
        pc = _sel3(ops, vmask, pc, inf_secB)
        return C.point_add_complete(acc, pc, ops), None

    buckets, _ = lax.scan(fold, inf_secB, (pieces, gv))

    # 3. suffix-weight: T_sec = Σ_{d≥1} d·S_d = Σ_{d≥1} U_d, U_d = Σ_{e≥d} S_e
    # (Hillis-Steele as a fori_loop with a TRACED shift: one add graph. The
    # unrolled-python-loop form with constant shifts gave wrong sums on
    # a v5e at (4, 256)-batch in round 4, under a runtime that is gone;
    # not re-tested since — fori/scan forms are exact, and fori is also
    # the compile-friendly shape.)
    idx_b = jnp.arange(B)

    def suffix_body(_, carry):
        U, k = carry
        rolled = tuple(
            jax.tree.map(lambda a: jnp.roll(a, -k, axis=-1), e) for e in U
        )
        rolled = _sel3(ops, idx_b < (B - k), rolled, inf_secB)
        U = C.point_add_complete(U, rolled, ops)
        return (U, k * 2)

    levels = B.bit_length() - 1
    U, _ = lax.fori_loop(0, levels, suffix_body, (buckets, jnp.int32(1)))
    U = _sel3(ops, idx_b >= 1, U, inf_secB)  # digit 0 carries weight 0
    totals = _reduce_last_axis(U, B, ops)  # (n_sec,)

    # 4. Horner over windows (hi → lo): acc = 2^w·acc ⊞ T_win, per group;
    # fewer than L.LANE_FLOOR groups (the verify kernels' one Σ rᵢ·sigᵢ)
    # run padded with copies, the batch on the lanes (limbs.py)
    W, w = windows, window_bits
    xs_rev = tuple(
        jax.tree.map(
            lambda a: L.widen_lanes(jnp.moveaxis(
                a.reshape(a.shape[0], n_groups, W), 2, 0
            )[::-1]),
            e,
        )
        for e in totals
    )
    init = _point_inf(ops, (max(n_groups, L.LANE_FLOOR),))

    def horner(acc, win_pt):
        # w doubles as a fori_loop (same anti-unroll discipline as above)
        acc = lax.fori_loop(0, w, lambda _i, a: C.point_double(a, ops), acc)
        return C.point_add_complete(acc, tuple(win_pt), ops), None

    acc, _ = lax.scan(horner, init, xs_rev)
    return tuple(jax.tree.map(lambda a: a[:, :n_groups], e) for e in acc)


def expand_glv_points(x, y, inf, endo, ops):
    """Affine batch (N,) → expanded affine batch (2N,): [P…, φP…], with
    φ(x, y) = (cx·x, cy·y) = [λ]·(x, y) (crypto/curves.py endo_constants).
    Returns (px, py, p_live) for msm_bucket_scan."""
    ex, ey = endo
    x2, y2 = ops.mul_many([x, y], [ex, ey])
    px = ops.concat([x, x2], 1)  # device batch axis
    py = ops.concat([y, y2], 1)
    live = jnp.concatenate([~inf, ~inf])
    return px, py, live


__all__ = ["MsmPlan", "plan_msm", "msm_bucket_scan", "expand_glv_points"]
