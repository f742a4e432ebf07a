"""Batched BLS signature-plane kernels on device + the host-facing backend.

This is the TPU equivalent of the reference's `bls` crate hot surface
(bls/src/signature.rs:96-129 `multi_verify`, :78-93 `fast_aggregate_verify`,
bls/src/secret_key.rs:82-86 `sign`) re-designed for the accelerator:

  - `multi_verify_kernel` — random-linear-combination batch verification:
    N (message, signature, pubkey) triples are checked with batched Miller
    loops, a log-depth Fp12 product tree, and ONE shared final
    exponentiation:  e(g1, Σ rᵢ·sigᵢ) == ∏ e(rᵢ·pkᵢ, H(mᵢ)).
  - `grouped_multi_verify_msm_kernel` — triples grouped by message, so
    Miller loops collapse from N to the number of distinct messages.
  - `aggregate_fast_verify_msm_kernel` (and `_idx`, over the resident
    registry) — the gossip-attestation firehose shape: M attestations × K
    committee members; pubkey aggregation is a log-depth complete-addition
    tree over the k-major flat batch, then the RLC check.
  - `batch_sign_kernel` — G2 scalar multiplications for multi-validator
    signing (signer/src/signer.rs:173-229).

Kernel boundary: hosts speak the REST FORMAT — numpy arrays with a trailing
limb axis (pk (N, 26), G2 coords (N, 2, 26), bool masks (N,), scalar bit
arrays (N, nbits)) — which is layout-agnostic and cheap to assemble. The
first traced ops of every kernel split rest-format arrays into the limb-list
form the device plane computes in (see limbs.py for why), and outputs are
merged back; XLA fuses both boundaries into the adjacent compute.

All kernels are shape-static (host pads to power-of-two buckets), branchless,
and batched over the trailing axis of every limb array. Padding slots are
all-infinity triples, which are algebraically neutral in every reduction.
Host-side policy checks (identity pubkey rejection, empty batches, subgroup
checks on decompression) happen in `TpuBlsBackend` before data reaches the
device, mirroring where the reference enforces them.

Multi-chip: the batch axis shards over a `jax.sharding.Mesh`; each chip
reduces its local Fp12 product and the cross-chip product is a single
all-gather of one Fp12 element per chip (see __graft_entry__.py).
"""

from __future__ import annotations

import json
import os
import secrets
import sys
import threading
from collections import OrderedDict
from contextlib import nullcontext
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from grandine_tpu.crypto import constants
from grandine_tpu.crypto import bls as A
from grandine_tpu.crypto.curves import G1, LAMBDA, decompose_glv, endo_constants
from grandine_tpu.crypto.hash_to_curve import hash_to_g2
from grandine_tpu import tracing as _tracing
from grandine_tpu.tpu import compile_scope as _compile_scope
from grandine_tpu.tpu import curve as C
from grandine_tpu.tpu import field as F
from grandine_tpu.tpu import limbs as L
from grandine_tpu.tpu import msm as M
from grandine_tpu.tpu import pairing as TP
from grandine_tpu.tpu.compile_scope import compiling

# JAX's own account of each compile (trace / lower / backend, cache hit or
# miss) goes to the compile scope's phase counters from here on
_compile_scope.listen(jax.monitoring)

shard_map = jax.shard_map


# --- module constants (host, Montgomery limb form) -------------------------

_NEG_G1_DEV = C.g1_point_to_dev(-G1)  # (x, y, inf=False)

# verified ψ coordinate-scaling constants (crypto.curves derivation) for
# the device subgroup-check kernel
from grandine_tpu.crypto.curves import psi_constants_ints

_PSI_HOST = psi_constants_ints()
_ABS_X = -constants.X  # the (negative) BLS parameter, as |x|

# GLV/ψ² endomorphism constants (derived + asserted in crypto/curves.py):
# (cx·x, cy·y) = [LAMBDA]·(x, y) on the respective curve.
_ENDO_HOST = endo_constants()


def _g1_endo(n: int):
    bx, by = _ENDO_HOST["g1"]
    return (
        L.const_fp([int(d) for d in L.to_mont(bx)], (n,)),
        L.const_fp([int(d) for d in L.to_mont(by)], (n,)),
    )


def _g2_endo(n: int):
    wx, wy = _ENDO_HOST["g2"]
    z = L.zeros_fp((n,))
    return (
        (L.const_fp([int(d) for d in L.to_mont(wx)], (n,)), z),
        (L.const_fp([int(d) for d in L.to_mont(wy)], (n,)), z),
    )


def rlc_bits_host(pairs, pad_to: int) -> np.ndarray:
    """[(r0, r1), …] 32-bit RLC pairs → (pad_to, 64) rest-format bit array
    ([r0 MSB-first 32 | r1 MSB-first 32]); padding rows are (1, 0).

    The RLC scalar of a row is r0 + r1·LAMBDA (mod r) — a set of 2⁶⁴
    distinct values (r0 + r1·λ < 2¹⁶⁰ < r, so the map is injective), so the
    forgery bound of the random-linear-combination check is the same 2⁻⁶⁴
    as uniform 64-bit scalars, while both scalar ladders run at half
    length (curve.scalar_mul_glv)."""
    n = len(pairs)
    r0 = [p[0] for p in pairs] + [1] * (pad_to - n)
    r1 = [p[1] for p in pairs] + [0] * (pad_to - n)
    lo = C.scalars_to_bits_msb(r0, 32)
    hi = C.scalars_to_bits_msb(r1, 32)
    return np.concatenate([lo, hi], axis=1)


def sign_bits_host(scalars, pad_to: int):
    """Secret scalars → GLV-decomposed ((pad_to, 256) bits, (pad_to, 2) neg
    masks) for batch_sign_kernel."""
    decs = [decompose_glv(int(k)) for k in scalars]
    decs += [(1, 1, 0, 1)] * (pad_to - len(decs))
    lo = C.scalars_to_bits_msb([d[0] for d in decs], 128)
    hi = C.scalars_to_bits_msb([d[2] for d in decs], 128)
    neg = np.array([[d[1] < 0, d[3] < 0] for d in decs], dtype=bool)
    return np.concatenate([lo, hi], axis=1), neg


def _rlc_ladders(bits64):
    """(N, 64) packed RLC bit rows → ((32, N) lo, (32, N) hi) scan arrays."""
    b = jnp.asarray(bits64)
    return jnp.transpose(b[:, :32]), jnp.transpose(b[:, 32:])


# --- rest-format ↔ limb-list adapters (first/last traced ops of kernels) ---


def _g1_in(x, y):
    """(N, 26) coord arrays → affine G1 limb-list pair."""
    return L.split(jnp.asarray(x)), L.split(jnp.asarray(y))


def _g2_in(x, y):
    return F.fp2_split(jnp.asarray(x)), F.fp2_split(jnp.asarray(y))


def _flat_km(arr, m: int, k: int):
    """(M, K, …) rest array → k-major flat (K·M, …) — the order
    sum_points_grouped reduces over."""
    a = jnp.asarray(arr)
    return jnp.swapaxes(a, 0, 1).reshape((k * m,) + a.shape[2:])


def _neg_g1(n: int):
    """The constant −g1 as an (n,)-batched Jacobian G1 point (Z = 1)."""
    return (
        L.const_fp([int(d) for d in _NEG_G1_DEV[0]], (n,)),
        L.const_fp([int(d) for d in _NEG_G1_DEV[1]], (n,)),
        L.const_fp(L.ONE_MONT_DIGITS, (n,)),
    )


def _rlc_finish_grouped(f_groups, sig_acc_jac, g: int):
    """The RLC product equation once a GROUP: f_groups is a (g,)-batched
    Fp12 (per-group Miller products), sig_acc_jac a (g,)-batched Jacobian
    G2 (per-group Σ rᵢ·sigᵢ). Each group gets its own e(−g1, ·) factor and
    the shared final exponentiation runs ONCE at width g — the per-group
    verdicts cost one device pass, not g."""
    sig_inf = F.fp2_is_zero(sig_acc_jac[2])
    sig_h = TP.jacobian_to_homogeneous(sig_acc_jac)
    f_sig = TP.miller_loop(_neg_g1(g), sig_h, sig_inf)
    f_total = F.fp12_mul(f_groups, f_sig)
    return TP.final_exp_is_one(f_total)


def _rlc_miller_product(rpk_jac, pair_inf, msg_x, msg_y, sig_acc_jac,
                        sig_off=None):
    """∏ e(rᵢ·pkᵢ, H(mᵢ)) · e(−g1, Σ rᵢ·sigᵢ) ahead of the final
    exponentiation, from ONE batched Miller loop: the n message pairs and
    the signature pair (−g1, Σ rᵢ·sigᵢ) as lane n. A Miller loop of its own
    for that one pair ran with the limbs on the lanes and cost nearly three
    times the batched loop (limbs.py LANE_FLOOR); as a 65th lane it is
    free. The one place (single- and multi-chip) that evaluates the RLC
    product equation's left side. `sig_off` (a traced bool) masks the
    signature lane: the multi-chip callers keep the factor on one chip."""
    n = msg_x[0].shape[1]
    sig_inf = F.fp2_is_zero(sig_acc_jac[2])
    if sig_off is not None:
        sig_inf = jnp.logical_or(sig_inf, sig_off)
    sig_h = TP.jacobian_to_homogeneous(sig_acc_jac)
    # message points: affine → homogeneous projective on the twist
    msg_q = (msg_x, msg_y, F.fp2_one((n,)))
    P = tuple(L.concat_fp([a, b]) for a, b in zip(rpk_jac, _neg_g1(1)))
    Q = tuple(F.cat2([a, F.lead2(b)]) for a, b in zip(msg_q, sig_h))
    inf = jnp.concatenate([pair_inf, sig_inf[None]])
    f = TP.miller_loop(P, Q, inf)
    f_msgs = TP.fp12_product_tree(tuple(F.slice6(c, 0, n) for c in f))
    return F.fp12_mul(f_msgs, tuple(F.take6(c, n) for c in f))


def _rlc_pairing_check(rpk_jac, pair_inf, msg_x, msg_y, sig_acc_jac):
    """Shared tail of the verify kernels: given rᵢ·pkᵢ (Jacobian G1), the
    per-pair infinity mask, affine message points H(mᵢ) on the twist, and
    Σ rᵢ·sigᵢ (Jacobian G2), evaluate

        ∏ e(rᵢ·pkᵢ, H(mᵢ)) · e(−g1, Σ rᵢ·sigᵢ) == 1

    with one Miller loop and one shared final exponentiation."""
    return TP.final_exp_is_one(
        _rlc_miller_product(rpk_jac, pair_inf, msg_x, msg_y, sig_acc_jac)
    )


def _psi_ladder_check(P, inf, x_bits):
    """Traced core of the ψ-criterion subgroup check (Bowe, the check
    blst ships): P ∈ G2 ⇔ ψ(P) == [x]P ⇔ ψ(P) + [|x|]P == ∞ (the BLS
    parameter x is negative). `P` is an already-split affine G2 limb-list
    pair, `inf` the (N,) mask, `x_bits` the (64, N) MSB-first |x| ladder.
    Returns (N,) bool; infinity rows pass (padding slots are neutral —
    callers reject real infinity signatures by policy)."""
    xp = C.scalar_mul(P[0], P[1], inf, x_bits, C.FP2_OPS)
    n = inf.shape[0]
    (cx0, cx1), (cy0, cy1) = _PSI_HOST
    cx = (
        L.const_fp([int(d) for d in L.to_mont(cx0)], (n,)),
        L.const_fp([int(d) for d in L.to_mont(cx1)], (n,)),
    )
    cy = (
        L.const_fp([int(d) for d in L.to_mont(cy0)], (n,)),
        L.const_fp([int(d) for d in L.to_mont(cy1)], (n,)),
    )

    def conj(a):
        return (a[0], L.neg_mod(a[1]))

    psi_x = F.fp2_mul(cx, conj(P[0]))
    psi_y = F.fp2_mul(cy, conj(P[1]))
    one = C.FP2_OPS.one_like(psi_x)
    total = C.point_add_complete(xp, (psi_x, psi_y, one), C.FP2_OPS)
    return jnp.logical_or(inf, F.fp2_is_zero(total[2]))


def _fused_subgroup_mask(sig, sig_inf):
    """ψ-membership of the signature plane INSIDE a verify kernel body:
    the |x| bit ladder is a trace-time constant (the batch width is
    static under jit), so the fused check adds NO kernel operands — the
    64-step batched ladder simply joins the traced graph ahead of the
    pairing, eliminating the separate g2_subgroup_check dispatch (and
    its HBM round-trip) per batch."""
    n = sig_inf.shape[0]
    x_bits = jnp.asarray(np.ascontiguousarray(
        C.scalars_to_bits_msb([_ABS_X] * n, 64).T
    ))
    return _psi_ladder_check(sig, sig_inf, x_bits)


def multi_verify_kernel(
    pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf, r_bits
):
    """RLC batch verify of N (msg, sig, pk) triples. Rest-format shapes:
    pk_x/pk_y (N, L); sig/msg coords (N, 2, L); inf masks (N,) bool;
    r_bits (N, 64) packed RLC rows (rlc_bits_host — the scalar is
    r0 + r1·LAMBDA, run as a half-length dual ladder). N must be a power of
    two; padding slots are all-infinity (neutral). Returns a scalar bool.

    Algebraic twin of Signature::multi_verify (bls/src/signature.rs:96-129).
    """
    pk = _g1_in(pk_x, pk_y)
    sig = _g2_in(sig_x, sig_y)
    msg = _g2_in(msg_x, msg_y)
    pk_inf = jnp.asarray(pk_inf)
    sig_inf = jnp.asarray(sig_inf)
    msg_inf = jnp.asarray(msg_inf)
    n = pk_inf.shape[0]
    lo, hi = _rlc_ladders(r_bits)
    rpk = C.scalar_mul_glv(pk[0], pk[1], pk_inf, lo, hi, _g1_endo(n), C.FP_OPS)
    rsig = C.scalar_mul_glv(
        sig[0], sig[1], sig_inf, lo, hi, _g2_endo(n), C.FP2_OPS
    )
    sig_acc = C.sum_points(rsig, C.FP2_OPS)
    pair_inf = pk_inf | msg_inf
    return _rlc_pairing_check(rpk, pair_inf, msg[0], msg[1], sig_acc)


def rlc_partition_verify_kernel(
    pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf,
    r_bits, group_tag, check_subgroup: int = 0
):
    """Fault-localization variant of multi_verify_kernel: same RLC math,
    but instead of one whole-batch verdict it returns PER-SUB-BATCH
    verdicts — the batch's N slots split into G = group_tag.shape[0]
    contiguous groups of N/G, each group evaluating its own

        ∏ᵢ∈g e(rᵢ·pkᵢ, H(mᵢ)) · e(−g1, Σᵢ∈g rᵢ·sigᵢ) == 1

    in ONE device pass (the ladders and Miller loops run once at full
    width; only the product tree stops at group boundaries and the final
    exponentiation runs at width G). Returns a (G,) bool array. group_tag
    is a (G,)-shaped carrier whose only job is making G part of the jit
    shape signature (and the dispatch shape ledger). All-padding groups
    (all-infinity slots) report True — neutral, like padding in the
    whole-batch kernel. N and G must be powers of two with G | N."""
    pk = _g1_in(pk_x, pk_y)
    sig = _g2_in(sig_x, sig_y)
    msg = _g2_in(msg_x, msg_y)
    pk_inf = jnp.asarray(pk_inf)
    sig_inf = jnp.asarray(sig_inf)
    msg_inf = jnp.asarray(msg_inf)
    n = pk_inf.shape[0]
    g = group_tag.shape[0]
    lo, hi = _rlc_ladders(r_bits)
    rpk = C.scalar_mul_glv(pk[0], pk[1], pk_inf, lo, hi, _g1_endo(n), C.FP_OPS)
    rsig = C.scalar_mul_glv(
        sig[0], sig[1], sig_inf, lo, hi, _g2_endo(n), C.FP2_OPS
    )
    sig_acc = C.sum_points_contiguous(rsig, n // g, C.FP2_OPS)
    pair_inf = pk_inf | msg_inf
    msg_q = (msg[0], msg[1], F.fp2_one((n,)))
    f_items = TP.miller_loop(rpk, msg_q, pair_inf)
    f_groups = TP.fp12_product_tree_grouped(f_items, n // g)
    ok = _rlc_finish_grouped(f_groups, sig_acc, g)
    if check_subgroup:
        member = _fused_subgroup_mask(sig, sig_inf)
        ok = jnp.logical_and(ok, member.reshape(g, n // g).all(axis=1))
    return ok


# --- MSM window autotune table ----------------------------------------------
#
# A measured calibration sweep (tools.shapes --autotune → tpu/autotune.py)
# persists its winning window widths next to the shape manifest as
# tools/shapes/msm_tune.json: {"platform": "<jax backend>", "windows":
# {"<n_points>:<n_groups>": w}}. pick_msm_window consults the table first
# (keys quantized up to powers of two, as the dispatch plane buckets) and
# falls back to the analytic op model for unmeasured shapes. A table is
# only believed on the platform it was measured on: widths timed on the
# CPU say nothing about the chip. No table is checked in — none has been
# measured on a chip yet — so the op model stands alone until one is.

_MSM_TUNE: "Optional[dict]" = None
_MSM_TUNE_LOCK = threading.Lock()


def msm_tune_path() -> str:
    """Path of the persisted MSM autotune table (GRANDINE_TPU_MSM_TUNE
    overrides; default lives next to tools/shapes/manifest.txt)."""
    env = os.environ.get("GRANDINE_TPU_MSM_TUNE")
    if env:
        return env
    root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(root, "tools", "shapes", "msm_tune.json")


def load_msm_tuning(path: "Optional[str]" = None) -> "Optional[dict]":
    """Load (and cache) the measured window table. Returns the
    {"<n>:<g>": w} mapping, or None when the file is absent/unreadable —
    the analytic model then stands alone. Thread-safe; first caller pays
    the read."""
    global _MSM_TUNE
    with _MSM_TUNE_LOCK:
        if _MSM_TUNE is not None and path is None:
            return _MSM_TUNE or None
        try:
            with open(path or msm_tune_path(), encoding="utf-8") as fh:
                raw = json.load(fh)
            table = {}
            windows = dict(raw.get("windows", {}))
            if raw.get("platform") != jax.default_backend():
                windows = {}  # measured elsewhere: the op model stands
            # per-entry validation: one corrupt row must not discard the
            # rest of the measured table
            for k, v in windows.items():
                try:
                    w = int(v)
                except (ValueError, TypeError):
                    continue
                if 4 <= w <= 8:
                    table[str(k)] = w
        except (OSError, ValueError, TypeError, AttributeError):
            table = {}
        if path is None:
            _MSM_TUNE = table
        return table or None


def set_msm_tuning(table: "Optional[dict]") -> None:
    """Test/CLI seam: install a window table directly ({"<n>:<g>": w}),
    or None to drop the cache so the next lookup re-reads the file."""
    global _MSM_TUNE
    with _MSM_TUNE_LOCK:
        _MSM_TUNE = None if table is None else {
            str(k): int(v) for k, v in table.items()
        }


def pick_msm_window(n_points: int, n_groups: int = 1) -> int:
    """Window width minimizing the modeled MSM op count: scan work
    windows·2N plus suffix/reduce work 2w·(groups·windows·2^w).

    A sequential-call-count "latency" model was tried (round 5) and
    measured WORSE end-to-end: it pushes w up, and wide bucket planes
    (n_groups·W·2^w lanes) spill the montmul carry out of VMEM — the op
    count model's preference for narrow windows under many groups is
    also, in practice, the VMEM-resident choice.

    A measured entry in the autotune table (load_msm_tuning) wins over
    the model; lookup keys quantize up to powers of two, as the dispatch
    plane's buckets do, so a table built from the calibration sweep
    covers every shape the warmed kernels can see. The key has no upper
    bound: a kernel-level caller may plan shapes above MAX_BUCKET, which
    simply miss the table."""
    table = load_msm_tuning()
    if table:
        key = "%d:%d" % (
            _bucket(n_points, hi=n_points << 1),
            _bucket(max(1, n_groups), lo=1, hi=max(1, n_groups) << 1),
        )
        w = table.get(key)
        if w is not None:
            return w
    best, best_cost = 4, None
    for w in range(4, 9):
        W = (32 + w - 1) // w
        cost = W * 2 * n_points + 2 * w * n_groups * W * (1 << w)
        if best_cost is None or cost < best_cost:
            best, best_cost = w, cost
    return best


def _grouped_msm_verify_tail(
    pk, sig, msg, pk_inf_f, sig_inf_f, msg_inf, m, k,
    g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g1_windows: int, g1_wbits: int, g2_windows: int, g2_wbits: int,
    check_subgroup: int = 0,
):
    """Shared tail of the grouped MSM verify kernels: per-group pubkey MSM,
    global signature MSM, then the RLC pairing check over M messages."""
    epx, epy, eplive = M.expand_glv_points(
        pk[0], pk[1], pk_inf_f, _g1_endo(m * k), C.FP_OPS
    )
    gpk = M.msm_bucket_scan(
        epx, epy, eplive,
        g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
        windows=g1_windows, window_bits=g1_wbits, n_groups=m, ops=C.FP_OPS,
    )
    esx, esy, eslive = M.expand_glv_points(
        sig[0], sig[1], sig_inf_f, _g2_endo(m * k), C.FP2_OPS
    )
    sig_acc_g = M.msm_bucket_scan(
        esx, esy, eslive,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        windows=g2_windows, window_bits=g2_wbits, n_groups=1, ops=C.FP2_OPS,
    )
    sig_acc = tuple(C.FP2_OPS.index(e, 0) for e in sig_acc_g)
    pair_inf = L.is_zero_val(gpk[2]) | msg_inf
    ok = _rlc_pairing_check(gpk, pair_inf, msg[0], msg[1], sig_acc)
    if check_subgroup:
        ok = jnp.logical_and(ok, _fused_subgroup_mask(sig, sig_inf_f).all())
    return ok


def grouped_multi_verify_msm_kernel(
    pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf,
    g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g1_windows: int, g1_wbits: int, g2_windows: int, g2_wbits: int,
    check_subgroup: int = 0,
):
    """Message-grouped RLC batch verify with BOTH scalar planes as device
    Pippenger MSMs (msm.py) instead of per-signature ladders: per-group
    Σᵢ∈ⱼ rᵢ·pkᵢ (M-group MSM) and the global Σᵢ rᵢ·sigᵢ (1-group MSM).
    Triples arrive GROUPED BY MESSAGE: pk/sig have rest-format shape
    (M, K, …) — M distinct messages × up to K triples each (padding slots
    all-infinity) — msg has shape (M, …); the RLC scalars travel as
    MsmPlan index arrays (flat k-major point order, group of point f =
    f mod M) built by the host, which draws the randomizers.

    Algebraic identity:  ∏ᵢ e(rᵢ·pkᵢ, H(mᵢ)) = ∏ⱼ e(Σᵢ∈ⱼ rᵢ·pkᵢ, H(mⱼ)),
    so only M (+1) Miller loops run instead of N (+1) while every triple
    keeps its own 64-bit randomizer (soundness unchanged — cancellation
    inside a group needs a collision against rᵢ). This is the shape of the
    real workloads: gossip batches and block replays carry few distinct
    AttestationData values per many signatures (BASELINE configs 2–4).

    Replaces the ladder plane per VERDICT r3 #1; matches blst's
    Pippenger-backed multi_verify (bls/src/signature.rs:96-129)."""
    m, k = pk_inf.shape
    return _grouped_msm_verify_tail(
        _g1_in(_flat_km(pk_x, m, k), _flat_km(pk_y, m, k)),
        _g2_in(_flat_km(sig_x, m, k), _flat_km(sig_y, m, k)),
        _g2_in(msg_x, msg_y),
        jnp.asarray(_flat_km(pk_inf, m, k)),
        jnp.asarray(_flat_km(sig_inf, m, k)),
        jnp.asarray(msg_inf), m, k,
        g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g1_windows=g1_windows, g1_wbits=g1_wbits,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )


def _flat_msm_verify_tail(
    pk, pk_inf, sig, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """Shared tail of the flat MSM verify kernels: per-signature G1 GLV
    ladders (each rᵢ·pkᵢ feeds its own Miller loop), Σ rᵢ·sigᵢ as one
    Pippenger sum, then the RLC pairing check. `pk` arrives as a limb-list
    pair — built either from uploaded coords or a registry gather; `sig`
    arrives as a split Fp2 (x, y) pair — built from uploaded coords or the
    on-device decompressor. With `check_subgroup` the ψ-ladder membership
    of the signature plane runs fused in the same pass and ANDs into the
    verdict."""
    msg = _g2_in(msg_x, msg_y)
    pk_inf = jnp.asarray(pk_inf)
    sig_inf = jnp.asarray(sig_inf)
    msg_inf = jnp.asarray(msg_inf)
    n = pk_inf.shape[0]
    lo, hi = _rlc_ladders(r_bits)
    rpk = C.scalar_mul_glv(pk[0], pk[1], pk_inf, lo, hi, _g1_endo(n), C.FP_OPS)
    esx, esy, eslive = M.expand_glv_points(
        sig[0], sig[1], sig_inf, _g2_endo(n), C.FP2_OPS
    )
    sig_acc_g = M.msm_bucket_scan(
        esx, esy, eslive,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        windows=g2_windows, window_bits=g2_wbits, n_groups=1, ops=C.FP2_OPS,
    )
    sig_acc = tuple(C.FP2_OPS.index(e, 0) for e in sig_acc_g)
    pair_inf = pk_inf | msg_inf
    ok = _rlc_pairing_check(rpk, pair_inf, msg[0], msg[1], sig_acc)
    if check_subgroup:
        ok = jnp.logical_and(ok, _fused_subgroup_mask(sig, sig_inf).all())
    return ok


def multi_verify_msm_kernel(
    pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """Flat RLC batch verify (one Miller loop per signature) with the G2
    scalar plane as a device MSM. The G1 side keeps per-signature GLV
    ladders — each rᵢ·pkᵢ is needed individually for its Miller loop —
    while Σ rᵢ·sigᵢ is a single Pippenger sum."""
    return _flat_msm_verify_tail(
        _g1_in(pk_x, pk_y), pk_inf,
        _g2_in(sig_x, sig_y), sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )


def multi_verify_msm_idx_kernel(
    reg_x, reg_y, pk_idx, pk_inf,
    sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """multi_verify_msm_kernel with the PUBKEY plane gathered on-device
    from the resident registry (tpu/registry.py): reg_x/reg_y are the
    (capacity, L) registry arrays (already device-resident — NOT part of
    the per-batch upload), pk_idx (N,) int32 selects each signer's row.
    Padding slots carry pk_idx 0 under pk_inf True (registry rows are
    never the identity, so only the batch mask matters)."""
    idx = jnp.asarray(pk_idx)
    pk = _g1_in(
        jnp.take(jnp.asarray(reg_x), idx, axis=0),
        jnp.take(jnp.asarray(reg_y), idx, axis=0),
    )
    return _flat_msm_verify_tail(
        pk, pk_inf,
        _g2_in(sig_x, sig_y), sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )


def _aggregate_msm_verify_tail(
    mem, mem_inf_f, m, k, slot_pad,
    sig, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """Shared tail of the firehose MSM kernels: member aggregation tree,
    identity-forgery rejection, per-aggregate G1 ladder, Σ rᵢ·sigᵢ as one
    MSM, then the RLC pairing check. `mem` arrives as a k-major flat
    limb-list pair — built either from uploaded coords or a registry
    gather; `sig` as a split Fp2 (x, y) pair — from uploaded coords or
    the on-device decompressor.

    Computes pkᵢ = Σₖ memᵢₖ (complete-add tree over the k-major flat
    batch), then the RLC check. A REAL slot whose members sum to the
    identity is rejected (matching the anchor's fast_aggregate_verify: an
    adversary could pair a [P, −P] committee with an infinity signature to
    fake participation); padding slots stay algebraically neutral.
    Reference shape: attestation_batch_triples + MultiVerifier::finish
    (p2p/src/attestation_verifier.rs:431-457, helper_functions verifier.rs:302).
    """
    one = C.FP_OPS.one_like(mem[0])
    zero = C.FP_OPS.zeros_like(mem[0])
    mem_jac = (
        C.FP_OPS.select(mem_inf_f, one, mem[0]),
        C.FP_OPS.select(mem_inf_f, one, mem[1]),
        C.FP_OPS.select(mem_inf_f, zero, one),
    )
    agg_pk = C.sum_points_grouped(mem_jac, k, C.FP_OPS)  # (M,) Jacobian G1
    agg_inf = L.is_zero_val(agg_pk[2])
    slot_pad = jnp.asarray(slot_pad)
    forged = jnp.any(jnp.logical_and(jnp.logical_not(slot_pad), agg_inf))
    msg = _g2_in(msg_x, msg_y)
    sig_inf = jnp.asarray(sig_inf)
    msg_inf = jnp.asarray(msg_inf)
    lo, hi = _rlc_ladders(r_bits)
    rpk = C.scalar_mul_jac_glv(agg_pk, agg_inf, lo, hi, _g1_endo(m), C.FP_OPS)
    esx, esy, eslive = M.expand_glv_points(
        sig[0], sig[1], sig_inf, _g2_endo(m), C.FP2_OPS
    )
    sig_acc_g = M.msm_bucket_scan(
        esx, esy, eslive,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        windows=g2_windows, window_bits=g2_wbits, n_groups=1, ops=C.FP2_OPS,
    )
    sig_acc = tuple(C.FP2_OPS.index(e, 0) for e in sig_acc_g)
    pair_inf = agg_inf | msg_inf
    ok = _rlc_pairing_check(rpk, pair_inf, msg[0], msg[1], sig_acc)
    if check_subgroup:
        ok = jnp.logical_and(ok, _fused_subgroup_mask(sig, sig_inf).all())
    return jnp.logical_and(ok, jnp.logical_not(forged))


def aggregate_fast_verify_msm_kernel(
    mem_x, mem_y, mem_inf, slot_pad,
    sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """Firehose kernel: M aggregates (gossip attestations), each signed by
    up to K committee members over one message, the Σ rᵢ·sigᵢ side as a
    device MSM. The G1 side keeps the per-aggregate Jacobian GLV ladder —
    each rᵢ·(Σ memᵢₖ) is needed individually for its Miller loop.
    Rest-format shapes: mem_x/mem_y (M, K, L) affine member pubkeys with
    mem_inf (M, K) padding mask; slot_pad (M,) marks batch-padding slots;
    sig/msg per aggregate as in multi_verify_kernel; r_bits (M, 64)."""
    m, k = mem_inf.shape
    mem = _g1_in(_flat_km(mem_x, m, k), _flat_km(mem_y, m, k))
    return _aggregate_msm_verify_tail(
        mem, _flat_km(mem_inf, m, k), m, k, slot_pad,
        _g2_in(sig_x, sig_y), sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )


def aggregate_fast_verify_msm_idx_kernel(
    reg_x, reg_y, mem_idx, mem_inf, slot_pad,
    sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """Firehose kernel with MEMBER PUBKEYS gathered on-device from the
    resident registry: reg_x/reg_y are the (capacity, L) registry arrays
    (device-resident, not uploaded per batch); mem_idx (M, K) int32 selects
    each committee member's registry row, with mem_inf (M, K) masking the
    padding slots (which carry index 0 — registry rows are never the
    identity, so the mask alone is authoritative). The per-batch upload
    shrinks to signatures + messages + the index plane: 4 B/member instead
    of 208 B/member of affine G1 coordinates."""
    m, k = mem_inf.shape
    idx_f = _flat_km(mem_idx, m, k)  # k-major flat, like the coord layout
    mem = _g1_in(
        jnp.take(jnp.asarray(reg_x), idx_f, axis=0),
        jnp.take(jnp.asarray(reg_y), idx_f, axis=0),
    )
    return _aggregate_msm_verify_tail(
        mem, _flat_km(mem_inf, m, k), m, k, slot_pad,
        _g2_in(sig_x, sig_y), sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )


def _g2_compressed_in(sig_rows):
    """(B, 96) uint8 compressed signature rows → on-device decompression
    (tpu/curve.py): split Fp2 (x, y) in Montgomery form, the decoded
    infinity mask, and a per-row validity mask covering all three failure
    classes (non-canonical encoding, non-residue/off-curve x,
    infinity-with-payload). Invalid rows come back zeroed under ok=False —
    the caller masks them out of the group law and ANDs `ok.all()` into
    the verdict so a malformed item fails its batch without ever being
    batch-fatal on the host."""
    x, y, inf, ok, _be, _bc, _bi = C.g2_decompress_dev(sig_rows)
    return (x, y), inf, ok


def multi_verify_msm_comp_kernel(
    pk_x, pk_y, pk_inf, sig_rows, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """multi_verify_msm_kernel with the SIGNATURE plane arriving as raw
    compressed wire bytes ((B, 96) uint8 — the gossip format itself):
    decompression runs as part of the same device pass, replacing the
    per-item pure-Python Fq2.sqrt host stage that made BENCH_r05
    prep-bound (47.6s host vs 12.54s device). `sig_inf` is the host's
    padding ∪ infinity-flag mask (padding rows carry the canonical
    infinity encoding, so they decompress valid); a row the decompressor
    rejects is masked out of the MSM and fails the batch via ok.all()."""
    sig, dec_inf, dec_ok = _g2_compressed_in(sig_rows)
    sig_inf = jnp.asarray(sig_inf) | dec_inf | jnp.logical_not(dec_ok)
    ok = _flat_msm_verify_tail(
        _g1_in(pk_x, pk_y), pk_inf,
        sig, sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )
    return jnp.logical_and(ok, dec_ok.all())


def aggregate_fast_verify_msm_comp_kernel(
    mem_x, mem_y, mem_inf, slot_pad,
    sig_rows, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """aggregate_fast_verify_msm_kernel with compressed-bytes signature
    ingest ((M, 96) uint8). Same rejection semantics as the uncompressed
    twin plus the decompressor's per-row validity classes ANDed into the
    verdict."""
    m, k = mem_inf.shape
    mem = _g1_in(_flat_km(mem_x, m, k), _flat_km(mem_y, m, k))
    sig, dec_inf, dec_ok = _g2_compressed_in(sig_rows)
    sig_inf = jnp.asarray(sig_inf) | dec_inf | jnp.logical_not(dec_ok)
    ok = _aggregate_msm_verify_tail(
        mem, _flat_km(mem_inf, m, k), m, k, slot_pad,
        sig, sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )
    return jnp.logical_and(ok, dec_ok.all())


def aggregate_fast_verify_msm_idx_comp_kernel(
    reg_x, reg_y, mem_idx, mem_inf, slot_pad,
    sig_rows, sig_inf, msg_x, msg_y, msg_inf, r_bits,
    g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    g2_windows: int, g2_wbits: int, check_subgroup: int = 0,
):
    """aggregate_fast_verify_msm_idx_kernel with compressed-bytes
    signature ingest: member pubkeys gathered on-device from the resident
    registry AND signatures decompressed on-device. The per-batch upload
    collapses to 96 B/aggregate of wire bytes + 4 B/member of indices —
    nothing in the hot path is host-converted any more."""
    m, k = mem_inf.shape
    idx_f = _flat_km(mem_idx, m, k)
    mem = _g1_in(
        jnp.take(jnp.asarray(reg_x), idx_f, axis=0),
        jnp.take(jnp.asarray(reg_y), idx_f, axis=0),
    )
    sig, dec_inf, dec_ok = _g2_compressed_in(sig_rows)
    sig_inf = jnp.asarray(sig_inf) | dec_inf | jnp.logical_not(dec_ok)
    ok = _aggregate_msm_verify_tail(
        mem, _flat_km(mem_inf, m, k), m, k, slot_pad,
        sig, sig_inf, msg_x, msg_y, msg_inf, r_bits,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
        g2_windows=g2_windows, g2_wbits=g2_wbits,
        check_subgroup=check_subgroup,
    )
    return jnp.logical_and(ok, dec_ok.all())


def g1_decompress_kernel(rows):
    """Batched on-device G1 decompression for the pubkey registry's
    deposit-churn path: (B, 48) uint8 compressed rows → rest-format
    (B, 26) Montgomery affine coords plus infinity/validity masks and the
    three per-row failure classes. Invalid rows come back zeroed (NOT
    batch-fatal); the registry scatter keeps them as zero rows and the
    host mirror (which validated the same bytes) is authoritative for
    naming the bad deposit."""
    x, y, inf, ok, bad_enc, bad_curve, bad_inf = C.g1_decompress_dev(rows)
    return (
        L.merge(x), L.merge(y), inf, ok,
        bad_enc, bad_curve, bad_inf,
    )


def g1_decompress_rows(rows, metrics=None):
    """Dispatch g1_decompress_kernel on pre-padded (B, 48) uint8 rows.

    The one sanctioned dispatch seam for the kernel: the registry's
    churn path and warmup both come through here so the jit cache sees a
    single registration site (and scheme-owned code keeps the factory
    call out of runtime/)."""
    fn = _jitted_global("g1_decompress", g1_decompress_kernel)
    args = (jnp.asarray(rows),)
    with dispatch_scope("g1_decompress", args, metrics):
        return fn(*args)


def batch_sign_kernel(msg_x, msg_y, msg_inf, sk_bits, sk_neg):
    """N signatures: [skᵢ]·H(mᵢ) on the twist. sk_bits (N, 256) packed GLV
    halves with sk_neg (N, 2) sign masks (sign_bits_host): the 255-bit
    ladder becomes a 128-step dual ladder. Returns a Jacobian G2 batch in
    rest format (N, 2, 26) per coord.

    NOTE: secret scalars live on the accelerator; the kernel is branchless
    (fixed trip count, select-based) but NOT hardened against physical side
    channels — acceptable for benching, keep hot production signing host-side
    (SURVEY.md §7 risks)."""
    msg = _g2_in(msg_x, msg_y)
    n = jnp.asarray(msg_inf).shape[0]
    b = jnp.asarray(sk_bits)
    neg = jnp.asarray(sk_neg)
    X, Y, Z = C.scalar_mul_glv(
        msg[0], msg[1], jnp.asarray(msg_inf),
        jnp.transpose(b[:, :128]), jnp.transpose(b[:, 128:]),
        _g2_endo(n), C.FP2_OPS,
        neg_lo=neg[:, 0], neg_hi=neg[:, 1],
    )
    return F.fp2_merge(X), F.fp2_merge(Y), F.fp2_merge(Z)


def g2_subgroup_check_kernel(sx, sy, s_inf, x_bits):
    """Batched ψ-criterion subgroup check (Bowe, the check blst ships):
    P ∈ G2  ⇔  ψ(P) == [x]P  ⇔  ψ(P) + [|x|]P == ∞ (the BLS parameter x
    is negative). Inputs are AFFINE on-curve G2 points in rest format
    ((N, 2, 26) coords, (N,) inf mask); x_bits is the shared |x| ladder
    ((64, N) MSB-first). Returns (N,) bool; infinity rows pass (the
    caller rejects infinity signatures by policy, as the anchor does).

    This moves the per-signature host subgroup scalar-mul (~9 ms each,
    THE firehose batch bottleneck) onto the device as one 64-step
    batched ladder. The same traced math also runs fused INSIDE the
    verify kernels (`_fused_subgroup_mask`); this standalone entry stays
    for the fault localizer's per-item attribution pass and the health
    seam."""
    return _psi_ladder_check(
        _g2_in(sx, sy), jnp.asarray(s_inf), jnp.asarray(x_bits)
    )


def g2_aggregate_kernel(sig_x, sig_y, sig_inf, group_tag):
    """Contiguous-group G2 sums for aggregate CONSTRUCTION: the batch's
    N affine signature points split into G = group_tag.shape[0]
    contiguous groups of N/G, each reduced to one Jacobian aggregate in
    a single masked-roll tree pass (curve.sum_points_contiguous). This
    is the sign-side twin of the verify plane's partition reducer: one
    device dispatch builds every attestation / sync-contribution
    aggregate of a slot instead of a host G2 point loop per committee.

    Padding slots are infinity (the identity is neutral in complete
    addition); an all-padding group returns infinity, matching the host
    anchor `Signature.aggregate([])`. group_tag is a (G,)-shaped carrier
    whose only job is making G part of the jit shape signature (and the
    dispatch shape ledger). N and G must be powers of two with G | N.
    Returns Jacobian (G, 2, L) coords in rest format."""
    sig = _g2_in(sig_x, sig_y)
    inf = jnp.asarray(sig_inf)
    n = inf.shape[0]
    g = group_tag.shape[0]
    one = C.FP2_OPS.one_like(sig[0])
    zero = C.FP2_OPS.zeros_like(sig[0])
    p = (
        C.FP2_OPS.select(inf, one, sig[0]),
        C.FP2_OPS.select(inf, one, sig[1]),
        C.FP2_OPS.select(inf, zero, one),
    )
    X, Y, Z = C.sum_points_contiguous(p, n // g, C.FP2_OPS)
    return F.fp2_merge(X), F.fp2_merge(Y), F.fp2_merge(Z)


def g1_aggregate_kernel(pk_x, pk_y, pk_inf, group_tag):
    """G1 twin of g2_aggregate_kernel: contiguous-group sums of affine
    public-key points → per-group Jacobian aggregate keys (the
    fast-aggregate-verify prep and proposer-boost style key aggregation
    run as one pass next to the registry). Same padding and group_tag
    conventions; returns Jacobian (G, L) coords in rest format."""
    pk = _g1_in(pk_x, pk_y)
    inf = jnp.asarray(pk_inf)
    n = inf.shape[0]
    g = group_tag.shape[0]
    one = C.FP_OPS.one_like(pk[0])
    zero = C.FP_OPS.zeros_like(pk[0])
    p = (
        C.FP_OPS.select(inf, one, pk[0]),
        C.FP_OPS.select(inf, one, pk[1]),
        C.FP_OPS.select(inf, zero, one),
    )
    X, Y, Z = C.sum_points_contiguous(p, n // g, C.FP_OPS)
    return L.merge(X), L.merge(Y), L.merge(Z)


def g2_aggregate_groups(groups, metrics=None):
    """Batched aggregate construction: a list of signature groups → one
    aggregate `A.Signature` per group, reduced on device in ONE
    contiguous-group sum pass (g2_aggregate_kernel).

    The one sanctioned dispatch seam for the kernel: duty aggregation
    (validator/duties.py), the signing plane, and warmup all come
    through here so the jit cache sees a single registration site. The
    group width pads to its pow-2 bucket with infinity slots (neutral)
    and the group count pads to its own pow-2 bucket with all-padding
    groups, so the (batch, groups) jit universe stays enumerable. Host
    `Signature.aggregate` is the differential twin (byte-identical
    aggregates, asserted in tests/test_sign_plane.py)."""
    if not groups:
        return []
    m = len(groups)
    s = _bucket(max(max((len(grp) for grp in groups), default=1), 1))
    per_chunk = max(1, MAX_BUCKET // s)
    if m > per_chunk:
        out: list = []
        for i in range(0, m, per_chunk):
            out.extend(g2_aggregate_groups(groups[i : i + per_chunk],
                                           metrics))
        return out
    gb = _bucket(m)
    n = gb * s
    x, y, inf = C.g2_points_to_dev(
        [sig.point for grp in groups for sig in grp]
    )
    sx = np.zeros((n, 2, L.NLIMBS), np.int32)
    sy = np.zeros((n, 2, L.NLIMBS), np.int32)
    sinf = np.ones((n,), bool)
    pos = 0
    for gi, grp in enumerate(groups):
        k = len(grp)
        base = gi * s
        sx[base : base + k] = x[pos : pos + k]
        sy[base : base + k] = y[pos : pos + k]
        sinf[base : base + k] = inf[pos : pos + k]
        pos += k
    fn = _jitted_global("g2_aggregate", g2_aggregate_kernel)
    args = (
        jnp.asarray(sx), jnp.asarray(sy), jnp.asarray(sinf),
        jnp.zeros((gb,), jnp.int32),
    )
    with dispatch_scope("g2_aggregate", args, metrics):
        X, Y, Z = fn(*args)
    X, Y, Z = np.asarray(X), np.asarray(Y), np.asarray(Z)
    return [
        A.Signature(C.dev_to_g2_point(X[i], Y[i], Z[i])) for i in range(m)
    ]


def g1_aggregate_groups(groups, metrics=None):
    """G1 twin seam: a list of public-key groups → one aggregate
    `A.PublicKey` per group via g1_aggregate_kernel. Host
    `PublicKey.aggregate` is the differential twin. Same bucketing and
    chunking conventions as g2_aggregate_groups."""
    if not groups:
        return []
    m = len(groups)
    s = _bucket(max(max((len(grp) for grp in groups), default=1), 1))
    per_chunk = max(1, MAX_BUCKET // s)
    if m > per_chunk:
        out: list = []
        for i in range(0, m, per_chunk):
            out.extend(g1_aggregate_groups(groups[i : i + per_chunk],
                                           metrics))
        return out
    gb = _bucket(m)
    n = gb * s
    x, y, inf = C.g1_points_to_dev(
        [pk.point for grp in groups for pk in grp]
    )
    px = np.zeros((n, L.NLIMBS), np.int32)
    py = np.zeros((n, L.NLIMBS), np.int32)
    pinf = np.ones((n,), bool)
    pos = 0
    for gi, grp in enumerate(groups):
        k = len(grp)
        base = gi * s
        px[base : base + k] = x[pos : pos + k]
        py[base : base + k] = y[pos : pos + k]
        pinf[base : base + k] = inf[pos : pos + k]
        pos += k
    fn = _jitted_global("g1_aggregate", g1_aggregate_kernel)
    args = (
        jnp.asarray(px), jnp.asarray(py), jnp.asarray(pinf),
        jnp.zeros((gb,), jnp.int32),
    )
    with dispatch_scope("g1_aggregate", args, metrics):
        X, Y, Z = fn(*args)
    X, Y, Z = np.asarray(X), np.asarray(Y), np.asarray(Z)
    return [
        A.PublicKey(C.dev_to_g1_point(X[i], Y[i], Z[i])) for i in range(m)
    ]


# --- multi-chip (SPMD over a device mesh) -----------------------------------


def make_sharded_multi_verify(mesh, axis: str = "batch",
                              check_subgroup: int = 0):
    """Build the multi-chip RLC batch verify: the batch axis is sharded over
    `mesh`'s `axis`; each chip runs its local Miller loops, scalar muls, and
    local Fp12 product / G2 partial sum; the only collectives are two
    all-gathers of ONE Fp12 element and ONE Jacobian G2 point per chip (a few
    KB over ICI). The final exponentiation runs replicated (it is per-batch,
    not per-signature). Returns a jitted fn with the same signature as
    `multi_verify_kernel`; per-chip batch must be a power of two.

    This is the framework's scale-out plane (SURVEY.md §2.4): the pairing
    product is the one cross-chip reduction the workload needs.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]
    assert n_dev & (n_dev - 1) == 0, (
        "make_sharded_multi_verify requires a power-of-two device count"
    )

    def gather_tree(t):
        # gather batchless (26,) limb-major leaves into (26, n_dev): the
        # device axis becomes the batch axis (position 1)
        return jax.tree.map(lambda x: lax.all_gather(x, axis, axis=1), t)

    def local_step(
        pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf, r_bits
    ):
        pk = _g1_in(pk_x, pk_y)
        sig = _g2_in(sig_x, sig_y)
        msg = _g2_in(msg_x, msg_y)
        n_local = pk_inf.shape[0]
        lo, hi = _rlc_ladders(r_bits)
        rpk = C.scalar_mul_glv(
            pk[0], pk[1], pk_inf, lo, hi, _g1_endo(n_local), C.FP_OPS
        )
        rsig = C.scalar_mul_glv(
            sig[0], sig[1], sig_inf, lo, hi, _g2_endo(n_local), C.FP2_OPS
        )
        sX, sY, sZ = C.sum_points(rsig, C.FP2_OPS)  # local G2 partial sum
        # cross-chip: gather the per-chip partials (tiny), finish replicated.
        # Each limb array is a scalar per chip → all_gather yields (n_dev,).
        sig_all = gather_tree((sX, sY, sZ))
        sig_acc = C.sum_points(sig_all, C.FP2_OPS)
        # the signature pair rides in chip 0's Miller loop alone
        f_local = _rlc_miller_product(
            rpk, pk_inf | msg_inf, msg[0], msg[1], sig_acc,
            sig_off=lax.axis_index(axis) != 0,
        )
        f_all = gather_tree(f_local)
        ok = TP.final_exp_is_one(TP.fp12_product_tree(f_all))
        if check_subgroup:
            # fused ψ membership: each chip checks its local signature
            # rows, one bool crosses the mesh
            mem_local = _fused_subgroup_mask(sig, sig_inf).all()
            ok = jnp.logical_and(ok, lax.all_gather(mem_local, axis).all())
        return ok

    batch = P(axis)
    shardings = (
        batch, batch, batch,  # pk x/y/inf
        batch, batch, batch,  # sig
        batch, batch, batch,  # msg
        batch,                # r_bits
    )
    # check_vma=False: montmul's lax.scan carries start as replicated
    # constants and become device-varying, which the VMA checker rejects
    # (the computation is still correct SPMD — every collective is explicit).
    fn = shard_map(
        local_step, mesh=mesh, in_specs=shardings, out_specs=P(), check_vma=False
    )
    return jax.jit(fn)


def sharded_msm_plans(r_lo, r_hi, pk_inf, sig_inf, n_dev: int):
    """Per-chip MsmPlans for the sharded grouped verify: the (M, K) batch
    is sharded over K (each chip owns K/n_dev members of every group), so
    chip d's scalars are the k-major rows kk ∈ [d·K/D, (d+1)·K/D). All
    chips share one (windows, window_bits, S, T, J) shape — J is padded to
    the fleet max so the stacked plan arrays are rectangular.

    Returns (g1_arrays, g2_arrays, g1_plan0, g2_plan0) where *_arrays are
    the MsmPlan.arrays tuples stacked on a leading device axis."""
    m, k = pk_inf.shape
    assert k % n_dev == 0, "K must divide over the mesh"
    k_loc = k // n_dev
    r_lo = np.asarray(r_lo, np.uint64).reshape(k, m)
    r_hi = np.asarray(r_hi, np.uint64).reshape(k, m)
    pk_inf_km = np.asarray(pk_inf, bool).T  # (K, M)
    sig_inf_km = np.asarray(sig_inf, bool).T
    groups_loc = np.arange(k_loc * m) % m
    g1_w = pick_msm_window(k_loc * m, m)
    g2_w = pick_msm_window(k_loc * m, 1)
    g1_plans, g2_plans = [], []
    for d in range(n_dev):
        sl = slice(d * k_loc, (d + 1) * k_loc)
        lo = r_lo[sl].reshape(-1)
        hi = r_hi[sl].reshape(-1)
        g1_plans.append(M.plan_msm(
            lo, hi, pk_inf_km[sl].reshape(-1), groups_loc, m,
            window_bits=g1_w,
        ))
        g2_plans.append(M.plan_msm(
            lo, hi, sig_inf_km[sl].reshape(-1), None, 1, window_bits=g2_w,
        ))

    def stack(plans):
        j_max = max(p.gather_idx.shape[0] for p in plans)

        def pad_j(a):
            if a.shape[0] == j_max:
                return a
            pad = np.zeros((j_max - a.shape[0],) + a.shape[1:], a.dtype)
            return np.concatenate([a, pad], axis=0)

        cols = list(zip(*(p.arrays for p in plans)))
        out = []
        for i, col in enumerate(cols):
            col = [pad_j(a) if i >= 3 else a for a in col]  # gather_* pads
            out.append(np.stack(col, axis=0))
        return tuple(out)

    return stack(g1_plans), stack(g2_plans), g1_plans[0], g2_plans[0]


def make_sharded_multi_verify_msm(
    mesh, g1_windows: int, g1_wbits: int, g2_windows: int, g2_wbits: int,
    axis: str = "batch", check_subgroup: int = 0,
):
    """Multi-chip grouped RLC batch verify on the MSM plane (VERDICT r4
    weak #4): the (M, K) member axis is sharded over the mesh; each chip
    runs the Pippenger bucket scan on its K/D members of every group, the
    per-group partial sums cross chips in ONE all-gather of M (+1) points,
    and the Miller plane is sharded by MESSAGE (chip d pairs groups
    [d·M/D, (d+1)·M/D) with the reduced sums). A second all-gather moves
    one Fp12 partial per chip; the final exponentiation runs replicated.

    Collectives: two tiny all-gathers over ICI — the pairing-product
    reduction is the only cross-chip communication the workload needs
    (SURVEY §2.4)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.shape[axis]
    assert n_dev & (n_dev - 1) == 0, "power-of-two mesh required"

    def reduce_over_devices(pt, ops):
        """All-gather per-chip partial points and tree-add over the device
        axis (leaves gain the gathered axis at position 1)."""
        gathered = tuple(
            jax.tree.map(lambda x: lax.all_gather(x, axis, axis=1), e)
            for e in pt
        )

        def body(_, carry):
            y, s = carry
            rolled = tuple(
                jax.tree.map(lambda a: jnp.roll(a, -s, axis=1), e)
                for e in y
            )
            y = C.point_add_complete(y, rolled, ops)
            return (y, s // 2)

        levels = n_dev.bit_length() - 1
        if levels:
            gathered, _ = lax.fori_loop(
                0, levels, body, (gathered, jnp.int32(n_dev // 2))
            )
        return tuple(jax.tree.map(lambda a: a[:, 0], e) for e in gathered)

    # NOT named `local_step`: the plain RLC factory's inner fn already
    # compiles as XLA module `jit_local_step`, and sharing the name made
    # one MSM compile read as a double compile of the RLC kernel in the
    # MULTICHIP dryrun logs (two identically-named slow-compile alarms)
    def local_step_msm(
        pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf,
        g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
        g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
    ):
        # plan blocks arrive with a length-1 leading device axis
        (g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
         g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid) = (
            a[0] for a in (
                g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
                g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
            )
        )
        m, k_loc = pk_inf.shape
        pk = _g1_in(_flat_km(pk_x, m, k_loc), _flat_km(pk_y, m, k_loc))
        sig = _g2_in(_flat_km(sig_x, m, k_loc), _flat_km(sig_y, m, k_loc))
        msg = _g2_in(msg_x, msg_y)
        pk_inf_f = jnp.asarray(_flat_km(pk_inf, m, k_loc))
        sig_inf_f = jnp.asarray(_flat_km(sig_inf, m, k_loc))
        msg_inf_l = jnp.asarray(msg_inf)

        epx, epy, eplive = M.expand_glv_points(
            pk[0], pk[1], pk_inf_f, _g1_endo(m * k_loc), C.FP_OPS
        )
        gpk_local = M.msm_bucket_scan(
            epx, epy, eplive,
            g1_pidx, g1_valid, g1_flush, g1_gidx, g1_gvalid,
            windows=g1_windows, window_bits=g1_wbits, n_groups=m,
            ops=C.FP_OPS,
        )
        esx, esy, eslive = M.expand_glv_points(
            sig[0], sig[1], sig_inf_f, _g2_endo(m * k_loc), C.FP2_OPS
        )
        sig_local = M.msm_bucket_scan(
            esx, esy, eslive,
            g2_pidx, g2_valid, g2_flush, g2_gidx, g2_gvalid,
            windows=g2_windows, window_bits=g2_wbits, n_groups=1,
            ops=C.FP2_OPS,
        )
        # cross-chip: group sums and the G2 partial (one all-gather each)
        gpk = reduce_over_devices(gpk_local, C.FP_OPS)  # (M,)
        sig_acc_g = reduce_over_devices(sig_local, C.FP2_OPS)  # (1,)
        sig_acc = tuple(C.FP2_OPS.index(e, 0) for e in sig_acc_g)

        # Miller plane sharded by MESSAGE: chip d takes its M/D slice
        assert m % n_dev == 0, "group count must divide over the mesh"
        m_loc = m // n_dev
        start = lax.axis_index(axis) * m_loc

        def slice_m(e):
            return jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, start, m_loc, axis=1),
                e,
            )

        gpk_s = tuple(slice_m(e) for e in gpk)
        msg_s = tuple(slice_m(e) for e in (msg[0], msg[1]))
        pair_inf = lax.dynamic_slice_in_dim(
            L.is_zero_val(gpk[2]) | msg_inf_l, start, m_loc, axis=0
        )
        # the signature pair rides in chip 0's Miller loop alone
        f_local = _rlc_miller_product(
            gpk_s, pair_inf, msg_s[0], msg_s[1], sig_acc,
            sig_off=lax.axis_index(axis) != 0,
        )
        f_all = jax.tree.map(
            lambda x: lax.all_gather(x, axis, axis=1), f_local
        )
        ok = TP.final_exp_is_one(TP.fp12_product_tree(f_all))
        if check_subgroup:
            mem_local = _fused_subgroup_mask(sig, sig_inf_f).all()
            ok = jnp.logical_and(ok, lax.all_gather(mem_local, axis).all())
        return ok

    member = P(None, axis)  # shard the K axis of (M, K, …) point arrays
    plan = P(axis)          # per-chip plan stacks (D, S, T)
    in_specs = (
        member, member, member,  # pk
        member, member, member,  # sig
        P(), P(), P(),           # msg replicated
        plan, plan, plan, plan, plan,   # g1 plan
        plan, plan, plan, plan, plan,   # g2 plan
    )
    fn = shard_map(
        local_step_msm, mesh=mesh, in_specs=in_specs, out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


# --- promoted sharded dispatch targets --------------------------------------
#
# The make_* factories above build a FRESH jax.jit wrapper per call — fine
# for one-shot dryruns, but the production verify plane dispatches per
# batch, and a fresh wrapper per batch would re-trace and re-compile every
# time. Promotion to registered dispatch targets means ONE process-wide
# executable per (kernel, mesh, statics), cached here — the mesh twin of
# `_JITTED` (kept separate because the key carries device identity and
# every entry is already wrapped in the persistent-cache bypass).

_SHARDED_FACTORIES: dict = {}
_SHARDED_FACTORY_LOCK = threading.Lock()


def _mesh_factory_key(mesh, axis: str) -> tuple:
    return (axis,) + tuple(
        int(d.id) for d in np.asarray(mesh.devices).flat
    )


def sharded_multi_verify(mesh, axis: str = "batch", check_subgroup: int = 0):
    """The registered multi-chip RLC batch-verify dispatch target: one
    cached `make_sharded_multi_verify` wrapper per (mesh, axis, statics),
    so every backend and every batch shares one compiled executable per
    shape."""
    key = (
        "sharded_multi_verify", _mesh_factory_key(mesh, axis),
        int(check_subgroup),
    )
    with _SHARDED_FACTORY_LOCK:
        fn = _SHARDED_FACTORIES.get(key)
        if fn is None:
            fn = make_sharded_multi_verify(
                mesh, axis=axis, check_subgroup=check_subgroup
            )
            _SHARDED_FACTORIES[key] = fn
    return fn


def sharded_multi_verify_msm(
    mesh, g1_windows: int, g1_wbits: int, g2_windows: int, g2_wbits: int,
    axis: str = "batch", check_subgroup: int = 0,
):
    """The registered multi-chip grouped-MSM dispatch target, cached per
    (mesh, axis, MSM window statics) like `sharded_multi_verify`."""
    key = (
        "sharded_multi_verify_msm", _mesh_factory_key(mesh, axis),
        int(g1_windows), int(g1_wbits), int(g2_windows), int(g2_wbits),
        int(check_subgroup),
    )
    with _SHARDED_FACTORY_LOCK:
        fn = _SHARDED_FACTORIES.get(key)
        if fn is None:
            fn = make_sharded_multi_verify_msm(
                mesh, g1_windows=g1_windows, g1_wbits=g1_wbits,
                g2_windows=g2_windows, g2_wbits=g2_wbits, axis=axis,
                check_subgroup=check_subgroup,
            )
            _SHARDED_FACTORIES[key] = fn
    return fn


# --- host-facing backend ----------------------------------------------------


#: Largest device bucket; bigger host batches are split into chunks of this
#: size (each chunk is one RLC check — all chunks must pass).
MAX_BUCKET = 1 << 14


def _bucket(n: int, lo: int = 4, hi: int = MAX_BUCKET) -> int:
    b = lo
    while b < n:
        b <<= 1
    if b > hi:
        raise ValueError(f"batch of {n} exceeds max bucket {hi}")
    return b


# jax.jit caches per wrapper object — keep one wrapper per kernel for the
# whole process so every TpuBlsBackend instance shares compilations.
_JITTED: dict = {}


def _jitted_global(name: str, fn, donate=()):
    """One process-wide jitted wrapper per (kernel, donation policy).
    `donate` names the positional operands XLA may alias as outputs
    (donate_argnums): the dispatch sites only donate per-batch uploads —
    never registry arrays — and the donated-buffer-reuse lint rule
    enforces that no donated operand is touched after dispatch."""
    key = name if not donate else name + "|donate=" + repr(tuple(donate))
    f = _JITTED.get(key)
    if f is None:
        f = jax.jit(fn, donate_argnums=tuple(donate))
        _JITTED[key] = f
    return f


# --- shape-signature tracking (tools/shapes contract) -----------------------
#
# Process-wide ledger of every (kernel, arg-shapes) signature dispatched
# through _run_kernel. jax.jit compiles per signature, so after warmup
# declares the manifest compiled, a NOVEL signature means a live batch is
# stalling on XLA — counted in `verify_recompiles_total` and asserted
# zero by bench soaks and tests. Global (not per-backend) because
# _JITTED is: every TpuBlsBackend shares one compile cache.

_SHAPE_LOCK = threading.Lock()
_SHAPES_SEEN: set = set()
_WARMUP_SEALED = [False]
_POST_WARMUP_COMPILES = [0]


def _shape_key(kernel: str, args: tuple):
    return (kernel, tuple(
        (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else repr(a)
        for a in args
    ))


def _node_profiler():
    """runtime.profiler.get_profiler, resolved lazily: the kernel layer
    must not pull the runtime package in at module import time. The
    profiler's annotate() is a dict bump when no capture session is
    active; during a session it opens the TraceAnnotation scope keyed
    (scheme, kernel, bucket). Its dispatched() queues the call's output
    for the device timeline's watcher thread."""
    mod = sys.modules.get("grandine_tpu.runtime.profiler")
    if mod is None:
        from grandine_tpu.runtime import profiler as mod
    return mod.get_profiler()


def note_dispatch_shapes(kernel: str, args: tuple, metrics=None) -> bool:
    """Record a dispatch signature; True when it is novel this process.

    Novel-after-seal increments the recompile accounting (and the
    `verify_recompiles_total` counter when metrics are wired)."""
    key = _shape_key(kernel, args)
    with _SHAPE_LOCK:
        if key in _SHAPES_SEEN:
            return False
        _SHAPES_SEEN.add(key)
        sealed = _WARMUP_SEALED[0]
        if sealed:
            _POST_WARMUP_COMPILES[0] += 1
    if sealed and metrics is not None:
        metrics.verify_recompiles.inc()
    return True


def dispatch_scope(kernel: str, args: tuple, metrics=None):
    """Note a dispatch signature and return the scope its call runs in.
    jax.jit compiles synchronously where a signature is first CALLED, so
    a novel one runs inside `compiling()` (tpu/compile_scope.py): the
    settle watchdog does not charge that time, and host memory is trimmed
    when the compile ends. A known signature gets a null scope."""
    if note_dispatch_shapes(kernel, args, metrics):
        return compiling()
    return nullcontext()


def declare_warmup_complete() -> None:
    """Seal the shape ledger: every signature from here on is a recompile."""
    with _SHAPE_LOCK:
        _WARMUP_SEALED[0] = True


def warmup_declared() -> bool:
    with _SHAPE_LOCK:
        return _WARMUP_SEALED[0]


def post_warmup_recompiles() -> int:
    with _SHAPE_LOCK:
        return _POST_WARMUP_COMPILES[0]


def reset_shape_tracking() -> None:
    """Test seam: forget signatures and unseal (compiles in _JITTED stay)."""
    with _SHAPE_LOCK:
        _SHAPES_SEEN.clear()
        _WARMUP_SEALED[0] = False
        _POST_WARMUP_COMPILES[0] = 0


_ZERO2 = np.zeros((2, L.NLIMBS), np.int32)


#: cap on the per-backend hash-to-curve device-point cache; gossip traffic
#: churns through distinct AttestationData roots, so an unbounded cache is a
#: slow leak (~1.3 KB/entry) — override for benchmarking via the environment
H2C_CACHE_CAP = int(os.environ.get("GT_H2C_CACHE_CAP", "4096"))


class _LruCache:
    """Bounded thread-safe LRU keyed by hashables, with labeled metrics.

    Used for the hash-to-G2 message-point cache: hits remove a ~1 ms host
    hash_to_curve from the batch clock, but gossip churn means the key
    space is unbounded, so eviction (not clearing) keeps the hot working
    set — the current epoch's AttestationData points — resident."""

    def __init__(self, cap: int, name: str, metrics=None) -> None:
        self.cap = max(1, int(cap))
        self.name = name
        self.metrics = metrics
        self._lock = threading.Lock()
        self._entries: "OrderedDict" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def _event(self, event: str) -> None:
        if self.metrics is not None:
            self.metrics.device_cache_events.labels(self.name, event).inc()

    def get(self, key):
        with self._lock:
            hit = self._entries.get(key)
            if hit is None:
                self._event("miss")
                return None
            self._entries.move_to_end(key)
            self._event("hit")
            return hit

    def put(self, key, value) -> None:
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.cap:
                self._entries.popitem(last=False)
                self._event("evict")
            if self.metrics is not None:
                self.metrics.device_cache_size.set(
                    self.name, value=float(len(self._entries))
                )


class TpuBlsBackend:
    """Host façade: anchor-typed in/out, device execution, bucket-padded jit.

    The policy mirror of grandine_tpu/crypto/bls.py's multi_verify /
    fast_aggregate_verify — same edge-case semantics (empty batch, identity
    pubkeys), differential-tested against the anchor."""

    #: the async verify seam the runtime dispatches through (the first
    #: two are what runtime/health.py's REQUIRED_SEAM_METHODS detects;
    #: fault injection wraps exactly these — testing/chaos.ChaosBackend)
    ASYNC_SEAM = (
        "fast_aggregate_verify_batch_async",
        "g2_subgroup_check_batch_async",
        "fast_aggregate_verify_batch_indexed_async",
        "multi_verify_async",
        "rlc_partition_verify_async",
        "multi_verify_compressed_async",
        "fast_aggregate_verify_batch_compressed_async",
        "fast_aggregate_verify_batch_indexed_compressed_async",
    )

    def __init__(self, metrics=None, tracer=None,
                 lane: str = "attestation", mesh=None,
                 fuse_subgroup: "Optional[bool]" = None,
                 donate_buffers: "Optional[bool]" = None) -> None:
        from grandine_tpu.tpu.mesh import mesh_or_none

        #: observability seams (wired by runtime/attestation_verifier):
        #: per-stage histograms/spans + per-kernel-variant counters when
        #: set; with both None every hook is a cheap early return
        self.metrics = metrics
        self.tracer = tracer
        #: injected VerifyMesh (tpu/mesh.py) — None (or a degenerate
        #: 1-device mesh, normalized away here) keeps every dispatch below
        #: byte-identical to the single-chip backend: same kernels, same
        #: jit cache keys, same executables. Topology is NEVER discovered
        #: here (no jax.devices() in dispatch paths — lint-enforced);
        #: whoever owns the process hands the mesh in.
        self.mesh = mesh_or_none(mesh)
        #: lane label on verify_stage_seconds — the verify scheduler
        #: builds one façade per lane so device stages attribute to the
        #: lane that dispatched them (jitted kernels stay shared)
        self.lane = lane
        self._h2c_cache = _LruCache(
            H2C_CACHE_CAP, "hash_to_g2_dev", metrics=metrics
        )
        #: single-pass fused verification: the ψ-ladder subgroup check
        #: runs INSIDE each verify kernel (check_subgroup static) and the
        #: dispatchers skip the separate g2_subgroup_check pass — one
        #: device dispatch per batch instead of two. Default ON;
        #: GRANDINE_TPU_FUSE_SUBGROUP=0 restores the two-pass plane (the
        #: differential tests compare both).
        if fuse_subgroup is None:
            fuse_subgroup = os.environ.get(
                "GRANDINE_TPU_FUSE_SUBGROUP", "1"
            ) not in ("0", "false", "no")
        self.fuse_subgroup = bool(fuse_subgroup)
        #: buffer donation (donate_argnums): per-batch uploads are handed
        #: to XLA for output aliasing, stopping the HBM round-trip per
        #: pipelined kernel. Donation is unimplemented on CPU (jax warns
        #: per call and falls back to copies), so the default is
        #: platform-gated; GRANDINE_TPU_DONATE=0/1 overrides. Registry
        #: arrays are NEVER donated — they persist across batches.
        if donate_buffers is None:
            env = os.environ.get("GRANDINE_TPU_DONATE")
            if env is not None:
                donate_buffers = env not in ("0", "false", "no")
            else:
                donate_buffers = jax.default_backend() != "cpu"
        self.donate_buffers = bool(donate_buffers)

    # -- conversions -------------------------------------------------------

    def _hash_to_g2_dev(self, message: bytes, dst: bytes):
        key = (message, dst)
        hit = self._h2c_cache.get(key)
        if hit is None:
            hit = C.g2_point_to_dev(hash_to_g2(message, dst))
            self._h2c_cache.put(key, hit)
        return hit

    def _jitted(self, name: str, fn, donate=()):
        return _jitted_global(name, fn, donate=donate)

    def _donate(self, n: int, skip: int = 0) -> tuple:
        """donate_argnums for a kernel taking `n` per-batch operands after
        `skip` persistent ones (registry arrays at positions < skip are
        never donated). Empty when donation is off."""
        if not self.donate_buffers:
            return ()
        return tuple(range(skip, skip + n))

    # -- observability -----------------------------------------------------

    def _observed(self) -> bool:
        return self.metrics is not None or self.tracer is not None

    def _stage(self, stage: str, **attrs):
        """One device-plane stage (tracing.stage): span (when tracing) +
        one `verify_stage_seconds{stage,lane,op}` observation (when
        metered) + a host span in the profiler's trace during a capture
        session."""
        if not self._observed():
            return nullcontext()
        return _tracing.stage(
            self.tracer or _tracing.NULL_TRACER, self.metrics, stage,
            self.lane, **attrs
        )

    def _count_kernel(self, kernel: str, sigs: int) -> None:
        if self.metrics is not None:
            self.metrics.device_kernel_calls.labels(kernel).inc()
            if sigs:
                self.metrics.device_kernel_sigs.labels(kernel).inc(sigs)

    @staticmethod
    def _block(out):
        for leaf in jax.tree_util.tree_leaves(out):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
        return out

    def _upload(self, args: tuple, kernel: str = "unlabeled") -> tuple:
        """upload_bytes stage: push host arrays to the device explicitly
        so the transfer is attributable PER KERNEL (dispatch would do the
        identical transfer implicitly). Device-resident operands — the
        pubkey registry arrays — must bypass this seam: the per-kernel
        `device_upload_bytes_total` counter is the accounting that
        the lint rule no-per-batch-upload audits. No-op when unobserved."""
        if not self._observed():
            return args
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in args)
        if self.metrics is not None:
            self.metrics.device_upload_bytes.labels(kernel).inc(nbytes)
        with self._stage("upload_bytes", bytes=nbytes, kernel=kernel):
            return self._block(jax.device_put(args))

    def _upload_sharded(self, args: tuple, shardings, kernel: str) -> tuple:
        """Mesh-mode upload: place each host array with its explicit
        `NamedSharding` (jit would infer the same placement from the
        shard_map in_specs, but explicit placement keeps the transfer on
        the upload_bytes clock and out of the dispatch stage). Unlike
        `_upload` this must run even unobserved — the placement is the
        point, not the accounting."""
        if not self._observed():
            return tuple(
                jax.device_put(a, s) for a, s in zip(args, shardings)
            )
        nbytes = sum(int(getattr(a, "nbytes", 0)) for a in args)
        if self.metrics is not None:
            self.metrics.device_upload_bytes.labels(kernel).inc(nbytes)
        with self._stage("upload_bytes", bytes=nbytes, kernel=kernel):
            return self._block(tuple(
                jax.device_put(a, s) for a, s in zip(args, shardings)
            ))

    def _run_kernel(self, kernel: str, fn, args: tuple, sigs: int = 0,
                    block: bool = True):
        """Dispatch with compile/execute attribution. The first dispatch
        of a (kernel, shapes) signature in this process (the shape ledger,
        `dispatch_scope`) blocks on trace+XLA compilation, so its
        host-side call time IS the compile stage and runs inside
        `compiling()`; warm dispatches are async µs and the device run is
        timed via block_until_ready. With
        block=False the caller keeps the async seam and settles later
        (see _settle)."""
        self._count_kernel(kernel, sigs)
        novel = note_dispatch_shapes(kernel, args, self.metrics)
        prof = _node_profiler()
        if novel:
            with compiling(), self._stage("compile", kernel=kernel):
                with prof.annotate(kernel, sigs):
                    out = fn(*args)
        else:
            with prof.annotate(kernel, sigs):
                out = fn(*args)
        # the device timeline: enqueued now, busy until `out` is ready
        prof.dispatched(kernel, out, sigs, self.tracer)
        if block and self._observed():
            with self._stage("execute", kernel=kernel):
                self._block(out)
        return out

    def _settle(self, kernel: str, result) -> bool:
        """Force an async dispatch: remaining device time under execute,
        the host conversion under readback."""
        if not self._observed():
            return bool(result)
        with self._stage("execute", kernel=kernel):
            self._block(result)
        with self._stage("readback", kernel=kernel):
            return bool(result)

    # -- verification ------------------------------------------------------

    def multi_verify(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        public_keys: Sequence["A.PublicKey"],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ) -> bool:
        return self.multi_verify_async(messages, signatures, public_keys, dst, rng)()

    def multi_verify_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        public_keys: Sequence["A.PublicKey"],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ):
        """Dispatch the batch to the device WITHOUT blocking: returns a
        zero-arg callable producing the bool. XLA execution is async until
        the result is forced, so host work (block processing) overlaps the
        device pairing — the seam `combined.custom_state_transition` uses
        for its verify-∥-process split."""
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            return lambda: False
        if n == 0:
            return lambda: True
        if n > MAX_BUCKET:
            # Two-deep pipeline: only chunk 0 is dispatched now (so callers
            # still overlap it with host work); settle() dispatches chunk
            # k+1 before forcing chunk k. Bounds device residency at two
            # chunks and stops dispatching after the first failure.
            def chunk(i):
                return self.multi_verify_async(
                    messages[i : i + MAX_BUCKET],
                    signatures[i : i + MAX_BUCKET],
                    public_keys[i : i + MAX_BUCKET],
                    dst,
                    rng,
                )

            first = chunk(0)

            def settle_chunks() -> bool:
                pending = first
                for i in range(MAX_BUCKET, n, MAX_BUCKET):
                    nxt = chunk(i)
                    if not pending():
                        return False
                    pending = nxt
                return pending()

            return settle_chunks
        if any(pk.point.is_infinity() for pk in public_keys):
            return lambda: False
        with self._stage("host_prep", op="point_convert", items=n):
            # batched host conversions: one inversion + one limb pass per
            # class
            g1x, g1y, g1inf = C.g1_points_to_dev(
                [pk.point for pk in public_keys]
            )
            g2x, g2y, g2inf = C.g2_points_to_dev(
                [s.point for s in signatures]
            )

            # group triples by message: Miller loops collapse from N to the
            # number of DISTINCT messages (grouped_multi_verify_msm_kernel)
            groups: "dict[bytes, list[int]]" = {}
            for i, msg in enumerate(messages):
                groups.setdefault(bytes(msg), []).append(i)
        n_groups = len(groups)
        if 2 * n_groups <= n:
            bm = _bucket(n_groups)
            bk = _bucket(max(len(v) for v in groups.values()))
            if bm * bk <= 4 * _bucket(n):  # bounded padding waste
                return self._grouped_multi_verify_async(
                    groups, g1x, g1y, g1inf, g2x, g2y, g2inf,
                    bm, bk, dst, rng,
                )

        with self._stage("host_prep", op="pack", items=n):
            b = _bucket(n)
            pk_x = np.zeros((b, L.NLIMBS), np.int32)
            pk_y = np.zeros((b, L.NLIMBS), np.int32)
            pk_inf = np.ones((b,), bool)
            sig_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            sig_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            sig_inf = np.ones((b,), bool)
            msg_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((b,), bool)
            pk_x[:n], pk_y[:n], pk_inf[:n] = g1x, g1y, g1inf
            sig_x[:n], sig_y[:n], sig_inf[:n] = g2x, g2y, g2inf
            for i in range(n):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(n)]
            r_bits = rlc_bits_host(pairs, b)
        mesh = self.mesh
        if mesh is not None and mesh.divides(b) and b >= 2 * mesh.device_count:
            # data-parallel whole-batch dispatch over the promoted sharded
            # RLC kernel: batch rows shard over the mesh, each chip runs
            # its local ladders/Miller loops, and the pairing-product
            # all-gather is the only collective (tpu/mesh.py seam)
            fn = sharded_multi_verify(
                mesh.mesh, axis=mesh.axis,
                check_subgroup=int(self.fuse_subgroup),
            )
            args = self._upload_sharded(
                (pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
                 msg_x, msg_y, msg_inf, r_bits),
                (mesh.batch_sharding(),) * 10,
                kernel="sharded_multi_verify",
            )
            result = self._run_kernel(
                "sharded_multi_verify", fn, args, sigs=n, block=False,
            )
            return lambda: self._settle("sharded_multi_verify", result)
        with self._stage("host_prep", op="msm_plan", items=n):
            g2_plan = self._g2_plan(pairs, b, sig_inf)
        args = self._upload((
            pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf,
            r_bits, *g2_plan.arrays,
        ), kernel="multi_verify_msm")
        fn = self._jitted_msm(
            "multi_verify_msm", multi_verify_msm_kernel,
            donate=self._donate(len(args)),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=int(self.fuse_subgroup),
        )
        # async dispatch; forcing happens in the returned closure
        result = self._run_kernel(
            "multi_verify_msm", fn, args, sigs=n, block=False
        )
        return lambda: self._settle("multi_verify_msm", result)

    @staticmethod
    def _g2_plan(pairs, b, sig_inf):
        """MSM plan for Σ rᵢ·sigᵢ over a padded bucket of b slots (real
        pairs first; padding masked out via sig_inf)."""
        r_lo = np.zeros(b, np.uint64)
        r_hi = np.zeros(b, np.uint64)
        n = len(pairs)
        r_lo[:n] = [p[0] for p in pairs]
        r_hi[:n] = [p[1] for p in pairs]
        return M.plan_msm(
            r_lo, r_hi, np.asarray(sig_inf, bool), None, 1,
            window_bits=pick_msm_window(b, 1),
        )

    def _jitted_msm(self, name: str, fn, donate=(), **static_kw):
        key = name + repr(sorted(static_kw.items()))
        if donate:
            key += "|donate=" + repr(tuple(donate))
        cached = _JITTED.get(key)
        if cached is None:
            import functools

            # functools.partial applies keywords only, so positional
            # donate_argnums indices are unaffected by the static binding
            cached = jax.jit(
                functools.partial(fn, **static_kw),
                donate_argnums=tuple(donate),
            )
            _JITTED[key] = cached
        return cached

    def _grouped_multi_verify_async(
        self, groups, g1x, g1y, g1inf, g2x, g2y, g2inf, bm, bk, dst, rng
    ):
        """Pack per-message groups into the (M, K) grouped MSM kernel.

        Kernel-flat point index f ↔ grouped slot (f mod bm, f div bm), so
        the MSM plans carry scalars in f = kk·bm + j order with
        group(f) = f mod bm."""
        with self._stage("host_prep", op="pack_grouped", items=bm * bk):
            pk_x = np.zeros((bm, bk, L.NLIMBS), np.int32)
            pk_y = np.zeros((bm, bk, L.NLIMBS), np.int32)
            pk_inf = np.ones((bm, bk), bool)
            sig_x = np.zeros((bm, bk, 2, L.NLIMBS), np.int32)
            sig_y = np.zeros((bm, bk, 2, L.NLIMBS), np.int32)
            sig_inf = np.ones((bm, bk), bool)
            msg_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((bm,), bool)
            r_lo = np.zeros(bm * bk, np.uint64)
            r_hi = np.zeros(bm * bk, np.uint64)
            n_real = 0
            for j, (msg, idxs) in enumerate(groups.items()):
                x, y, inf = self._hash_to_g2_dev(msg, dst)
                msg_x[j], msg_y[j], msg_inf[j] = x, y, inf
                for kk, i in enumerate(idxs):
                    pk_x[j, kk], pk_y[j, kk], pk_inf[j, kk] = (
                        g1x[i], g1y[i], g1inf[i],
                    )
                    sig_x[j, kk], sig_y[j, kk], sig_inf[j, kk] = (
                        g2x[i], g2y[i], g2inf[i],
                    )
                    r_lo[kk * bm + j], r_hi[kk * bm + j] = self._rlc_pair(rng)
                    n_real += 1
        mesh = self.mesh
        if (
            mesh is not None
            and bk % mesh.device_count == 0
            and bm % mesh.device_count == 0
        ):
            return self._sharded_grouped_verify_async(
                mesh, pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
                msg_x, msg_y, msg_inf, r_lo, r_hi, n_real,
            )
        with self._stage("host_prep", op="msm_plan", items=bm * bk):
            flat_inf = pk_inf.T.reshape(-1)  # f = kk·bm + j order; pads True
            flat_groups = np.arange(bm * bk) % bm
            g1_plan = M.plan_msm(
                r_lo, r_hi, flat_inf, flat_groups, bm,
                window_bits=pick_msm_window(n_real, bm),
            )
            g2_plan = M.plan_msm(
                r_lo, r_hi, sig_inf.T.reshape(-1), None, 1,
                window_bits=pick_msm_window(n_real, 1),
            )
        args = self._upload((
            pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
            msg_x, msg_y, msg_inf, *g1_plan.arrays, *g2_plan.arrays,
        ), kernel="grouped_multi_verify_msm")
        fn = self._jitted_msm(
            "grouped_multi_verify_msm", grouped_multi_verify_msm_kernel,
            donate=self._donate(len(args)),
            g1_windows=g1_plan.windows, g1_wbits=g1_plan.window_bits,
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=int(self.fuse_subgroup),
        )
        result = self._run_kernel(
            "grouped_multi_verify_msm", fn, args, sigs=n_real, block=False
        )
        return lambda: self._settle("grouped_multi_verify_msm", result)

    def _sharded_grouped_verify_async(
        self, mesh, pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
        msg_x, msg_y, msg_inf, r_lo, r_hi, n_real,
    ):
        """Grouped batch over the promoted sharded MSM kernel: the (M, K)
        member axis shards across the mesh, per-chip Pippenger bucket
        scans reduce in one all-gather of group partials, and the Miller
        plane shards by message (make_sharded_multi_verify_msm)."""
        bm, bk = pk_inf.shape
        with self._stage("host_prep", op="sharded_msm_plan", items=bm * bk):
            g1_stack, g2_stack, g1_p0, g2_p0 = sharded_msm_plans(
                r_lo, r_hi, pk_inf, sig_inf, mesh.device_count
            )
        fn = sharded_multi_verify_msm(
            mesh.mesh,
            g1_windows=g1_p0.windows, g1_wbits=g1_p0.window_bits,
            g2_windows=g2_p0.windows, g2_wbits=g2_p0.window_bits,
            axis=mesh.axis,
            check_subgroup=int(self.fuse_subgroup),
        )
        plan = mesh.batch_sharding()
        args = self._upload_sharded(
            (pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
             msg_x, msg_y, msg_inf, *g1_stack, *g2_stack),
            (mesh.member_sharding(),) * 6 + (mesh.replicated(),) * 3
            + (plan,) * (len(g1_stack) + len(g2_stack)),
            kernel="sharded_multi_verify_msm",
        )
        result = self._run_kernel(
            "sharded_multi_verify_msm", fn, args, sigs=n_real, block=False,
        )
        return lambda: self._settle("sharded_multi_verify_msm", result)

    def verify(
        self,
        message: bytes,
        signature: "A.Signature",
        public_key: "A.PublicKey",
        dst: bytes = constants.DST_SIGNATURE,
    ) -> bool:
        return self.multi_verify([message], [signature], [public_key], dst)

    def fast_aggregate_verify_batch(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        member_keys: Sequence[Sequence["A.PublicKey"]],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
        bucket_floor: "Optional[tuple[int, int]]" = None,
    ) -> bool:
        """M aggregates, each over its own committee (the gossip firehose)."""
        return self.fast_aggregate_verify_batch_async(
            messages, signatures, member_keys, dst, rng, bucket_floor
        )()

    def fast_aggregate_verify_batch_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        member_keys: Sequence[Sequence["A.PublicKey"]],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
        bucket_floor: "Optional[tuple[int, int]]" = None,
    ):
        """Async firehose verify: host prep + dispatch now, a zero-arg
        settle callable forces the device result. This is the seam the
        pipelined AttestationVerifier uses to overlap batch N+1's host
        prep with batch N's device execute.

        `bucket_floor` is the (items, widest committee) — or the bucket
        itself — of the batch this call is a PART of: the call then pads
        to that batch's bucket, so a half of a failed batch runs the
        executable its parent ran (the kernel masks padding slots, and
        the MSM plan's shapes are a function of the bucket alone), never
        a smaller one nobody warmed."""
        m = len(messages)
        if not (m == len(signatures) == len(member_keys)):
            return lambda: False
        if m == 0:
            return lambda: True
        if any(not ks for ks in member_keys):
            return lambda: False
        if m > MAX_BUCKET:
            # Two-deep chunk pipeline, same shape as multi_verify_async:
            # settle() dispatches chunk k+1 before forcing chunk k.
            def chunk(i):
                return self.fast_aggregate_verify_batch_async(
                    messages[i : i + MAX_BUCKET],
                    signatures[i : i + MAX_BUCKET],
                    member_keys[i : i + MAX_BUCKET],
                    dst,
                    rng,
                    bucket_floor,
                )

            first = chunk(0)

            def settle_chunks() -> bool:
                pending = first
                for i in range(MAX_BUCKET, m, MAX_BUCKET):
                    nxt = chunk(i)
                    if not pending():
                        return False
                    pending = nxt
                return pending()

            return settle_chunks
        if any(pk.point.is_infinity() for ks in member_keys for pk in ks):
            return lambda: False
        with self._stage("host_prep", op="pack_aggregate", items=m):
            if max(len(ks) for ks in member_keys) > MAX_BUCKET:
                # committee wider than a device bucket: host-aggregate those
                # committees to a single key (same check: e(agg_pk, H(m)))
                member_keys = [
                    ks if len(ks) <= MAX_BUCKET else [A.PublicKey.aggregate(ks)]
                    for ks in member_keys
                ]
            floor_m, floor_k = bucket_floor or (0, 0)
            bm = _bucket(max(m, floor_m))
            bk = _bucket(
                max(max(len(ks) for ks in member_keys), floor_k), lo=4
            )
            mem_x = np.zeros((bm, bk, L.NLIMBS), np.int32)
            mem_y = np.zeros((bm, bk, L.NLIMBS), np.int32)
            mem_inf = np.ones((bm, bk), bool)
            slot_pad = np.arange(bm) >= m
            sig_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            sig_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            sig_inf = np.ones((bm,), bool)
            msg_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((bm,), bool)
            flat_keys = [pk.point for ks in member_keys for pk in ks]
            fx, fy, finf = C.g1_points_to_dev(flat_keys)
            pos = 0
            for i in range(m):
                k = len(member_keys[i])
                mem_x[i, :k] = fx[pos : pos + k]
                mem_y[i, :k] = fy[pos : pos + k]
                mem_inf[i, :k] = finf[pos : pos + k]
                pos += k
            g2x, g2y, g2inf = C.g2_points_to_dev([s.point for s in signatures])
            sig_x[:m], sig_y[:m], sig_inf[:m] = g2x, g2y, g2inf
            for i in range(m):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(m)]
            r_bits = rlc_bits_host(pairs, bm)
            g2_plan = self._g2_plan(pairs, bm, sig_inf)
        args = self._upload((
            mem_x, mem_y, mem_inf, slot_pad, sig_x, sig_y, sig_inf,
            msg_x, msg_y, msg_inf, r_bits, *g2_plan.arrays,
        ), kernel="agg_fast_verify_msm")
        fn = self._jitted_msm(
            "agg_fast_verify_msm", aggregate_fast_verify_msm_kernel,
            donate=self._donate(len(args)),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=int(self.fuse_subgroup),
        )
        out = self._run_kernel(
            "agg_fast_verify_msm", fn, args, sigs=m, block=False
        )
        return lambda: self._settle("agg_fast_verify_msm", out)

    def fast_aggregate_verify_batch_indexed(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        member_indices: Sequence[Sequence[int]],
        registry,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
        bucket_floor: "Optional[tuple[int, int]]" = None,
    ) -> bool:
        return self.fast_aggregate_verify_batch_indexed_async(
            messages, signatures, member_indices, registry, dst, rng,
            bucket_floor,
        )()

    def fast_aggregate_verify_batch_indexed_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        member_indices: Sequence[Sequence[int]],
        registry,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
        bucket_floor: "Optional[tuple[int, int]]" = None,
    ):
        """Registry firehose verify: committee pubkeys stay device-resident
        (tpu/registry.py), gathered on-device by validator index — the
        per-batch upload shrinks from 208 B/member of affine coordinates to
        4 B/member of int32 indices. Registry rows never hold the identity
        (decompress raises), so the infinity policy reduces to the padding
        mask. A committee wider than a device bucket falls back to the
        upload path through the registry's host mirror; an index the
        registry does not cover (cold registry, out-of-range) is a
        verification failure — it names a validator outside the set the
        caller synced the registry to. `bucket_floor`: as in
        `fast_aggregate_verify_batch_async`."""
        m = len(messages)
        if not (m == len(signatures) == len(member_indices)):
            return lambda: False
        if m == 0:
            return lambda: True
        if any(len(ix) == 0 for ix in member_indices):
            return lambda: False
        if m > MAX_BUCKET:
            def chunk(i):
                return self.fast_aggregate_verify_batch_indexed_async(
                    messages[i : i + MAX_BUCKET],
                    signatures[i : i + MAX_BUCKET],
                    member_indices[i : i + MAX_BUCKET],
                    registry,
                    dst,
                    rng,
                    bucket_floor,
                )

            first = chunk(0)

            def settle_chunks() -> bool:
                pending = first
                for i in range(MAX_BUCKET, m, MAX_BUCKET):
                    nxt = chunk(i)
                    if not pending():
                        return False
                    pending = nxt
                return pending()

            return settle_chunks
        reg_x, reg_y, reg_n = registry.arrays()
        widest = max(len(ix) for ix in member_indices)
        if reg_x is None or any(
            not 0 <= int(i) < reg_n for ix in member_indices for i in ix
        ):
            # an index the registry has never seen names a validator
            # outside the head state's set — the signature cannot verify
            return lambda: False
        if widest > MAX_BUCKET:
            # committee wider than a device bucket: resolve through the
            # host mirror and take the upload path (which host-aggregates
            # oversized committees to a single key)
            return self.fast_aggregate_verify_batch_async(
                messages,
                signatures,
                [registry.public_keys(ix) for ix in member_indices],
                dst,
                rng,
                bucket_floor,
            )
        with self._stage("host_prep", op="pack_aggregate_idx", items=m):
            floor_m, floor_k = bucket_floor or (0, 0)
            bm = _bucket(max(m, floor_m))
            bk = _bucket(max(widest, floor_k), lo=4)
            mem_idx = np.zeros((bm, bk), np.int32)
            mem_inf = np.ones((bm, bk), bool)  # True = padding slot
            slot_pad = np.arange(bm) >= m
            sig_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            sig_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            sig_inf = np.ones((bm,), bool)
            msg_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((bm,), bool)
            for i, ix in enumerate(member_indices):
                k = len(ix)
                mem_idx[i, :k] = np.fromiter(
                    (int(v) for v in ix), np.int32, count=k
                )
                mem_inf[i, :k] = False
            g2x, g2y, g2inf = C.g2_points_to_dev([s.point for s in signatures])
            sig_x[:m], sig_y[:m], sig_inf[:m] = g2x, g2y, g2inf
            for i in range(m):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(m)]
            r_bits = rlc_bits_host(pairs, bm)
            g2_plan = self._g2_plan(pairs, bm, sig_inf)
        # registry arrays are already device-resident: they are passed to
        # the kernel directly, NOT through _upload, so the per-batch
        # upload accounting stays honest (lint rule no-per-batch-upload)
        args = self._upload((
            mem_idx, mem_inf, slot_pad, sig_x, sig_y, sig_inf,
            msg_x, msg_y, msg_inf, r_bits, *g2_plan.arrays,
        ), kernel="agg_fast_verify_msm_idx")
        # donation skips the two registry operands — they outlive the batch
        fn = self._jitted_msm(
            "agg_fast_verify_msm_idx", aggregate_fast_verify_msm_idx_kernel,
            donate=self._donate(len(args), skip=2),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=int(self.fuse_subgroup),
        )
        out = self._run_kernel(
            "agg_fast_verify_msm_idx", fn, (reg_x, reg_y, *args),
            sigs=m, block=False,
        )
        return lambda: self._settle("agg_fast_verify_msm_idx", out)

    # -- compressed-ingest verification ------------------------------------
    #
    # The *_compressed_async trio takes SIGNATURES AS RAW WIRE BYTES
    # (48/96-byte compressed encodings) and decompresses them on device
    # inside the verify kernel itself, replacing the per-item pure-Python
    # Fq2.sqrt host stage (`host_prep op=g2_decompress` in tpu/schemes.py)
    # that made the plane prep-bound. The host twin path is retained
    # verbatim as the anchor and degradation target.

    @staticmethod
    def _pack_sig_rows(signatures, b: int):
        """(b, 96) uint8 padded compressed signature rows + the host-side
        sig_inf mask (padding ∪ wire infinity flag). Padding rows carry
        the canonical infinity encoding (0xC0 ‖ 0⁹⁵) so they decompress
        as valid neutral slots; malformed payloads are NOT screened here —
        per-row rejection is the device kernel's job. Raises ValueError
        on a wrong-length blob (the one structural property bytes can't
        defer)."""
        rows = C.compressed_rows(signatures, 96)
        n = rows.shape[0]
        sig_rows = np.zeros((b, 96), np.uint8)
        sig_rows[:, 0] = C.COMPRESSED_FLAG | C.INFINITY_FLAG
        sig_rows[:n] = rows
        sig_inf = np.ones((b,), bool)
        sig_inf[:n] = C.compressed_infinity_flags(rows)
        return sig_rows, sig_inf

    def multi_verify_compressed(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        public_keys: Sequence["A.PublicKey"],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ) -> bool:
        return self.multi_verify_compressed_async(
            messages, signatures, public_keys, dst, rng
        )()

    def multi_verify_compressed_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        public_keys: Sequence["A.PublicKey"],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ):
        """multi_verify_async with signatures as compressed wire bytes:
        host prep shrinks to a memcpy row-pack (no Fq2.sqrt, no Montgomery
        lift), decompression + subgroup + pairing run as ONE device pass
        (multi_verify_msm_comp_kernel). Always takes the flat MSM path —
        grouping/sharding stay on the uncompressed twins."""
        n = len(messages)
        if not (n == len(signatures) == len(public_keys)):
            return lambda: False
        if n == 0:
            return lambda: True
        if n > MAX_BUCKET:
            def chunk(i):
                return self.multi_verify_compressed_async(
                    messages[i : i + MAX_BUCKET],
                    signatures[i : i + MAX_BUCKET],
                    public_keys[i : i + MAX_BUCKET],
                    dst,
                    rng,
                )

            first = chunk(0)

            def settle_chunks() -> bool:
                pending = first
                for i in range(MAX_BUCKET, n, MAX_BUCKET):
                    nxt = chunk(i)
                    if not pending():
                        return False
                    pending = nxt
                return pending()

            return settle_chunks
        if any(pk.point.is_infinity() for pk in public_keys):
            return lambda: False
        with self._stage("host_prep", op="pack_compressed", items=n):
            b = _bucket(n)
            try:
                sig_rows, sig_inf = self._pack_sig_rows(signatures, b)
            except ValueError:
                return lambda: False  # wrong-length blob
            g1x, g1y, g1inf = C.g1_points_to_dev(
                [pk.point for pk in public_keys]
            )
            pk_x = np.zeros((b, L.NLIMBS), np.int32)
            pk_y = np.zeros((b, L.NLIMBS), np.int32)
            pk_inf = np.ones((b,), bool)
            msg_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((b,), bool)
            pk_x[:n], pk_y[:n], pk_inf[:n] = g1x, g1y, g1inf
            for i in range(n):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(n)]
            r_bits = rlc_bits_host(pairs, b)
        with self._stage("host_prep", op="msm_plan", items=n):
            g2_plan = self._g2_plan(pairs, b, sig_inf)
        args = self._upload((
            pk_x, pk_y, pk_inf, sig_rows, sig_inf,
            msg_x, msg_y, msg_inf, r_bits, *g2_plan.arrays,
        ), kernel="multi_verify_msm_comp")
        # compressed ingest ALWAYS fuses the ψ-ladder subgroup check:
        # the decompressed points never exist on the host, so the
        # two-pass g2_subgroup_check_batch_async fallback cannot cover
        # them — check_subgroup is not optional here
        fn = self._jitted_msm(
            "multi_verify_msm_comp", multi_verify_msm_comp_kernel,
            donate=self._donate(len(args)),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=1,
        )
        result = self._run_kernel(
            "multi_verify_msm_comp", fn, args, sigs=n, block=False
        )
        return lambda: self._settle("multi_verify_msm_comp", result)

    def fast_aggregate_verify_batch_compressed(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        member_keys: Sequence[Sequence["A.PublicKey"]],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ) -> bool:
        return self.fast_aggregate_verify_batch_compressed_async(
            messages, signatures, member_keys, dst, rng
        )()

    def fast_aggregate_verify_batch_compressed_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        member_keys: Sequence[Sequence["A.PublicKey"]],
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ):
        """fast_aggregate_verify_batch_async with signatures as compressed
        wire bytes — the gossip firehose's native format, decompressed on
        device in the verify pass (aggregate_fast_verify_msm_comp_kernel)."""
        m = len(messages)
        if not (m == len(signatures) == len(member_keys)):
            return lambda: False
        if m == 0:
            return lambda: True
        if any(not ks for ks in member_keys):
            return lambda: False
        if m > MAX_BUCKET:
            def chunk(i):
                return self.fast_aggregate_verify_batch_compressed_async(
                    messages[i : i + MAX_BUCKET],
                    signatures[i : i + MAX_BUCKET],
                    member_keys[i : i + MAX_BUCKET],
                    dst,
                    rng,
                )

            first = chunk(0)

            def settle_chunks() -> bool:
                pending = first
                for i in range(MAX_BUCKET, m, MAX_BUCKET):
                    nxt = chunk(i)
                    if not pending():
                        return False
                    pending = nxt
                return pending()

            return settle_chunks
        if any(pk.point.is_infinity() for ks in member_keys for pk in ks):
            return lambda: False
        with self._stage("host_prep", op="pack_aggregate_compressed", items=m):
            if max(len(ks) for ks in member_keys) > MAX_BUCKET:
                member_keys = [
                    ks if len(ks) <= MAX_BUCKET else [A.PublicKey.aggregate(ks)]
                    for ks in member_keys
                ]
            bm = _bucket(m)
            bk = _bucket(max(len(ks) for ks in member_keys), lo=4)
            try:
                sig_rows, sig_inf = self._pack_sig_rows(signatures, bm)
            except ValueError:
                return lambda: False
            mem_x = np.zeros((bm, bk, L.NLIMBS), np.int32)
            mem_y = np.zeros((bm, bk, L.NLIMBS), np.int32)
            mem_inf = np.ones((bm, bk), bool)
            slot_pad = np.arange(bm) >= m
            msg_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((bm,), bool)
            flat_keys = [pk.point for ks in member_keys for pk in ks]
            fx, fy, finf = C.g1_points_to_dev(flat_keys)
            pos = 0
            for i in range(m):
                k = len(member_keys[i])
                mem_x[i, :k] = fx[pos : pos + k]
                mem_y[i, :k] = fy[pos : pos + k]
                mem_inf[i, :k] = finf[pos : pos + k]
                pos += k
            for i in range(m):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(m)]
            r_bits = rlc_bits_host(pairs, bm)
            g2_plan = self._g2_plan(pairs, bm, sig_inf)
        args = self._upload((
            mem_x, mem_y, mem_inf, slot_pad, sig_rows, sig_inf,
            msg_x, msg_y, msg_inf, r_bits, *g2_plan.arrays,
        ), kernel="agg_fast_verify_msm_comp")
        # subgroup check always fused on compressed ingest (see
        # multi_verify_compressed_async)
        fn = self._jitted_msm(
            "agg_fast_verify_msm_comp", aggregate_fast_verify_msm_comp_kernel,
            donate=self._donate(len(args)),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=1,
        )
        out = self._run_kernel(
            "agg_fast_verify_msm_comp", fn, args, sigs=m, block=False
        )
        return lambda: self._settle("agg_fast_verify_msm_comp", out)

    def fast_aggregate_verify_batch_indexed_compressed(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        member_indices: Sequence[Sequence[int]],
        registry,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ) -> bool:
        return self.fast_aggregate_verify_batch_indexed_compressed_async(
            messages, signatures, member_indices, registry, dst, rng
        )()

    def fast_aggregate_verify_batch_indexed_compressed_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence[bytes],
        member_indices: Sequence[Sequence[int]],
        registry,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ):
        """The fully-device-fed firehose: member pubkeys gathered from the
        resident registry by index AND signatures decompressed on device.
        Per-batch upload = 96 B/aggregate of wire bytes + 4 B/member of
        indices; host prep does no field arithmetic at all."""
        m = len(messages)
        if not (m == len(signatures) == len(member_indices)):
            return lambda: False
        if m == 0:
            return lambda: True
        if any(len(ix) == 0 for ix in member_indices):
            return lambda: False
        if m > MAX_BUCKET:
            def chunk(i):
                return self.fast_aggregate_verify_batch_indexed_compressed_async(
                    messages[i : i + MAX_BUCKET],
                    signatures[i : i + MAX_BUCKET],
                    member_indices[i : i + MAX_BUCKET],
                    registry,
                    dst,
                    rng,
                )

            first = chunk(0)

            def settle_chunks() -> bool:
                pending = first
                for i in range(MAX_BUCKET, m, MAX_BUCKET):
                    nxt = chunk(i)
                    if not pending():
                        return False
                    pending = nxt
                return pending()

            return settle_chunks
        reg_x, reg_y, reg_n = registry.arrays()
        widest = max(len(ix) for ix in member_indices)
        if reg_x is None or any(
            not 0 <= int(i) < reg_n for ix in member_indices for i in ix
        ):
            return lambda: False
        if widest > MAX_BUCKET:
            return self.fast_aggregate_verify_batch_compressed_async(
                messages,
                signatures,
                [registry.public_keys(ix) for ix in member_indices],
                dst,
                rng,
            )
        with self._stage(
            "host_prep", op="pack_aggregate_idx_compressed", items=m
        ):
            bm = _bucket(m)
            bk = _bucket(widest, lo=4)
            try:
                sig_rows, sig_inf = self._pack_sig_rows(signatures, bm)
            except ValueError:
                return lambda: False
            mem_idx = np.zeros((bm, bk), np.int32)
            mem_inf = np.ones((bm, bk), bool)  # True = padding slot
            slot_pad = np.arange(bm) >= m
            msg_x = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((bm, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((bm,), bool)
            for i, ix in enumerate(member_indices):
                k = len(ix)
                mem_idx[i, :k] = np.fromiter(
                    (int(v) for v in ix), np.int32, count=k
                )
                mem_inf[i, :k] = False
            for i in range(m):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(m)]
            r_bits = rlc_bits_host(pairs, bm)
            g2_plan = self._g2_plan(pairs, bm, sig_inf)
        # registry arrays are device-resident: passed directly, NOT through
        # _upload, so per-batch upload accounting stays honest
        args = self._upload((
            mem_idx, mem_inf, slot_pad, sig_rows, sig_inf,
            msg_x, msg_y, msg_inf, r_bits, *g2_plan.arrays,
        ), kernel="agg_fast_verify_msm_idx_comp")
        # subgroup check always fused on compressed ingest (see
        # multi_verify_compressed_async)
        fn = self._jitted_msm(
            "agg_fast_verify_msm_idx_comp",
            aggregate_fast_verify_msm_idx_comp_kernel,
            donate=self._donate(len(args), skip=2),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=1,
        )
        out = self._run_kernel(
            "agg_fast_verify_msm_idx_comp", fn, (reg_x, reg_y, *args),
            sigs=m, block=False,
        )
        return lambda: self._settle("agg_fast_verify_msm_idx_comp", out)

    def multi_verify_indexed(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        indices: Sequence[int],
        registry,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ) -> bool:
        """Flat RLC batch verify with signer pubkeys gathered on-device
        from the registry by validator index (one signer per triple).
        Batches beyond one bucket fall back to the upload path through
        the host mirror; an index the registry does not cover fails."""
        n = len(messages)
        if not (n == len(signatures) == len(indices)):
            return False
        if n == 0:
            return True
        reg_x, reg_y, reg_n = registry.arrays()
        if reg_x is None or any(not 0 <= int(i) < reg_n for i in indices):
            return False  # unknown validator index → cannot verify
        if n > MAX_BUCKET:
            return self.multi_verify(
                messages, signatures, registry.public_keys(indices), dst, rng
            )
        with self._stage("host_prep", op="pack_idx", items=n):
            b = _bucket(n)
            pk_idx = np.zeros((b,), np.int32)
            pk_inf = np.ones((b,), bool)  # True = padding slot
            pk_idx[:n] = np.fromiter(
                (int(v) for v in indices), np.int32, count=n
            )
            pk_inf[:n] = False
            sig_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            sig_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            sig_inf = np.ones((b,), bool)
            msg_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((b,), bool)
            g2x, g2y, g2inf = C.g2_points_to_dev([s.point for s in signatures])
            sig_x[:n], sig_y[:n], sig_inf[:n] = g2x, g2y, g2inf
            for i in range(n):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(n)]
            r_bits = rlc_bits_host(pairs, b)
            g2_plan = self._g2_plan(pairs, b, sig_inf)
        args = self._upload((
            pk_idx, pk_inf, sig_x, sig_y, sig_inf, msg_x, msg_y, msg_inf,
            r_bits, *g2_plan.arrays,
        ), kernel="multi_verify_msm_idx")
        fn = self._jitted_msm(
            "multi_verify_msm_idx", multi_verify_msm_idx_kernel,
            donate=self._donate(len(args), skip=2),
            g2_windows=g2_plan.windows, g2_wbits=g2_plan.window_bits,
            check_subgroup=int(self.fuse_subgroup),
        )
        result = self._run_kernel(
            "multi_verify_msm_idx", fn, (reg_x, reg_y, *args),
            sigs=n, block=False,
        )
        return self._settle("multi_verify_msm_idx", result)

    def fast_aggregate_verify(
        self,
        message: bytes,
        signature: "A.Signature",
        public_keys: Sequence["A.PublicKey"],
        dst: bytes = constants.DST_SIGNATURE,
    ) -> bool:
        return self.fast_aggregate_verify_batch(
            [message], [signature], [public_keys], dst
        )

    def g2_subgroup_check_batch(self, points) -> "np.ndarray":
        """Batched subgroup membership for decompressed (on-curve) G2
        points — ONE device ladder replaces N host scalar-muls. Accepts
        anchor `Point[Fq2]` values; returns an (N,) bool array (infinity
        rows True; reject them separately by policy)."""
        return self.g2_subgroup_check_batch_async(points)()

    def g2_subgroup_check_batch_async(self, points):
        """Async variant of g2_subgroup_check_batch: dispatch now, force
        via the returned zero-arg callable. The pipelined verifier stacks
        this dispatch with the verify-kernel dispatch so both device runs
        queue back-to-back ahead of any host readback."""
        n = len(points)
        if n == 0:
            return lambda: np.zeros((0,), bool)
        with self._stage("host_prep", op="pack_subgroup", items=n):
            bn = _bucket(n)
            sx = np.zeros((bn, 2, L.NLIMBS), np.int32)
            sy = np.zeros((bn, 2, L.NLIMBS), np.int32)
            s_inf = np.ones((bn,), bool)
            gx, gy, ginf = C.g2_points_to_dev(points)
            sx[:n], sy[:n], s_inf[:n] = gx, gy, ginf
            x_bits = np.ascontiguousarray(
                C.scalars_to_bits_msb([_ABS_X] * bn, 64).T
            )
        args = self._upload((sx, sy, s_inf, x_bits), kernel="g2_subgroup_check")
        fn = self._jitted(
            "g2_subgroup_check", g2_subgroup_check_kernel,
            donate=self._donate(len(args)),
        )
        dev_out = self._run_kernel(
            "g2_subgroup_check", fn, args, sigs=n, block=False
        )

        def settle() -> "np.ndarray":
            if not self._observed():
                return np.asarray(dev_out)[:n]
            with self._stage("execute", kernel="g2_subgroup_check"):
                self._block(dev_out)
            with self._stage("readback", kernel="g2_subgroup_check"):
                return np.asarray(dev_out)[:n]

        return settle

    # -- fault localization ------------------------------------------------

    def rlc_partition_verify(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        member_keys: Sequence[Sequence["A.PublicKey"]],
        groups: int,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ) -> "np.ndarray":
        return self.rlc_partition_verify_async(
            messages, signatures, member_keys, groups, dst, rng
        )()

    def rlc_partition_verify_async(
        self,
        messages: Sequence[bytes],
        signatures: Sequence["A.Signature"],
        member_keys: Sequence[Sequence["A.PublicKey"]],
        groups: int,
        dst: bytes = constants.DST_SIGNATURE,
        rng=secrets,
    ):
        """Per-sub-batch verdicts for fault localization: the batch's
        bucket splits into `groups` contiguous groups and ONE device pass
        (rlc_partition_verify_kernel) reports a bool per group — the seam
        runtime/isolation.py descends through after a failed batch, so
        the host never single-verifies more than the named-bad leaves.
        Items keep the firehose shape (one signature over an aggregate of
        member keys); committees collapse to one key by host aggregation
        (only paid on already-failed batches). Items with no keys or an
        identity key are named bad on the host and their slots stay
        padding, so they cannot poison their group's device verdict.
        Returns a zero-arg settle producing a (groups,) bool array
        (padding-only groups True)."""
        n = len(messages)
        g = _bucket(groups, lo=4)
        if not (n and n == len(signatures) == len(member_keys)):
            return lambda: np.zeros((0,), bool)
        b = _bucket(n)
        if g > b:
            g = b
        with self._stage("host_prep", op="pack_partition", items=n):
            bad_host = np.zeros((b,), bool)
            agg_pts = []
            slots = []
            for i, ks in enumerate(member_keys):
                if not ks or any(pk.point.is_infinity() for pk in ks):
                    bad_host[i] = True
                    continue
                key = ks[0] if len(ks) == 1 else A.PublicKey.aggregate(ks)
                agg_pts.append(key.point)
                slots.append(i)
            pk_x = np.zeros((b, L.NLIMBS), np.int32)
            pk_y = np.zeros((b, L.NLIMBS), np.int32)
            pk_inf = np.ones((b,), bool)
            sig_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            sig_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            sig_inf = np.ones((b,), bool)
            msg_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((b,), bool)
            if agg_pts:
                g1x, g1y, g1inf = C.g1_points_to_dev(agg_pts)
                g2x, g2y, g2inf = C.g2_points_to_dev(
                    [signatures[i].point for i in slots]
                )
                pk_x[slots], pk_y[slots], pk_inf[slots] = g1x, g1y, g1inf
                sig_x[slots], sig_y[slots], sig_inf[slots] = g2x, g2y, g2inf
                for i in slots:
                    x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                    msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            pairs = [self._rlc_pair(rng) for _ in range(n)]
            r_bits = rlc_bits_host(pairs, b)
            group_tag = np.zeros((g,), np.int32)
        args = self._upload((
            pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
            msg_x, msg_y, msg_inf, r_bits, group_tag,
        ), kernel="rlc_partition")
        fn = self._jitted_msm(
            "rlc_partition", rlc_partition_verify_kernel,
            donate=self._donate(len(args)),
            check_subgroup=int(self.fuse_subgroup),
        )
        dev_out = self._run_kernel(
            "rlc_partition", fn, args, sigs=n, block=False
        )
        span = b // g

        def settle() -> "np.ndarray":
            if self._observed():
                with self._stage("execute", kernel="rlc_partition"):
                    self._block(dev_out)
            verdicts = np.array(np.asarray(dev_out), bool)
            for i in np.nonzero(bad_host)[0]:
                verdicts[i // span] = False
            return verdicts

        return settle

    # -- signing -----------------------------------------------------------

    def batch_sign(
        self,
        messages: Sequence[bytes],
        secret_keys: Sequence["A.SecretKey"],
        dst: bytes = constants.DST_SIGNATURE,
    ) -> "list[A.Signature]":
        """N signatures on device (signer/src/signer.rs:173-229 equivalent)."""
        n = len(messages)
        assert n == len(secret_keys)
        if n == 0:
            return []
        if n > MAX_BUCKET:
            out: list = []
            for i in range(0, n, MAX_BUCKET):
                out.extend(
                    self.batch_sign(
                        messages[i : i + MAX_BUCKET],
                        secret_keys[i : i + MAX_BUCKET],
                        dst,
                    )
                )
            return out
        with self._stage("host_prep", op="pack_sign", items=n):
            b = _bucket(n)
            msg_x = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_y = np.zeros((b, 2, L.NLIMBS), np.int32)
            msg_inf = np.ones((b,), bool)
            for i in range(n):
                x, y, inf = self._hash_to_g2_dev(messages[i], dst)
                msg_x[i], msg_y[i], msg_inf[i] = x, y, inf
            sk_bits, sk_neg = sign_bits_host(
                [sk.scalar for sk in secret_keys], b
            )
        fn = self._jitted("batch_sign", batch_sign_kernel)
        args = self._upload(
            (msg_x, msg_y, msg_inf, sk_bits, sk_neg), kernel="batch_sign"
        )
        X, Y, Z = self._run_kernel("batch_sign", fn, args, sigs=n)
        with self._stage("readback", kernel="batch_sign"):
            X, Y, Z = np.asarray(X), np.asarray(Y), np.asarray(Z)
        return [A.Signature(C.dev_to_g2_point(X[i], Y[i], Z[i])) for i in range(n)]

    @staticmethod
    def _rlc_pair(rng) -> "tuple[int, int]":
        """A nonzero (r0, r1) 32-bit RLC pair (see rlc_bits_host)."""
        a, b = 0, 0
        while a == 0 and b == 0:
            a, b = rng.randbits(32), rng.randbits(32)
        return a, b


__all__ = [
    "TpuBlsBackend",
    "rlc_bits_host",
    "sign_bits_host",
    "pick_msm_window",
    "msm_tune_path",
    "load_msm_tuning",
    "set_msm_tuning",
    "multi_verify_kernel",
    "rlc_partition_verify_kernel",
    "multi_verify_msm_kernel",
    "multi_verify_msm_idx_kernel",
    "multi_verify_msm_comp_kernel",
    "aggregate_fast_verify_msm_comp_kernel",
    "aggregate_fast_verify_msm_idx_comp_kernel",
    "g1_decompress_kernel",
    "g1_decompress_rows",
    "g2_aggregate_kernel",
    "g1_aggregate_kernel",
    "g2_aggregate_groups",
    "g1_aggregate_groups",
    "grouped_multi_verify_msm_kernel",
    "aggregate_fast_verify_msm_kernel",
    "aggregate_fast_verify_msm_idx_kernel",
    "batch_sign_kernel",
    "make_sharded_multi_verify",
    "make_sharded_multi_verify_msm",
    "sharded_multi_verify",
    "sharded_multi_verify_msm",
    "sharded_msm_plans",
    "note_dispatch_shapes",
    "dispatch_scope",
    "declare_warmup_complete",
    "warmup_declared",
    "post_warmup_recompiles",
    "reset_shape_tracking",
]
