"""Limb-decomposed Montgomery arithmetic for Fp (BLS12-381 base field) on TPU.

Representation ("relaxed signed digits", limb-major form): an Fp element is
ONE int32 array of shape (26, *batch) — little-endian 15-bit digits along the
LEADING axis, Montgomery form (value·R mod p, R = 2³⁹⁰). Digits are redundant
and signed: |digit| ≤ LMAX = 2¹⁵ + 256; values are only canonical modulo p at
explicit canonicalization points (equality tests, host export).

Why the limb axis is LEADING (three designs were measured on v5e, by a
microbenchmark that predates PERF_LEDGER.jsonl and is gone):
  - Trailing limb axis (batch, 26): the minor axis maps to the 128 vector
    lanes, so 26/128 lanes do work AND every shifted-column accumulation in
    the Montgomery product is a cross-lane concatenate (a relayout of the
    whole tensor): ~47 ns/montmul/element.
  - One array per limb (pytree of 26 arrays): montmul becomes pure
    elementwise code at full lane occupancy (~12 ns/element), but every
    cheap op (add, select) costs ~100 HLO instructions, and an XLA
    optimization pass that is quadratic in computation size pushes compiles
    of real kernels into minutes (and tens of GB of compiler memory).
  - Limb-major array (26, *batch) — this file: adds/selects are single HLO
    ops (the batch owns the minor axes: full lanes), the carry-relaxation
    shift moves whole batch planes along the major axis (a cheap copy, no
    lane shuffles), and montmul internally scans over the leading limb axis
    with its column accumulators as a 27-tuple carry that lives in VMEM —
    keeping the ~12 ns/element speed with ~30 flat ops per call site.
A narrow batch puts the limbs back on the lanes: under 32 wide XLA lays a
(26, n) array out `{0,1}` (the 26 limbs along the 128 lanes, the batch
on sublanes), which is the first design again, and the same 63-step Miller
loop cost 38.16 ms a call at width 1 against 13.59 at width 64
(PERF_LEDGER.jsonl, PR 32, `breakdown.device_ops`). So no loop of the verify
call carries a batch under LANE_FLOOR: `widen_lanes` pads it with copies and
the result is read from the lanes that were there.

Why 15-bit signed digits:
  - products of two digits: ≤ LMAX² < 2³¹ — exact in int32;
  - CIOS column accumulators stay |·| < 2²² — no wide accumulator needed;
  - add/sub/neg are a plain elementwise op plus ONE flat carry-relaxation
    round (arithmetic shift + mask): no borrow ripples, no conditional
    subtracts. Signed digits are what make subtraction free.
  - value bounds are machine-checked: tools/ranges abstract-interprets
    every kernel call site and certifies the per-site digit-product,
    accumulator, and operand-value bounds into tools/ranges/bounds.txt
    (regenerate with `python -m tools.ranges --write-cert`). The int32
    bounds above hold unconditionally at every site. The |v| < 20p
    montmul working bound is proven per-site on the Fp/G1 paths;
    through Fp2 Karatsuba chains the worst-case interval hull exceeds
    it (each product's m·p/R term is [0, p) and independent in the
    abstraction — see the annotated sites in field.py), which is why
    the 20p figure is a working envelope, not a blanket invariant.
    Montgomery products land in (−0.1p, 2p) (see montmul docstring),
    which keeps the dropped top carry of the relaxation round provably
    zero.

Reference counterpart: the blst field arithmetic behind
bls/src/signature.rs:96-129 (multi_verify) — re-designed here for a vector
unit instead of 64-bit scalar pipelines.
"""

from __future__ import annotations

import os

import numpy as np
import jax.numpy as jnp
from jax import lax

from grandine_tpu.crypto.constants import P

#: lax.scan unroll factor for the CIOS loop. unroll=1 measured fastest on
#: v5e with honest (host-fetch) timing; kept as an env knob for experiments.
MONTMUL_UNROLL = int(os.environ.get("GT_MONTMUL_UNROLL", "1"))

#: Below this static batch size the CIOS loop would be FULLY unrolled:
#: narrow-width products (final exponentiation at width ≤54) are
#: latency-bound on the 26-iteration inner scan. Disabled by default (0):
#: measured on a v5e in round 4, the unrolled bodies push XLA compile
#: past 10 minutes while the no-inversion final exp (pairing.py
#: final_exp_is_one) already removes most narrow-width latency. Kept as an
#: experiment knob.
MONTMUL_UNROLL_NUMEL = int(os.environ.get("GT_MONTMUL_UNROLL_NUMEL", "0"))

LIMB_BITS = 15
NLIMBS = 26
MASK = (1 << LIMB_BITS) - 1
LMAX = (1 << LIMB_BITS) + 256  # relaxed digit bound (see module docstring)
R_MONT = 1 << (LIMB_BITS * NLIMBS)  # 2^390
R_INV = pow(R_MONT, -1, P)
R2 = R_MONT * R_MONT % P
N0_INV = (-pow(P, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)

_DT = jnp.int32

#: The narrowest trailing batch axis that XLA's TPU compiler lays out with
#: the batch on the 128 lanes: a loop's (26, n) carries are `{0,1}` (limbs
#: on the lanes, module docstring) at n = 1, 2, 8, 16 and `{1,0:T(8,128)}`
#: from 32 up (tests/test_tpu_compile.py holds it). One v5e, 20 calls each
#: (PERF.md section 6, PR 33): the 63-step Miller loop 36.7 ms at 1 lane,
#: 23.0 at 16, 11.5 at 32, 10.5 at 64; the final exponentiation's hard
#: part 23.6 at 2, 28.4 at 16, 18.3 at 32, 13.6 at 64. The chip would
#: take 64 (4.7 ms a call more); the CPU, where the tests run and a lane
#: costs its share of the time, triples a 4-item call's time there and
#: doubles it at 32 (PERF.md section 7): 32 until the rehearsals' windows
#: have room for it.
LANE_FLOOR = 32


# --- host-side conversions -------------------------------------------------


def int_to_limbs(v: int) -> np.ndarray:
    """Canonical (non-Montgomery) digit decomposition, (26,) int32."""
    assert 0 <= v < R_MONT
    return np.array(
        [(v >> (LIMB_BITS * i)) & MASK for i in range(NLIMBS)], dtype=np.int32
    )


def limbs_to_int(a) -> int:
    """(…, 26) trailing-limb REST-format array → int."""
    a = np.asarray(a)
    return sum(int(a[..., i]) << (LIMB_BITS * i) for i in range(NLIMBS))


def to_mont(v: int) -> np.ndarray:
    return int_to_limbs(v * R_MONT % P)


def from_mont(a) -> int:
    """Host conversion out of Montgomery form (REST format — trailing limb
    axis; handles redundant/signed digits via exact integer arithmetic)."""
    return limbs_to_int(a) * R_INV % P


P_LIMBS = int_to_limbs(P)
ZERO = np.zeros(NLIMBS, dtype=np.int32)
ONE_MONT = to_mont(1)
# R mod p as digits — folds the 27th result column of montmul back in.
R_MOD_P = int_to_limbs(R_MONT % P)
EIGHT_P = int_to_limbs(8 * P)
# canonical digit patterns of k·p, k = 0..15 (for is_zero after a +8p offset)
_KP_PATTERNS = np.stack([int_to_limbs(k * P) for k in range(16)])  # (16, 26)

# Python-int digit views for use as broadcast scalars in compute code.
P_DIGITS = [int(x) for x in P_LIMBS]
R_MOD_P_DIGITS = [int(x) for x in R_MOD_P]
ONE_MONT_DIGITS = [int(x) for x in ONE_MONT]
EIGHT_P_DIGITS = [int(x) for x in EIGHT_P]


# --- structure helpers -----------------------------------------------------
#
# Device Fp = (26, *batch) int32. REST format (host buffers, kernel
# boundaries) keeps the limb axis TRAILING (…, 26) — layout-agnostic and
# cheap to assemble on host; `split`/`merge` move between the two (one
# transpose, fused by XLA into adjacent compute).


def split(arr) -> jnp.ndarray:
    """REST (…, 26) → device (26, …)."""
    return jnp.moveaxis(jnp.asarray(arr), -1, 0)


def merge(fp) -> jnp.ndarray:
    """Device (26, …) → REST (…, 26)."""
    return jnp.moveaxis(fp, 0, -1)


def merge_np(fp) -> np.ndarray:
    return np.moveaxis(np.asarray(fp), 0, -1)


def const_fp(digits, shape=()) -> jnp.ndarray:
    """Digit vector (length 26, host ints) → (26, *shape) constant."""
    d = jnp.asarray(np.asarray(digits, dtype=np.int32))
    return jnp.broadcast_to(
        d.reshape((NLIMBS,) + (1,) * len(shape)), (NLIMBS,) + tuple(shape)
    )


def zeros_fp(shape=()) -> jnp.ndarray:
    return jnp.zeros((NLIMBS,) + tuple(shape), _DT)


def stack_fp(elems, axis: int = 1) -> jnp.ndarray:
    """Stack K independent Fp elements along a new batch axis (default:
    right after the limb axis)."""
    return jnp.stack(list(elems), axis=axis)


def unstack_fp(fp, k: int, axis: int = 1) -> list:
    return [jnp.take(fp, i, axis=axis) for i in range(k)]


def concat_fp(elems, axis: int = 1) -> jnp.ndarray:
    """Concatenate Fp elements along an existing batch axis."""
    return jnp.concatenate(list(elems), axis=axis)


def widen_lanes(fp) -> jnp.ndarray:
    """Pad the TRAILING batch axis up to LANE_FLOOR with copies of its last
    lane, so that a loop over the value runs with the batch on the lanes.
    A batch already that wide comes back as it is (the width is static
    under jit). Callers read their result from the lanes they brought."""
    assert fp.ndim >= 2, "widen_lanes pads a batch axis, not the limb axis"
    n = fp.shape[-1]
    if n >= LANE_FLOOR:
        return fp
    fill = jnp.broadcast_to(fp[..., -1:], fp.shape[:-1] + (LANE_FLOOR - n,))
    return jnp.concatenate([fp, fill], axis=-1)


def index_fp(fp, idx) -> jnp.ndarray:
    """Index the leading batch axis (device axis 1)."""
    return fp[:, idx]


def batch_shape(fp):
    return fp.shape[1:]


# --- flat primitives -------------------------------------------------------


def relax(s) -> jnp.ndarray:
    """One carry-relaxation round, exactly value-preserving: digits 0..24 go
    to [0,2¹⁵) + a signed carry into the next digit; the TOP digit is left
    unsplit (signed). Under the |value| < 20p invariant the top digit stays
    |·| ≲ 2¹¹, so products involving it remain far below int32 overflow.
    The carry shift moves batch planes along the major axis — no lane
    shuffles."""
    hi = s[: NLIMBS - 1] >> LIMB_BITS
    lo = s[: NLIMBS - 1] & MASK
    top = s[NLIMBS - 1 :] + hi[NLIMBS - 2 :]
    shifted = jnp.concatenate([jnp.zeros_like(hi[:1]), hi[: NLIMBS - 2]], 0)
    return jnp.concatenate([lo + shifted, top], axis=0)


def add_mod(a, b) -> jnp.ndarray:
    return relax(a + b)


def sub_mod(a, b) -> jnp.ndarray:
    return relax(a - b)


def neg_mod(a) -> jnp.ndarray:
    return relax(-a)


def double_mod(a) -> jnp.ndarray:
    return relax(a + a)


def montmul(a, b) -> jnp.ndarray:
    """Montgomery product a·b·R⁻¹ mod p: CIOS over signed digits, scanned
    over the 26 limb rows of `a` with the 27 column accumulators as a tuple
    carry (they live in VMEM — see module docstring).

    Value bound: for |a|,|b| < 20p, |a·b| < 400p² ≲ R·p, so the reduced value
    lies in (-0.1p, 2p) and the relaxed output digits are ≤ LMAX. Inputs are
    digit-bounded by LMAX (products < 2³¹) and value-bounded by callers.
    """
    shape = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    a = jnp.broadcast_to(a, (NLIMBS,) + shape).astype(_DT)
    b = jnp.broadcast_to(b, (NLIMBS,) + shape).astype(_DT)
    bl = [b[j] for j in range(NLIMBS)]
    t0 = tuple(jnp.zeros(shape, _DT) for _ in range(NLIMBS + 1))

    def step(t, ai):
        t = list(t)
        for j in range(NLIMBS):
            prod = ai * bl[j]  # |·| < 2^31 exact
            t[j] = t[j] + (prod & MASK)
            t[j + 1] = t[j + 1] + (prod >> LIMB_BITS)
        m = (t[0] * N0_INV) & MASK
        for j in range(NLIMBS):
            prod2 = m * P_DIGITS[j]
            t[j] = t[j] + (prod2 & MASK)
            t[j + 1] = t[j + 1] + (prod2 >> LIMB_BITS)
        carry = t[0] >> LIMB_BITS  # exact: t[0] ≡ 0 mod 2^15
        t = t[1:] + [jnp.zeros(shape, _DT)]
        t[0] = t[0] + carry
        return tuple(t), None

    numel = 1
    for d in shape:
        numel *= int(d)
    unroll = NLIMBS if numel <= MONTMUL_UNROLL_NUMEL else MONTMUL_UNROLL
    t, _ = lax.scan(step, t0, a, unroll=unroll)
    # fold the 27th column (weight 2^390 = R) back in via R mod p, relax
    main = jnp.stack(
        [t[j] + t[NLIMBS] * R_MOD_P_DIGITS[j] for j in range(NLIMBS)], 0
    )
    return relax(main)


def montsq(a) -> jnp.ndarray:
    return montmul(a, a)


# --- packed transfer format -------------------------------------------------
#
# Canonical Fp values reach the device as 13 little-endian uint32 words
# (the 13th is always zero padding) — 52 bytes instead of the 104-byte
# int32 limb form; the compressed-ingest path (curve.py
# _bytes_to_canonical) builds them from 48-byte wire payloads. The device
# unpacks to 15-bit limbs with static shifts/gathers and one montmul by
# R² lifts the batch into Montgomery form.

_UNPACK_J = np.array([(15 * i) >> 5 for i in range(NLIMBS)], np.int32)
_UNPACK_OFF = np.array([(15 * i) & 31 for i in range(NLIMBS)], np.int32)
R2_DIGITS = [int(x) for x in int_to_limbs(R2)]


def unpack_words(w) -> jnp.ndarray:
    """(…, 13) uint32 REST words → canonical device limbs (26, …) int32
    (NON-Montgomery; multiply by R² via montmul to enter the field)."""
    w = jnp.asarray(w, jnp.uint32)
    j = jnp.asarray(_UNPACK_J)
    off = jnp.asarray(_UNPACK_OFF.astype(np.uint32))
    lo = jnp.take(w, j, axis=-1) >> off  # (…, 26)
    hi_src = jnp.take(w, j + 1, axis=-1)
    hi = jnp.where(off == 0, jnp.uint32(0), hi_src << (32 - off))
    limbs = ((lo | hi) & jnp.uint32(MASK)).astype(_DT)
    return jnp.moveaxis(limbs, -1, 0)


def to_mont_dev(x_canonical) -> jnp.ndarray:
    """Canonical device limbs → Montgomery form (one fused montmul)."""
    r2 = const_fp(R2_DIGITS, x_canonical.shape[1:])
    return montmul(x_canonical, r2)


def pow_fixed(a, exponent: int) -> jnp.ndarray:
    """a^e for a host-known exponent (LSB-first square-and-multiply scan)."""
    nbits = max(exponent.bit_length(), 1)
    bits = np.array([(exponent >> i) & 1 for i in range(nbits)], dtype=np.int32)
    one = const_fp(ONE_MONT_DIGITS, a.shape[1:])
    a = a.astype(_DT)

    def step(carry, bit):
        result, base = carry
        taken = montmul(result, base)
        result = jnp.where(bit.astype(bool), taken, result)
        base = montsq(base)
        return (result, base), None

    (result, _), _ = lax.scan(step, (one, a), jnp.asarray(bits))
    return result


def inv_mod(a) -> jnp.ndarray:
    """a⁻¹ via Fermat (Montgomery in/out). inv(0) = 0."""
    return pow_fixed(a, P - 2)


# --- canonicalization & predicates ----------------------------------------


def canonical_digits(t) -> jnp.ndarray:
    """Full ripple to canonical digits in [0, 2¹⁵). Only correct for
    non-negative values < 2³⁹⁰ — callers offset by +8p first. lax.scan over
    the limb axis (sequential carry chain — off the hot path)."""

    def step(c, v):
        s = v + c
        return s >> LIMB_BITS, s & MASK

    carry, ys = lax.scan(step, jnp.zeros(t.shape[1:], _DT), t[: NLIMBS - 1])
    return jnp.concatenate([ys, t[NLIMBS - 1 :] + carry[None]], axis=0)


def is_zero_val(a) -> jnp.ndarray:
    """value(a) ≡ 0 (mod p), for |value| < 8p (the widest bound any caller
    reaches — mixed-add Z outputs are < 6p): canonicalize a+8p and compare
    against the digit patterns of k·p, k = 0..15. Returns a bool array of
    the batch shape."""
    a = jnp.asarray(a)
    canon = canonical_digits(a + const_fp(EIGHT_P_DIGITS, a.shape[1:]))
    pats = jnp.asarray(np.ascontiguousarray(_KP_PATTERNS.T))  # (26, 16)
    pats = pats.reshape((NLIMBS, 16) + (1,) * (canon.ndim - 1))
    eq = canon[:, None] == pats  # (26, 16, *batch)
    return jnp.any(jnp.all(eq, axis=0), axis=0)


def is_zero_val_many(elems) -> list:
    """Zero tests for K same-shape elements in ONE canonicalization pass
    (canonical_digits is a 25-step sequential scan — the dominant latency of
    a zero test at narrow widths; stacking amortizes it)."""
    stacked = stack_fp(list(elems))  # (26, K, *batch)
    z = is_zero_val(stacked)  # (K, *batch)
    return [z[i] for i in range(len(elems))]


def is_one_mont(a) -> jnp.ndarray:
    """value(a) ≡ 1·R (mod p) — same bound discipline as is_zero_val."""
    a = jnp.asarray(a)
    return is_zero_val(a - const_fp(ONE_MONT_DIGITS, a.shape[1:]))


def select(cond, a, b) -> jnp.ndarray:
    """cond ? a : b, with cond of the batch shape (broadcast over limbs)."""
    return jnp.where(cond[None], a, b)
