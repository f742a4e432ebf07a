"""Batched Ed25519 verification on device — the curve25519 entry of the
scheme dispatch table (tpu/schemes.py).

Field plane: the limbs.py representation instantiated for p = 2²⁵⁵−19 —
limb-major relaxed signed 15-bit digits, int32, Montgomery form with
R = 2²⁷⁰ (18 limbs). 17 limbs would cover 255 bits exactly but leaves
ZERO headroom between p and R: the |value| < 20p working bound that
makes the relaxation round's dropped carry provably zero needs value
room above p, and R·p must dominate the 400p² Montgomery product bound
(2²⁷⁰·p ≈ 2⁵²⁵ vs 400p² ≈ 2⁵¹⁹ — the 18th limb is the safety margin,
exactly like 26 limbs over the 381-bit BLS field). The per-site
digit-product/accumulator/operand bounds of this plane are
machine-checked alongside the BLS plane — with LIMB_BITS/NLIMBS parsed
from this file's source — and certified into tools/ranges/bounds.txt
(`python -m tools.ranges --write-cert`); p/R = 2⁻¹⁵ here, so every
Montgomery product contracts the value hull far harder than on the
BLS plane. All structural
choices (leading limb axis, tuple-carry CIOS scan, one relaxation round
per add) are limbs.py's, re-derived here for the smaller field; see
that module's docstring for the measurements behind them.

Curve plane: twisted Edwards a = −1 in extended coordinates with the
strongly-unified add-2008-hwcd-3 formula — COMPLETE for a = −1 on
points with correct T, so one formula serves add and double, identity
needs no special case, and padding slots are plain (0, 1) identity
points with zero scalars (algebraically neutral, branch-free).

Verification is the cofactored RFC 8032 batch equation under a random
linear combination. Host prep draws 128-bit z_i, folds the S_i into one
base-point scalar c_B = Σ z_i·S_i mod L, and pre-negates R_i and A_i,
so the device evaluates ONE multi-scalar multiplication

    T = [c_B]B + Σ [z_i](−R_i) + Σ [z_i·k_i mod L](−A_i)

as a batched 253-bit MSB ladder + a log-depth sum tree, then clears the
cofactor with three unified doublings ([8]T) and runs the fused
identity test (X ≡ 0 ∧ Y ≡ Z). Reducing z_i·k_i mod L is sound ONLY
because the ×8 follows the sum: L·A_i is 8-torsion for any decoded
point, and the final ×8 kills it — the same reason the host twin
(crypto/ed25519.py) must be cofactored for verdicts to match
bit-for-bit. All verdict-relevant decode checks (canonical y, S < L
malleability bound) run on host in `prepare`, identically to the twin.

Kernel registration rides the BLS plane's global jit cache +
shape-ledger (`_jitted_global` / `note_dispatch_shapes` in tpu/bls.py),
so persistent-cache behavior and the zero-post-warmup-recompile
invariant cover this scheme with no new machinery.
"""

from __future__ import annotations

import secrets

import numpy as np
import jax.numpy as jnp
from jax import lax

from grandine_tpu.crypto import ed25519 as HE
from grandine_tpu.tracing import NULL_TRACER

LIMB_BITS = 15
NLIMBS = 18
MASK = (1 << LIMB_BITS) - 1
P = HE.P
R_MONT = 1 << (LIMB_BITS * NLIMBS)  # 2^270
R_INV = pow(R_MONT, -1, P)
N0_INV = (-pow(P, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS)
#: ladder bit width: every RLC scalar is < 2^253 (c_B and z·k are
#: reduced mod L < 2^253; the z_i are 128-bit)
NBITS = 253

_DT = jnp.int32


# --- host-side conversions -------------------------------------------------


def int_to_limbs(v: int) -> np.ndarray:
    assert 0 <= v < R_MONT
    return np.array(
        [(v >> (LIMB_BITS * i)) & MASK for i in range(NLIMBS)], dtype=np.int32
    )


def limbs_to_int(a) -> int:
    a = np.asarray(a)
    return sum(int(a[..., i]) << (LIMB_BITS * i) for i in range(NLIMBS))


def to_mont(v: int) -> np.ndarray:
    return int_to_limbs(v * R_MONT % P)


def from_mont(a) -> int:
    return limbs_to_int(a) * R_INV % P


P_LIMBS = int_to_limbs(P)
ONE_MONT = to_mont(1)
R_MOD_P = int_to_limbs(R_MONT % P)
EIGHT_P = int_to_limbs(8 * P)
_KP_PATTERNS = np.stack([int_to_limbs(k * P) for k in range(16)])  # (16, 18)

P_DIGITS = [int(x) for x in P_LIMBS]
R_MOD_P_DIGITS = [int(x) for x in R_MOD_P]
ONE_MONT_DIGITS = [int(x) for x in ONE_MONT]
EIGHT_P_DIGITS = [int(x) for x in EIGHT_P]
#: 2d in Montgomery form (the unified-add constant)
K2D_DIGITS = [int(x) for x in to_mont(2 * HE.D % P)]


def ints_to_mont_limbs(values) -> np.ndarray:
    """[v_0, …] → (N, 18) int32 Montgomery digit arrays, vectorized
    (curve.ints_to_mont_limbs re-derived for the 25519 field)."""
    n = len(values)
    if n == 0:
        return np.zeros((0, NLIMBS), np.int32)
    nb = (LIMB_BITS * NLIMBS + 7) // 8  # 34 bytes for 270 bits
    buf = bytearray(n * nb)
    for i, v in enumerate(values):
        buf[i * nb : (i + 1) * nb] = (v * R_MONT % P).to_bytes(nb, "little")
    raw = np.frombuffer(bytes(buf), np.uint8).reshape(n, nb)
    bits = np.unpackbits(raw, axis=1, bitorder="little")
    bits = bits[:, : NLIMBS * LIMB_BITS].reshape(n, NLIMBS, LIMB_BITS)
    weights = (1 << np.arange(LIMB_BITS, dtype=np.int64)).astype(np.int32)
    return (bits.astype(np.int32) * weights).sum(axis=2).astype(np.int32)


# --- structure helpers (device fp = (18, *batch) int32) --------------------


def split(arr) -> jnp.ndarray:
    """REST (…, 18) → device (18, …)."""
    return jnp.moveaxis(jnp.asarray(arr), -1, 0)


def merge(fp) -> jnp.ndarray:
    return jnp.moveaxis(fp, 0, -1)


def const_fp(digits, shape=()) -> jnp.ndarray:
    d = jnp.asarray(np.asarray(digits, dtype=np.int32))
    return jnp.broadcast_to(
        d.reshape((NLIMBS,) + (1,) * len(shape)), (NLIMBS,) + tuple(shape)
    )


def select(cond, a, b) -> jnp.ndarray:
    return jnp.where(cond[None], a, b)


# --- flat primitives (limbs.py technique at 18 limbs) ----------------------


def relax(s) -> jnp.ndarray:
    """One carry-relaxation round, exactly value-preserving; the top
    digit stays unsplit (signed) — |value| < 20p keeps it ≲ 2⁵."""
    hi = s[: NLIMBS - 1] >> LIMB_BITS
    lo = s[: NLIMBS - 1] & MASK
    top = s[NLIMBS - 1 :] + hi[NLIMBS - 2 :]
    shifted = jnp.concatenate([jnp.zeros_like(hi[:1]), hi[: NLIMBS - 2]], 0)
    return jnp.concatenate([lo + shifted, top], axis=0)


def add_mod(a, b) -> jnp.ndarray:
    return relax(a + b)


def sub_mod(a, b) -> jnp.ndarray:
    return relax(a - b)


def double_mod(a) -> jnp.ndarray:
    return relax(a + a)


def montmul(a, b) -> jnp.ndarray:
    """Montgomery product a·b·R⁻¹ mod p: CIOS over signed digits (see
    limbs.montmul — same scan, 19 column accumulators). For |a|,|b| <
    20p, |a·b| < 400p² < R·p, so the reduced value lies in (−0.1p, 2p)
    and the relaxed output digits are bounded."""
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

    t, _ = lax.scan(step, t0, a)
    # fold the 19th column (weight 2^270 = R) back in via R mod p, relax
    main = jnp.stack(
        [t[j] + t[NLIMBS] * R_MOD_P_DIGITS[j] for j in range(NLIMBS)], 0
    )
    return relax(main)


def canonical_digits(t) -> jnp.ndarray:
    """Full ripple to canonical digits in [0, 2¹⁵) — non-negative values
    < 2²⁷⁰ only; callers offset by +8p first."""

    def step(c, v):
        s = v + c
        return s >> LIMB_BITS, s & MASK

    carry, ys = lax.scan(step, jnp.zeros(t.shape[1:], _DT), t[: NLIMBS - 1])
    return jnp.concatenate([ys, t[NLIMBS - 1 :] + carry[None]], axis=0)


def is_zero_val(a) -> jnp.ndarray:
    """value(a) ≡ 0 (mod p) for |value| < 8p: canonicalize a+8p and
    compare against the digit patterns of k·p, k = 0..15."""
    a = jnp.asarray(a)
    canon = canonical_digits(a + const_fp(EIGHT_P_DIGITS, a.shape[1:]))
    pats = jnp.asarray(np.ascontiguousarray(_KP_PATTERNS.T))  # (18, 16)
    pats = pats.reshape((NLIMBS, 16) + (1,) * (canon.ndim - 1))
    eq = canon[:, None] == pats
    return jnp.any(jnp.all(eq, axis=0), axis=0)


# --- Edwards curve plane ---------------------------------------------------


def ed_add(p, q):
    """Unified add-2008-hwcd-3 (a = −1): complete on correctly-extended
    points — also the doubling. 8 montmuls + the 2d constant mult; every
    montmul input is relaxed (digit-bounded) and value-bounded < 6p."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    k2d = const_fp(K2D_DIGITS, x1.shape[1:])
    a = montmul(sub_mod(y1, x1), sub_mod(y2, x2))
    b = montmul(add_mod(y1, x1), add_mod(y2, x2))
    c = montmul(montmul(t1, k2d), t2)
    d = double_mod(montmul(z1, z2))
    e = sub_mod(b, a)
    f = sub_mod(d, c)
    g = add_mod(d, c)
    h = add_mod(b, a)
    return (montmul(e, f), montmul(g, h), montmul(f, g), montmul(e, h))


def _ladder(px, py, pt, bits_msb):
    """[k_i]P_i for a batch of affine extended points, k as (NBITS, B)
    MSB-first bits. Identity accumulator + complete adds: no started
    flag, zero scalars yield the identity (padding is free)."""
    shape = px.shape[1:]
    one = const_fp(ONE_MONT_DIGITS, shape)
    zero = jnp.zeros_like(px)
    base = (px, py, one, pt)
    acc0 = (zero, one, one, jnp.zeros_like(px))

    def step(acc, bit):
        acc = ed_add(acc, acc)
        added = ed_add(acc, base)
        cond = bit.astype(bool)
        return tuple(
            select(cond, after, before)
            for before, after in zip(acc, added)
        ), None

    acc, _ = lax.scan(step, acc0, bits_msb)
    return acc


def _sum_tree(pts):
    """Reduce the (18, B) point batch to one point: fixed-shape
    masked-roll reduction (curve._tree_reduce_points' trick — one
    compiled body for all log₂B levels)."""
    n = pts[0].shape[1]
    assert n & (n - 1) == 0, "ed25519 sum tree requires a power-of-two batch"
    levels = n.bit_length() - 1
    if levels:

        def body(_, carry):
            y, s = carry
            rolled = tuple(jnp.roll(c, -s, axis=1) for c in y)
            y = ed_add(y, rolled)
            return (y, s // 2)

        (pts, _) = lax.fori_loop(0, levels, body, (pts, jnp.int32(n // 2)))
    return tuple(c[:, 0] for c in pts)


def verify_kernel(px, py, pt, bits):
    """One batched cofactored RLC verdict: px/py/pt (B, 18) REST-format
    Montgomery affine-extended coords, bits (B, 253) MSB-first scalar
    bits. Returns a scalar bool."""
    x, y, t = split(px), split(py), split(pt)
    acc = _ladder(x, y, t, jnp.transpose(jnp.asarray(bits)))
    s = _sum_tree(acc)
    for _ in range(3):  # ×8: clear the cofactor AFTER the RLC sum
        s = ed_add(s, s)
    sx, sy, sz, _st = s
    # identity in extended projective form: X ≡ 0 ∧ Y ≡ Z (mod p)
    zt = jnp.stack([sx, sub_mod(sy, sz)], axis=1)  # (18, 2)
    return jnp.all(is_zero_val(zt))


# --- host-facing backend ---------------------------------------------------


def _ladder_bucket(m: int) -> int:
    """Pow-4 bucket ladder {8, 32, 128}: fewer warm shapes than pow-2
    at the cost of ≤ 4× padding — the ladder is batched, so padding
    costs lanes, not steps."""
    b = 8
    while b < m:
        b *= 4
    return b


class Ed25519Backend:
    """The ed25519 scheme backend (built via schemes.get("ed25519"),
    one per lane). Host prep decodes strictly (canonical y, S < L),
    draws the RLC coefficients, and buckets the MSM batch; the device
    runs one ladder + sum-tree + cofactor-clear + identity-test pass."""

    ASYNC_SEAM = ("verify_batch_async",)
    #: beyond this the 2n+1-point MSM leaves the warmed {8,32,128}
    #: ladder buckets — prepare reports "oversize" and the scheduler
    #: degrades the batch to the host twin (never a new shape mid-slot)
    MAX_ITEMS = 63

    def __init__(self, *, metrics=None, tracer=None, lane: str = "ed25519",
                 mesh=None, rng=None) -> None:
        self.metrics = metrics
        self.tracer = tracer or NULL_TRACER
        self.lane = lane
        #: randbits source for the RLC coefficients (tests inject a
        #: deterministic twin)
        self.rng = rng if rng is not None else secrets

    def _count_kernel(self, kernel: str, sigs: int) -> None:
        if self.metrics is not None:
            self.metrics.device_kernel_calls.labels(kernel).inc()
            if sigs:
                self.metrics.device_kernel_sigs.labels(kernel).inc(sigs)

    def prepare(self, items):
        """(status, payload): "ok" → arrays for verify_batch_async,
        "invalid" → some item can never verify (bad encoding, S ≥ L —
        the batch must FAIL so bisection isolates), "oversize" → degrade
        to the host path."""
        n = len(items)
        if n == 0:
            return "ok", ()
        if n > self.MAX_ITEMS:
            return "oversize", None
        decoded = []
        for it in items:
            keys = it.public_keys
            if keys is None or len(keys) != 1:
                return "invalid", None
            sig = bytes(it.signature)
            if len(sig) != 64:
                return "invalid", None
            pk = bytes(keys[0])
            a_pt = HE.point_decompress(pk)
            r_pt = HE.point_decompress(sig[:32])
            if a_pt is None or r_pt is None:
                return "invalid", None
            s = int.from_bytes(sig[32:], "little")
            if s >= HE.L:  # malleability bound, same rule as the twin
                return "invalid", None
            k = int.from_bytes(
                HE.sha512(sig[:32] + pk + bytes(it.message)), "little"
            ) % HE.L
            decoded.append((a_pt, r_pt, s, k))
        zs = [self.rng.randbits(128) | 1 for _ in range(n)]
        c_b = sum(z * s for z, (_, _, s, _) in zip(zs, decoded)) % HE.L
        # MSM rows: [c_B]B, [z_i](−R_i), [z_i·k_i](−A_i); pads are the
        # identity point with scalar zero
        points = [(HE.BASE[0], HE.BASE[1])]
        scalars = [c_b]
        for z, (_, r_pt, _, _) in zip(zs, decoded):
            points.append(((P - r_pt[0]) % P, r_pt[1]))
            scalars.append(z)
        for z, (a_pt, _, _, k) in zip(zs, decoded):
            points.append(((P - a_pt[0]) % P, a_pt[1]))
            scalars.append(z * k % HE.L)
        bm = _ladder_bucket(len(points))
        while len(points) < bm:
            points.append((0, 1))
            scalars.append(0)
        xs = [x for x, _ in points]
        ys = [y for _, y in points]
        ts = [x * y % P for x, y in points]
        limbs = ints_to_mont_limbs(xs + ys + ts)
        px, py, pt = limbs[:bm], limbs[bm : 2 * bm], limbs[2 * bm :]
        from grandine_tpu.tpu import curve as C

        bits = C.scalars_to_bits_msb(scalars, NBITS)
        return "ok", (px, py, pt, bits, n)

    def verify_batch_async(self, prep):
        """Dispatch the prepared batch; returns the zero-arg settle
        (forces the device verdict)."""
        if not prep:
            return lambda: True
        px, py, pt, bits, n = prep
        from grandine_tpu.tpu import bls as B

        fn = B._jitted_global("ed25519_verify", verify_kernel)
        args = (px, py, pt, bits)
        scope = B.dispatch_scope("ed25519_verify", args, self.metrics)
        self._count_kernel("ed25519_verify", n)
        with scope, self.tracer.span(
            "device_dispatch", {"kernel": "ed25519_verify", "lane": self.lane}
        ):
            with B._node_profiler().annotate("ed25519_verify", n):
                out = fn(*args)

        def settle() -> bool:
            return bool(np.asarray(out))

        return settle


__all__ = [
    "Ed25519Backend",
    "NBITS",
    "NLIMBS",
    "ed_add",
    "verify_kernel",
    "to_mont",
    "from_mont",
    "ints_to_mont_limbs",
    "montmul",
    "is_zero_val",
]
