"""Batched optimal ate pairing on device, in limb-list form.

Differences from the anchor (crypto/pairing.py), all validated differentially:
  - G2 loop point T is homogeneous projective on the twist (no inversions);
    lines are evaluated via the D-twist untwist structure, landing in the
    sparse Fp12 subspace spanned by {1, w³, w⁵} over Fp2.
  - Each line is freely scaled by Fp2/Fp factors (killed by the final
    exponentiation), which lets the G1 point stay Jacobian — no batch
    inversion anywhere.
  - Line factors multiply in SPARSELY (`mul_by_line`, 14 Fp2 products vs 18
    for a full Fp12 Karatsuba) and loop squarings use the complex-squaring
    shape (`fp12_sq_fast`, 12 Fp2 products) — in both cases every Fp2
    product of the operation runs in ONE fused montmul call.
  - The final exponentiation easy part uses conjugate/Frobenius; the hard
    part uses the x-chain (x-1)²(x+p)(x²+p²-1)+3 = 3·(p⁴-p²+1)/r, i.e. the
    device computes FE(f)³ — equivalent for pairing-product checks since
    gcd(3, r) = 1, and differentially tested as anchor_FE(f)**3.
  - The Miller loop is ONE lax.scan over the bit pattern of |x|, the 5
    add steps gated by lax.cond — a single compiled body with no wasted
    add work (see miller_loop).

Batch semantics: all inputs carry a batch shape on every limb array;
infinity inputs yield f = 1 (neutral in the product), matching anchor
miller_loop.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from grandine_tpu.crypto.constants import X
from grandine_tpu.tpu import field as F
from grandine_tpu.tpu import limbs as L

# |x| = 2^63 + 2^62 + 2^60 + 2^57 + 2^48 + 2^16; MSB handled by T = Q.
_ABS_X = abs(X)
_BITS_AFTER_MSB = [(_ABS_X >> i) & 1 for i in range(62, -1, -1)]
# segment structure: (n_doubles_before_this_add) per add bit, plus tail doubles
_SEGMENTS: "list[int]" = []
_run = 0
for _b in _BITS_AFTER_MSB:
    _run += 1
    if _b:
        _SEGMENTS.append(_run)
        _run = 0
_TAIL_DOUBLES = _run
assert len(_SEGMENTS) == 5 and _TAIL_DOUBLES == 16


_fp2_many = F.fp2_pair_products


def mul_by_line(f, line):
    """f · (a + b·w³ + c·w⁵), sparse: 14 Fp2 products in one montmul call.

    With ℓ = (ℓ0, ℓ1) = ((a,0,0), (0,b,c)) over Fp6 and w² = v:
      c0 = f0·ℓ0 + v·(f1·ℓ1),  c1 = (f0+f1)·(ℓ0+ℓ1) − f0·ℓ0 − f1·ℓ1.
    f0·ℓ0 is a v-degree-0 scale (3 products); f1·ℓ1 is a 2-sparse Fp6
    product (5 with one Karatsuba share); (f0+f1)(a,b,c) is a full Fp6
    product (6, Karatsuba hybrid).
    """
    a, b, c = line
    f0, f1 = f
    g0, g1, g2 = f0
    h0, h1, h2 = f1
    s0, s1, s2 = (F.fp2_add(x, y) for x, y in zip(f0, f1))
    bc = F.fp2_add(b, c)
    ab = a  # ℓ0+ℓ1 = (a, b, c)
    # Karatsuba pre-sums for t2 = (s0,s1,s2)·(a,b,c)
    s12 = F.fp2_add(s1, s2)
    s01 = F.fp2_add(s0, s1)
    s02 = F.fp2_add(s0, s2)
    prods = _fp2_many([
        (g0, a), (g1, a), (g2, a),                    # t0 = f0·ℓ0
        (h0, b), (h0, c), (h1, b), (h2, c),           # t1 parts
        (F.fp2_add(h1, h2), bc),                      # t1 Karatsuba share
        (s0, ab), (s1, b), (s2, c),                   # t2 diagonal
        (s12, bc), (s01, F.fp2_add(ab, b)), (s02, F.fp2_add(ab, c)),
    ])
    (g0a, g1a, g2a,
     h0b, h0c, h1b, h2c, h12bc,
     s0a, s1b, s2c, t12, t01, t02) = prods
    # t1 = f1·(0,b,c) = (ξ(h1c + h2b), h0b + ξ·h2c, h0c + h1b)
    #   with h1c + h2b = (h1+h2)(b+c) − h1b − h2c
    h1c_h2b = F.fp2_sub(h12bc, F.fp2_add(h1b, h2c))
    t1 = (
        F.fp2_mul_by_xi(h1c_h2b),
        F.fp2_add(h0b, F.fp2_mul_by_xi(h2c)),
        F.fp2_add(h0c, h1b),
    )
    # t2 = (s0+s1 v+s2 v²)(a+b v+c v²), Karatsuba hybrid
    #   d0 = s0a + ξ(s12·bc − s1b − s2c)
    #   d1 = (s01·(a+b) − s0a − s1b) + ξ s2c
    #   d2 = (s02·(a+c) − s0a − s2c) + s1b
    d0 = F.fp2_add(s0a, F.fp2_mul_by_xi(F.fp2_sub(t12, F.fp2_add(s1b, s2c))))
    d1 = F.fp2_add(F.fp2_sub(t01, F.fp2_add(s0a, s1b)), F.fp2_mul_by_xi(s2c))
    d2 = F.fp2_add(F.fp2_sub(t02, F.fp2_add(s0a, s2c)), s1b)
    t2 = (d0, d1, d2)
    t0 = (g0a, g1a, g2a)
    c0 = F.fp6_add(t0, F.fp6_mul_by_v(t1))
    c1 = F.fp6_sub(t2, F.fp6_add(t0, t1))
    return (c0, c1)


def fp12_sq_fast(f):
    """f² via complex squaring over Fp6 (w² = v): c0 = f0² + v·f1²,
    c1 = 2·f0·f1 — expressed as two Fp6 products (f0+f1)(f0+v·f1) and f0·f1
    (12 Fp2 products, one montmul call) instead of a full 18-product mul."""
    f0, f1 = f
    vf1 = F.fp6_mul_by_v(f1)
    A = F.cat6([F.lead6(F.fp6_add(f0, f1)), F.lead6(f0)])
    B = F.cat6([F.lead6(F.fp6_add(f0, vf1)), F.lead6(f1)])
    T = F.fp6_mul_many(A, B)
    s = F.unlead6(F.slice6(T, 0, 1))   # (f0+f1)(f0+v f1)
    m = F.unlead6(F.slice6(T, 1, 2))   # f0·f1
    c0 = F.fp6_sub(s, F.fp6_add(m, F.fp6_mul_by_v(m)))
    c1 = F.fp6_add(m, m)
    return (c0, c1)


def prepare_g1(P):
    """Precompute the Miller-loop constants of a Jacobian G1 point
    P = (Xp, Yp, Zp): (ξ·yP·Zp³, xP·Zp³) = ((Yp, Yp), Xp·Zp) and Zp³."""
    Xp, Yp, Zp = P
    m = L.montmul(L.stack_fp([Xp, Zp]), L.stack_fp([Zp, Zp]))
    XpZp, Zp2 = L.unstack_fp(m, 2)
    Zp3 = L.montmul(Zp2, Zp)
    xi_yp = (Yp, Yp)  # ξ·Yp with ξ = 1+u, as an Fp2 element
    neg_xpzp = L.neg_mod(XpZp)
    return xi_yp, neg_xpzp, Zp3


def _as_fp2(x):
    """Fp scalar → Fp2 element (x, 0)."""
    return (x, L.zeros_fp(x.shape[1:]))


def _double_step(T, g1c):
    """One Miller doubling: T ← 2T, return the evaluated line."""
    Xt, Yt, Zt = T
    xi_yp, neg_xpzp, zp3 = g1c
    X2 = F.fp2_sq(Xt)
    A = F.fp2_add(F.fp2_add(X2, X2), X2)  # 3X²
    m1 = _fp2_many([(Yt, Zt), (A, Xt)])
    YZ, AX = m1
    B = F.fp2_add(YZ, YZ)  # 2YZ
    m2 = _fp2_many([(Yt, B), (B, Zt), (A, Zt), (B, B)])
    YB, BZ, AZ, B2 = m2
    # line coefficients (scaled by BZ·Zp³)
    la_lb_lc = _fp2_many([
        (BZ, xi_yp),
        (F.fp2_sub(AX, YB), _as_fp2(zp3)),
        (AZ, _as_fp2(neg_xpzp)),
    ])
    l_a, l_b, l_c = la_lb_lc
    # new point: X₂ = B(A²Z − 2XB²), Y₂ = A(3XB² − A²Z) − YB³, Z₂ = B³Z
    m3 = _fp2_many([(A, A), (Xt, B2), (B, B2)])
    A2, XB2, B3 = m3
    m4 = _fp2_many([(A2, Zt), (Yt, B3), (B3, Zt)])
    A2Z, YB3, Z2 = m4
    XB2_2 = F.fp2_add(XB2, XB2)
    XB2_3 = F.fp2_add(XB2_2, XB2)
    m5 = _fp2_many([
        (B, F.fp2_sub(A2Z, XB2_2)),
        (A, F.fp2_sub(XB2_3, A2Z)),
    ])
    Xn = m5[0]
    Yn = F.fp2_sub(m5[1], YB3)
    return (Xn, Yn, Z2), (l_a, l_b, l_c)


def _add_step(T, Q, g1c):
    """Miller addition: T ← T + Q (both homogeneous projective), return line."""
    Xt, Yt, Zt = T
    Xq, Yq, Zq = Q
    xi_yp, neg_xpzp, zp3 = g1c
    m1 = _fp2_many([(Yt, Zq), (Yq, Zt), (Xt, Zq), (Xq, Zt)])
    YZq, YqZ, XZq, XqZ = m1
    E = F.fp2_sub(YZq, YqZ)
    Fv = F.fp2_sub(XZq, XqZ)
    m2 = _fp2_many([(E, Xq), (Fv, Yq), (E, Zq), (Fv, Zq), (Fv, Fv)])
    EXq, FYq, EZq, FZq, F2 = m2
    lines = _fp2_many([
        (FZq, xi_yp),
        (F.fp2_sub(EXq, FYq), _as_fp2(zp3)),
        (EZq, _as_fp2(neg_xpzp)),
    ])
    l_a, l_b, l_c = lines
    # point update
    m3 = _fp2_many([
        (E, E), (Fv, F2), (F2, F.fp2_add(XZq, XqZ)), (F2, Xt),
    ])
    E2, F3, Fsum, XF2 = m3
    m4 = _fp2_many([(E2, Zt), (XF2, Zq), (F3, Yt), (F3, Zt)])
    E2Z, XF2Zq, YF3, F3Z = m4
    m5 = _fp2_many([(E2Z, Zq), (YF3, Zq), (F3Z, Zq)])
    E2ZZq, YF3Zq, Z3 = m5
    G = F.fp2_sub(E2ZZq, Fsum)
    m6 = _fp2_many([(Fv, G), (E, F.fp2_sub(XF2Zq, G))])
    X3 = m6[0]
    Y3 = F.fp2_sub(m6[1], YF3Zq)
    return (X3, Y3, Z3), (l_a, l_b, l_c)


def miller_loop(P_jac, Q_proj, inf_mask):
    """f_{|x|,Q}(P) conjugated (negative x), batched.

    P_jac: G1 Jacobian (X, Y, Z), limb-list Fp elements.
    Q_proj: G2 homogeneous projective on the twist, limb-list Fp2 coords.
    inf_mask: bool batch array — True where either input is the identity;
    those slots yield f = 1 (neutral in the product). Passed explicitly by
    the host (which knows the flags) so no value-level zero test is needed.

    Structure: ONE lax.scan over the 63 post-MSB bits of |x|; each step
    doubles, and on the 5 set bits a lax.cond runs the add step — the cond
    executes its taken branch only, so zero bits pay nothing, and the whole
    loop is a single compiled body (the Python-unrolled segment structure
    compiled the same graph six times over — XLA compile time is
    superlinear in graph size).
    """
    shape = Q_proj[0][0].shape[1:]
    # a batch under L.LANE_FLOOR runs padded with copies, the batch on the
    # lanes (limbs.py); the lanes that came are the lanes returned
    P_jac, Q_proj = jax.tree.map(L.widen_lanes, (P_jac, Q_proj))
    g1c = prepare_g1(P_jac)
    f0 = F.fp12_one(Q_proj[0][0].shape[1:])

    def step(carry, bit):
        T, f = carry
        f = fp12_sq_fast(f)
        T, line = _double_step(T, g1c)
        f = mul_by_line(f, line)

        def with_add(args):
            T, f = args
            T, line_a = _add_step(T, Q_proj, g1c)
            return T, mul_by_line(f, line_a)

        T, f = lax.cond(bit.astype(bool), with_add, lambda a: a, (T, f))
        return (T, f), None

    bits = jnp.asarray(np.array(_BITS_AFTER_MSB, dtype=np.int32))
    (_, f), _ = lax.scan(step, (Q_proj, f0), bits)

    f = jax.tree.map(lambda x: x[..., : shape[-1]], f)
    f = F.fp12_conj(f)  # negative BLS parameter
    return F.fp12_select(inf_mask, F.fp12_one(shape), f)


_ABS_X_BITS_MSB = np.array(
    [(_ABS_X >> i) & 1 for i in range(_ABS_X.bit_length() - 1, -1, -1)],
    dtype=np.int32,
)


def expx_abs(m):
    """m^|x| (square-and-multiply, MSB-first, seeded with m for the MSB).
    |x| has only six set bits, so the multiply is gated behind lax.cond —
    5 of 63 steps pay it instead of all (the step bit is a scan-carried
    scalar, so cond executes one branch)."""

    def step(acc, bit):
        acc = fp12_sq_fast(acc)
        acc = lax.cond(
            bit.astype(bool), lambda a: F.fp12_mul(a, m), lambda a: a, acc
        )
        return acc, None

    acc, _ = lax.scan(step, m, jnp.asarray(_ABS_X_BITS_MSB[1:]))
    return acc


def _hard_part(m):
    """m^(3·(p⁴-p²+1)/r) via the x-chain (x-1)²(x+p)(x²+p²-1)+3. Valid for
    m in the cyclotomic subgroup, where conj is the inverse; also valid
    componentwise on a (num, den) pair whose QUOTIENT is cyclotomic —
    every op here (mul, conj, Frobenius, expx) is a quotient homomorphism."""
    conj = F.fp12_conj
    mul = F.fp12_mul
    t1 = conj(mul(expx_abs(m), m))  # m^(x-1)
    t2 = conj(mul(expx_abs(t1), t1))  # ^(x-1) again
    t3 = mul(conj(expx_abs(t2)), F.fp12_frobenius(t2))  # ^(x+p)
    t4 = conj(expx_abs(conj(expx_abs(t3))))  # ^(x²)
    m3 = mul(mul(m, m), m)
    return mul(mul(mul(t4, F.fp12_frobenius_n(t3, 2)), conj(t3)), m3)


def final_exponentiation(f):
    """f^(3·(p¹²-1)/r): easy part by conjugate/Frobenius, hard part by the
    x-chain (identity verified in tests)."""
    t = F.fp12_mul(F.fp12_conj(f), F.fp12_inv(f))  # f^(p⁶-1)
    m = F.fp12_mul(F.fp12_frobenius_n(t, 2), t)  # ^(p²+1)
    return _hard_part(m)


def final_exp_is_one(f):
    """final_exponentiation(f) == 1, WITHOUT the Fp12 inversion.

    f^(p⁶-1) = conj(f)/f, so the easy-part output is carried as a
    numerator/denominator PAIR side by side on one batch axis (the g
    numerators, then the g denominators) — the hard part then runs once
    over both (same latency as one) and the check becomes num == den. The
    ~580-sequential-multiply Fermat inversion this replaces was ~90% of
    the final-exp wall time on device (round-4 profile: fp12_inv 482 ms of
    532 ms at width 1). That axis is widened to L.LANE_FLOOR: the hard
    part's five 63-step ladders over a pair of values ran with the limbs
    on the lanes (limbs.py)."""
    shape = f[0][0][0].shape[1:]
    g = int(np.prod(shape, dtype=np.int64))
    flat = jax.tree.map(lambda x: x.reshape(x.shape[0], g), f)
    pair = jax.tree.map(  # (num…, den…) ≡ f^(p⁶-1)
        lambda n, d: L.widen_lanes(jnp.concatenate([n, d], axis=1)),
        F.fp12_conj(flat), flat,
    )
    m = F.fp12_mul(F.fp12_frobenius_n(pair, 2), pair)  # ^(p²+1)
    e = _hard_part(m)
    num = jax.tree.map(lambda x: x[:, :g], e)
    den = jax.tree.map(lambda x: x[:, g : 2 * g], e)
    diff = jax.tree.leaves(jax.tree.map(L.sub_mod, num, den))
    # one fused Montgomery reduction (×R·R⁻¹ = identity) pulls the 12
    # component values into (−0.1p, 2p) before the 8p-bounded zero test
    stacked = L.stack_fp(diff)
    one = L.const_fp(L.ONE_MONT_DIGITS, (1,) * (stacked.ndim - 1))
    # Interval worst case of the fp12 difference reaches ~123p via
    # compounded m·p/R terms; theorem (a) still holds and the product
    # contracts into (-0.1p, 2p) (see tools/ranges/bounds.txt).
    red = L.montmul(stacked, one)  # lint: disable=limb-range
    return jnp.all(L.is_zero_val(red), axis=0).reshape(shape)


def multi_pairing_check(P_jac, Q_proj, inf_mask):
    """∏ e(Pᵢ, Qᵢ) == 1 over the batch. Batch must be a power of two (pad
    with infinity pairs — neutral). One shared final exponentiation."""
    f = miller_loop(P_jac, Q_proj, inf_mask)
    f = fp12_product_tree(f)
    return final_exp_is_one(f)


def fp12_product_tree(f):
    """Reduce a batch of Fp12 elements (leading batch axis on every limb
    array) to one element. Batch must be a power of two (pad with one — the
    neutral element). Fixed-shape masked-roll reduction, one compiled body
    (see curve._tree_reduce_points for why)."""
    n = f[0][0][0].shape[1]
    assert n & (n - 1) == 0, "fp12_product_tree requires a power-of-two batch"
    if n < L.LANE_FLOOR:  # the batch on the lanes (limbs.py): pad with one
        ones = F.fp12_one((L.LANE_FLOOR - n,))
        f = jax.tree.map(lambda x, o: jnp.concatenate([x, o], axis=1), f, ones)
        n = L.LANE_FLOOR
    levels = n.bit_length() - 1
    if levels:

        def body(_, carry):
            y, s = carry
            rolled = jax.tree.map(lambda x: jnp.roll(x, -s, axis=1), y)
            y = F.fp12_mul_many(y, rolled)
            return (y, s // 2)

        f, _ = lax.fori_loop(0, levels, body, (f, jnp.int32(n // 2)))
    return tuple(F.take6(c, 0) for c in f)


def fp12_product_tree_grouped(f, group_size: int):
    """Reduce a batch of Fp12 elements to N/group_size products over
    CONTIGUOUS groups [0,S), [S,2S), ... (pad with one — neutral).
    group_size must be a power of two. Same one-compiled-body roll
    reduction as fp12_product_tree, but the strides stop at the group
    width so position g*S holds group g's product; the group products
    come out as a width-N/S batch via one strided slice. Feeds the
    fault-localization kernel's per-sub-batch pairing verdicts."""
    assert group_size & (group_size - 1) == 0, (
        "fp12_product_tree_grouped requires a power-of-two group size"
    )
    if group_size <= 1:
        return f
    levels = group_size.bit_length() - 1

    def body(_, carry):
        y, s = carry
        rolled = jax.tree.map(lambda x: jnp.roll(x, -s, axis=1), y)
        y = F.fp12_mul_many(y, rolled)
        return (y, s // 2)

    f, _ = lax.fori_loop(0, levels, body, (f, jnp.int32(group_size // 2)))
    return jax.tree.map(lambda x: x[:, ::group_size], f)


def jacobian_to_homogeneous(P):
    """(X, Y, Z) Jacobian → (XZ, Y, Z³) homogeneous (no inversion), Fp2."""
    Xj, Yj, Zj = P
    m = _fp2_many([(Xj, Zj), (Zj, Zj)])
    XZ, Z2 = m
    Z3 = F.fp2_mul(Z2, Zj)
    return (XZ, Yj, Z3)
