"""Analysis roots: the kernel entry points the interpreter drives.

Each root builds worst-case *envelope* inputs — abstract LimbVals whose
hulls sit at the documented operating bounds — and calls one module's
real entry points.  The envelopes are the analysis' input assumptions
and are listed in the certificate header:

  * montmul-output envelope: the state of any value produced by a
    Montgomery product / relax round — |v| < 2p, digits at the relax
    output bound.  Every kernel-internal field element is of this form.
  * canonical envelope: host-prepared Montgomery constants and
    decompressed coordinates — v ∈ [0, p), digits in [0, MASK].
  * LMAX envelope (limbs validation root only): digits pushed to the
    documented |digit| ≤ LMAX bound with |v| < 20p, validating the
    headline LMAX² < 2³¹ claim at the montmul primitive itself.

Scalars, bit arrays, masks and byte rows enter as ``Opaque`` (shape and
dtype only) — their *values* never feed limb arithmetic.

``COVER_EXEMPT`` lists host-only helpers (converters, planners) per
module; every other top-level function of an analyzed module must be
visited by some root or the runner emits an "uncovered function"
finding — the coverage contract that keeps new kernels from silently
escaping the certifier.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

from tools.ranges.domain import Aff, LimbVal, Opaque


# --- input envelopes --------------------------------------------------------


def _mont_env(eng, fp, shape, axis=0):
    """Montgomery-product/relax output envelope: |v| < 2p, digits at the
    relax-output bound of a worst-case (LMAX-digit) product."""
    eng.recorder.assume(
        f"root inputs ({fp.name}): kernel-internal field elements are "
        f"montmul/relax outputs — |v| < 2p, digits within the relax "
        f"output bound"
    )
    sim = fp.cios(fp.lmax, fp.lmax, fp.lmax)
    top = min(sim["out_top"],
              fp.top_bound_from_value(Fraction(2), sim["out_body"]))
    val = Aff.of_sym(eng.tab.fresh(Fraction(-1), Fraction(2)))
    return LimbVal(fp, shape, axis, sim["out_body"], top,
                   False, False, val)


def _canon_env(eng, fp, shape, axis=0):
    """Host-prepared canonical Montgomery value: v ∈ [0, p)."""
    eng.recorder.assume(
        f"root inputs ({fp.name}): host-prepared constants and "
        f"coordinates are canonical — v ∈ [0, p), digits in [0, MASK]"
    )
    top = int((fp.p - 1) >> (fp.limb_bits * (fp.nlimbs - 1)))
    val = Aff.of_sym(eng.tab.fresh(Fraction(0), Fraction(fp.p - 1, fp.p)))
    return LimbVal(fp, shape, axis, fp.mask, top, True, True, val)


def _lmax_env(eng, fp, shape):
    """Digits at the documented ±LMAX bound, |v| < 20p — the montmul
    operand contract itself, validated at the primitive."""
    eng.recorder.assume(
        f"validation inputs ({fp.name}): montmul operands at the "
        f"documented contract — |digit| <= LMAX, |v| < 20p"
    )
    val = Aff.of_sym(eng.tab.fresh(Fraction(-19), Fraction(19)))
    return LimbVal(fp, shape, 0, fp.lmax, fp.lmax, False, False, val)


def _nonneg_env(eng, fp, shape, hi_p):
    """Non-negative value in [0, hi_p·p) with relax-output digits —
    the canonical_digits operand shape (e.g. the +8p offset form)."""
    sim = fp.cios(fp.lmax, fp.lmax, fp.lmax)
    val = Aff.of_sym(eng.tab.fresh(Fraction(0), Fraction(hi_p)))
    return LimbVal(fp, shape, 0, sim["out_body"],
                   fp.top_bound_from_value(Fraction(hi_p),
                                           sim["out_body"]),
                   True, False, val)


def _bits(shape):
    return Opaque(shape, np.int32)


def _mask(shape):
    return Opaque(shape, np.bool_)


# --- roots ------------------------------------------------------------------


def _root_limbs(eng, mods):
    L = mods["limbs"]
    fp = eng.fields[0]
    B = (4,)
    a = _lmax_env(eng, fp, (fp.nlimbs,) + B)
    b = _lmax_env(eng, fp, (fp.nlimbs,) + B)
    # montmul validated at the documented operand contract itself
    m = L.montmul(a, b)
    m2 = L.montsq(m)
    # relax-family probes at the LMAX digit bound (no value precondition)
    L.add_mod(a, b)
    L.sub_mod(a, b)
    L.neg_mod(a)
    L.double_mod(a)
    L.relax(a + b)
    # zero tests only ever see short chains of montmul outputs (|v| < 2p)
    s = L.add_mod(m, m2)
    d = L.sub_mod(s, m)
    n = L.neg_mod(d)
    L.double_mod(n)
    L.relax(m + s)
    sel = L.select(_mask(B), m, s)
    L.is_zero_val(L.sub_mod(m, sel))
    L.is_one_mont(m)
    L.is_zero_val_many([m, s])
    L.canonical_digits(_nonneg_env(eng, fp, (fp.nlimbs,) + B, 9))
    w = Opaque(B + (13,), np.uint32)
    x = L.unpack_words(w)
    L.to_mont_dev(x)
    L.inv_mod(m)
    L.pow_fixed(m, (fp.p + 1) // 4)
    rest = L.merge(m)
    L.split(rest)
    st = L.stack_fp([m, s])
    L.unstack_fp(st, 2)
    L.concat_fp([m, s])
    L.index_fp(st, 0)
    L.batch_shape(m)
    L.zeros_fp(B)
    L.const_fp(L.ONE_MONT_DIGITS, B)


def _root_field_tower(eng, mods):
    F = mods["field"]
    fp = eng.fields[0]
    B = (4,)

    def me():
        return _mont_env(eng, fp, (fp.nlimbs,) + B)

    def fp2():
        return (me(), me())

    def fp6():
        return (fp2(), fp2(), fp2())

    def fp12():
        return (fp6(), fp6())

    a2, b2 = fp2(), fp2()
    F.fp2_add(a2, b2)
    F.fp2_sub(a2, b2)
    F.fp2_neg(a2)
    F.fp2_double(a2)
    F.fp2_mul(a2, b2)
    F.fp2_sq(a2)
    F.fp2_pair_products([(a2, b2), (b2, a2)])
    F.fp2_scale(a2, _mont_env(eng, fp, (fp.nlimbs, 1)))
    F.fp2_conj(a2)
    F.fp2_mul_by_xi(a2)
    F.fp2_inv(a2)
    F.fp2_is_zero(a2)
    F.fp2_is_zero_many([a2, b2])
    F.fp2_select(_mask(B), a2, b2)
    F.fp2_zero(B)
    F.fp2_one(B)
    a6, b6 = fp6(), fp6()
    F.fp6_add(a6, b6)
    F.fp6_sub(a6, b6)
    F.fp6_neg(a6)
    F.fp6_mul(a6, b6)
    F.fp6_sq(a6)
    F.fp6_mul_by_v(a6)
    F.fp6_scale2(a6, a2)
    F.fp6_inv(a6)
    F.fp6_zero(B)
    F.fp6_one(B)
    a12, b12 = fp12(), fp12()
    F.fp12_mul(a12, b12)
    F.fp12_sq(a12)
    F.fp12_conj(a12)
    F.fp12_inv(a12)
    F.fp12_select(_mask(B), a12, b12)
    F.fp12_is_one(a12)
    F.fp12_from_components(F.fp12_components(a12))
    F.fp12_zero(B)
    F.fp12_one(B)
    for k in (1, 2, 3):
        F.fp12_frobenius_n(a12, k)
    # REST-layout boundary plumbing (device-capable split/merge)
    F.fp2_merge(a2)
    F.fp2_split(np.zeros((4, 2, fp.nlimbs), np.int32))
    F.fp6_split(np.zeros((4, 3, 2, fp.nlimbs), np.int32))
    F.fp12_split(np.zeros((4, 2, 3, 2, fp.nlimbs), np.int32))


def _root_field_sqrt(eng, mods):
    F = mods["field"]
    fp = eng.fields[0]
    B = (4,)
    a = _mont_env(eng, fp, (fp.nlimbs,) + B)
    F.fq_is_square(a)
    F.fq_sqrt(a)
    F.fq2_sqrt((_mont_env(eng, fp, (fp.nlimbs,) + B),
                _mont_env(eng, fp, (fp.nlimbs,) + B)))


def _curve_point(eng, fp, B, ops_name):
    def me():
        return _mont_env(eng, fp, (fp.nlimbs,) + B)

    if ops_name == "fp2":
        return ((me(), me()), (me(), me()), (me(), me()))
    return (me(), me(), me())


def _root_curve_formulas(eng, mods):
    C = mods["curve"]
    fp = eng.fields[0]
    B = (8,)
    for ops, kind in ((C.FP_OPS, "fp"), (C.FP2_OPS, "fp2")):
        p = _curve_point(eng, fp, B, kind)
        q = _curve_point(eng, fp, B, kind)
        C.point_double(p, ops)
        C.point_madd_unsafe(p, q[0], q[1], ops)
        C.point_add_complete(p, q, ops)
        C.point_infinity_like(p[0], ops)
    a2 = (_mont_env(eng, fp, (fp.nlimbs, 8)),
          _mont_env(eng, fp, (fp.nlimbs, 8)))
    C._fp2_index(C._fp2_concat([a2, a2], axis=1), 0)


def _root_curve_ladders(eng, mods):
    C = mods["curve"]
    fp = eng.fields[0]
    B = (8,)

    def me():
        return _mont_env(eng, fp, (fp.nlimbs,) + B)

    inf = _mask(B)
    bits = _bits((255,) + B)
    for ops, kind in ((C.FP_OPS, "fp"), (C.FP2_OPS, "fp2")):
        pt = _curve_point(eng, fp, B, kind)
        C.scalar_mul(pt[0], pt[1], inf, bits, ops)
        C.scalar_mul_jac(pt, inf, bits, ops)
    endo = (_canon_env(eng, fp, (fp.nlimbs,) + B),
            _canon_env(eng, fp, (fp.nlimbs,) + B))
    b_lo, b_hi = _bits((128,) + B), _bits((128,) + B)
    C.scalar_mul_glv(me(), me(), inf, b_lo, b_hi, endo, C.FP_OPS,
                     neg_lo=_mask(B), neg_hi=_mask(B))
    C.scalar_mul_jac_glv(_curve_point(eng, fp, B, "fp"), inf, b_lo, b_hi,
                         endo, C.FP_OPS)


def _root_curve_sums(eng, mods):
    C = mods["curve"]
    fp = eng.fields[0]
    B = (8,)
    for ops, kind in ((C.FP_OPS, "fp"), (C.FP2_OPS, "fp2")):
        p = _curve_point(eng, fp, B, kind)
        C.sum_points(p, ops)
        C.sum_points_grouped(p, 4, ops)
        C.sum_points_contiguous(p, 4, ops)


def _root_curve_decompress(eng, mods):
    C = mods["curve"]
    C.g1_decompress_dev(Opaque((4, 48), np.uint8))
    C.g2_decompress_dev(Opaque((4, 96), np.uint8))


def _root_pairing_check(eng, mods):
    PR = mods["pairing"]
    fp = eng.fields[0]
    B = (4,)

    def me():
        return _mont_env(eng, fp, (fp.nlimbs,) + B)

    P_jac = (me(), me(), me())
    Q_proj = ((me(), me()), (me(), me()), (me(), me()))
    PR.multi_pairing_check(P_jac, Q_proj, _mask(B))


def _root_pairing_tail(eng, mods):
    PR = mods["pairing"]
    fp = eng.fields[0]
    B = (4,)

    def me():
        return _mont_env(eng, fp, (fp.nlimbs,) + B)

    def fp12():
        return tuple(
            tuple((me(), me()) for _ in range(3)) for _ in range(2)
        )

    PR.final_exponentiation(fp12())
    PR.fp12_product_tree(fp12())
    PR.fp12_product_tree_grouped(fp12(), 2)
    PR.jacobian_to_homogeneous(((me(), me()), (me(), me()), (me(), me())))


def _root_msm(eng, mods):
    M = mods["msm"]
    C = mods["curve"]
    fp = eng.fields[0]
    n = 8
    r_lo = np.array([3, 0x12345, 1, 0xFFFFFFFF, 7, 0, 11, 255],
                    dtype=np.uint64)
    r_hi = np.array([5, 1, 0xABCDEF, 2, 0, 9, 1, 4096], dtype=np.uint64)
    inf_host = np.zeros(n, bool)
    inf_host[5] = True
    plan = M.plan_msm(
        r_lo, r_hi, inf_host,
        group_of_point=np.arange(n) // 4, n_groups=2,
        window_bits=4, lanes=8,
    )
    x = _mont_env(eng, fp, (fp.nlimbs, n))
    y = _mont_env(eng, fp, (fp.nlimbs, n))
    endo = (_canon_env(eng, fp, (fp.nlimbs, n)),
            _canon_env(eng, fp, (fp.nlimbs, n)))
    px, py, live = M.expand_glv_points(x, y, _mask((n,)), endo, C.FP_OPS)
    M.msm_bucket_scan(
        px, py, live,
        plan.point_idx, plan.valid, plan.flush,
        plan.gather_idx, plan.gather_valid,
        plan.windows, plan.window_bits, plan.n_groups, C.FP_OPS,
    )


def _root_ed25519(eng, mods):
    E = mods["ed25519"]
    ed = eng.fields[1]
    B = 4
    px = _canon_env(eng, ed, (B, ed.nlimbs), axis=1)
    py = _canon_env(eng, ed, (B, ed.nlimbs), axis=1)
    pt = _canon_env(eng, ed, (B, ed.nlimbs), axis=1)
    E.verify_kernel(px, py, pt, _bits((B, 253)))
    E.merge(E.split(np.zeros((B, ed.nlimbs), np.int32)))


def _root_spans(eng, mods):
    S = mods["spans"]
    n, e = 4, S.SPAN_GRID_EPOCHS
    S._span_grid_compute(
        Opaque((n, e), np.int32), Opaque((n, e), np.int32),
        Opaque((n,), np.int32), Opaque((n,), np.int32),
        _mask((n,)), Opaque((1,), np.int32),
    )


#: (root name, modules it needs loaded) — execution order is fixed so
#: the certificate text is deterministic.
ROOTS = (
    ("limbs.primitives", _root_limbs),
    ("field.tower", _root_field_tower),
    ("field.sqrt", _root_field_sqrt),
    ("curve.formulas", _root_curve_formulas),
    ("curve.ladders", _root_curve_ladders),
    ("curve.sums", _root_curve_sums),
    ("curve.decompress", _root_curve_decompress),
    ("pairing.check", _root_pairing_check),
    ("pairing.tail", _root_pairing_tail),
    ("msm.bucket_scan", _root_msm),
    ("ed25519.verify", _root_ed25519),
    ("spans.grid", _root_spans),
)


# --- coverage contract ------------------------------------------------------

#: host-only top-level functions per module: converters between Python
#: ints / anchor field objects and limb arrays, numpy-only planners, and
#: host bucketing helpers.  Everything else must be visited by a root.
COVER_EXEMPT = {
    "limbs": {
        "int_to_limbs", "limbs_to_int", "to_mont", "from_mont",
        "merge_np",
    },
    "field": {
        "fq2_to_dev", "fq6_to_dev", "fq12_to_dev", "fp2_merge_np",
        "fp6_merge_np", "fp12_merge_np", "dev_to_fq2", "dev_to_fq6",
        "dev_to_fq12",
    },
    "curve": {
        "scalars_to_bits_msb", "g1_point_to_dev", "g2_point_to_dev",
        "dev_to_g1_point", "dev_to_g2_point", "ints_to_mont_limbs",
        "_batch_inv_mod_p", "g1_points_to_dev", "g2_points_to_dev",
        "compressed_rows",
        "compressed_infinity_flags",
    },
    "msm": {"_next_pow2"},
    "ed25519": {
        "int_to_limbs", "limbs_to_int", "to_mont", "from_mont",
        "ints_to_mont_limbs", "_ladder_bucket",
    },
    "spans": {"grid_merge_host"},
}
