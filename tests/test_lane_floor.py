"""The verify call's tail at the lane floor (limbs.LANE_FLOOR).

No loop of the verify executable carries a batch narrower than the width at
which XLA's TPU compiler keeps the batch on the lanes: the signature pair
(−g1, Σ rᵢ·sigᵢ) rides in the batched Miller loop as one more lane, and the
final exponentiation's (num, den) pair and the MSM's Horner accumulators run
padded with copies. The same mathematics, value for value: each piece is
held to the host anchor here, and the rule itself to the traced program
(`jax.make_jaxpr`, seconds on a CPU): it says statically that the rule
engaged in every loop. The compiled layouts are tests/test_tpu_compile.py's.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from grandine_tpu.crypto import pairing as AP
from grandine_tpu.crypto.constants import R
from grandine_tpu.crypto.curves import (
    G1, G2, LAMBDA, g1_infinity, g2_infinity,
)
from grandine_tpu.crypto.fields import Fq, Fq2
from grandine_tpu.tpu import bls as B
from grandine_tpu.tpu import curve as C
from grandine_tpu.tpu import field as F
from grandine_tpu.tpu import limbs as L
from grandine_tpu.tpu import msm as M
from grandine_tpu.tpu import pairing as TP

N = 4  # message pairs: the folded Miller loop has N + 1 lanes


# ------------------------------------------------------------ host → device


def _g1_jac(pt, z: int):
    """Anchor G1 point → rest-format Jacobian (X, Y, Z) with Z = z, so the
    Jacobian path is really taken; infinity is (1, 1, 0)."""
    aff = pt.to_affine()
    if aff is None:
        return L.to_mont(1), L.to_mont(1), L.ZERO.copy()
    zf = Fq(z)
    return tuple(
        L.to_mont(v.n) for v in (aff[0] * zf * zf, aff[1] * zf * zf * zf, zf)
    )


def _g2_jac(pt, z: Fq2):
    aff = pt.to_affine()
    if aff is None:
        one = Fq2(Fq(1), Fq(0))
        return tuple(F.fq2_to_dev(v) for v in (one, one, Fq2(Fq(0), Fq(0))))
    return tuple(
        F.fq2_to_dev(v) for v in (aff[0] * z * z, aff[1] * z * z * z, z)
    )


def _check_args(rpk, msgs, sig_acc):
    """(rᵢ·pkᵢ, H(mᵢ), Σ rᵢ·sigᵢ) as anchor points → the rest-format
    arguments of the jitted check below."""
    rpk_d = [_g1_jac(p, 3 + i) for i, p in enumerate(rpk)]
    msg_d = [C.g2_point_to_dev(q) for q in msgs]
    pair_inf = np.array(
        [p.is_infinity() or q.is_infinity() for p, q in zip(rpk, msgs)]
    )
    return (
        tuple(np.stack([d[k] for d in rpk_d]) for k in range(3)),
        pair_inf,
        np.stack([d[0] for d in msg_d]),
        np.stack([d[1] for d in msg_d]),
        _g2_jac(sig_acc, Fq2(Fq(5), Fq(7))),
    )


def _check(rpk, pair_inf, msg_x, msg_y, sig_acc):
    return B._rlc_pairing_check(
        tuple(L.split(c) for c in rpk), jnp.asarray(pair_inf),
        F.fp2_split(msg_x), F.fp2_split(msg_y),
        tuple(F.fp2_split(c) for c in sig_acc),
    )


@pytest.fixture(scope="module")
def rlc_check():
    return jax.jit(_check)


def _anchor_product_is_one(rpk, msgs, sig_acc) -> bool:
    """The host anchor's product equation, pair by pair."""
    return AP.pairing_check(list(zip(rpk, msgs)) + [(-G1, sig_acc)])


def _batch(case: str):
    rng = random.Random(0x33)
    sks = [rng.randrange(1, R) for _ in range(N)]
    rs = [rng.randrange(1, 1 << 64) for _ in range(N)]
    msgs = [G2.mul(rng.randrange(1, R)) for _ in range(N)]
    if case == "sig_sum_infinity":
        # two votes of one key over one message whose randomized
        # signatures cancel: the message pairs' product is 1 AND
        # Σ rᵢ·sigᵢ = ∞, so lane N's own infinity mask decides
        msgs[1] = msgs[0]
        rpk = [G1.mul(sks[0] * rs[0] % R), -G1.mul(sks[0] * rs[0] % R),
               g1_infinity(), g1_infinity()]
        return rpk, msgs, g2_infinity()
    if case == "padding_only":
        return [g1_infinity()] * N, msgs, g2_infinity()
    rpk = [G1.mul(sk * r % R) for sk, r in zip(sks, rs)]
    sigs = [m.mul(sk) for m, sk in zip(msgs, sks)]
    if case == "one_forged":
        sigs[2] = msgs[2].mul(sks[2] + 1)
    sig_acc = g2_infinity()
    for s, r in zip(sigs, rs):
        sig_acc = sig_acc + s.mul(r)
    return rpk, msgs, sig_acc


@pytest.mark.parametrize("case, want", [
    ("valid", True),
    ("one_forged", False),
    ("sig_sum_infinity", True),
    ("padding_only", True),
])
def test_folded_pair_matches_the_anchors_product_equation(
        rlc_check, case, want):
    rpk, msgs, sig_acc = _batch(case)
    assert _anchor_product_is_one(rpk, msgs, sig_acc) is want
    assert bool(rlc_check(*_check_args(rpk, msgs, sig_acc))) is want


# ------------------------------------------ final exponentiation at the floor


@pytest.fixture(scope="module")
def fe_is_one():
    return jax.jit(lambda f: TP.final_exp_is_one(F.fp12_split(f)))


@pytest.mark.parametrize("case", ["unit", "non_unit"])
def test_final_exp_is_one_at_the_floor(fe_is_one, case):
    """An UNBATCHED f: its (num, den) pair runs as 2 of LANE_FLOOR lanes."""
    a = 0xA5A5A5A5
    f = AP.miller_loop(G1.mul(a), G2)
    if case == "unit":  # e(aP, Q) · e(−P, aQ) = 1
        f = f * AP.miller_loop(-G1, G2.mul(a))
    want = AP.final_exponentiation(f).pow(3).is_one()
    assert want is (case == "unit")
    assert bool(fe_is_one(F.fq12_to_dev(f))) is want


def test_final_exp_is_one_keeps_a_batch_shape(fe_is_one):
    """Width g: the g numerators, then the g denominators, on one axis."""
    unit = AP.miller_loop(G1.mul(6), G2) * AP.miller_loop(-G1, G2.mul(6))
    other = AP.miller_loop(G1.mul(7), G2)
    f = np.stack([F.fq12_to_dev(x) for x in (unit, other, unit)])
    assert np.asarray(fe_is_one(f)).tolist() == [True, False, True]


# ------------------------------------------------- the MSM's Horner at the floor


def _g2_msm(points, r_lo, r_hi, n_groups, groups):
    """A fresh jit of the G2 bucket scan (the floor is read at trace time)."""
    inf_mask = np.array([p.is_infinity() for p in points])
    plan = M.plan_msm(r_lo, r_hi, inf_mask, groups, n_groups,
                      window_bits=4, lanes=64)
    x, y, inf = C.g2_points_to_dev(points)

    def kern(x, y, inf, *arrs):
        epx, epy, elive = M.expand_glv_points(
            F.fp2_split(jnp.asarray(x)), F.fp2_split(jnp.asarray(y)),
            jnp.asarray(inf), B._g2_endo(len(points)), C.FP2_OPS,
        )
        out = M.msm_bucket_scan(
            epx, epy, elive, *arrs,
            windows=plan.windows, window_bits=plan.window_bits,
            n_groups=n_groups, ops=C.FP2_OPS,
        )
        return tuple(F.fp2_merge(e) for e in out)

    return [np.asarray(c) for c in jax.jit(kern)(x, y, inf, *plan.arrays)]


@pytest.mark.parametrize("n_groups", [1, 3])
def test_horner_at_the_floor_is_the_narrow_horner(monkeypatch, n_groups):
    """Padded with copies, the accumulators that were there hold the very
    limbs they held at `n_groups` wide (LANE_FLOOR 1 is the program as it
    was), and both are the anchor's Σ (r0ᵢ + r1ᵢ·λ)·Pᵢ."""
    rng = random.Random(17)
    n = 9
    points = [G2.mul(rng.randrange(1, 1 << 64)) for _ in range(n)]
    points[4] = g2_infinity()
    r_lo = [rng.randrange(0, 1 << 32) for _ in range(n)]
    r_hi = [rng.randrange(0, 1 << 32) for _ in range(n)]
    groups = [i % n_groups for i in range(n)]
    at_floor = _g2_msm(points, r_lo, r_hi, n_groups, groups)
    monkeypatch.setattr(L, "LANE_FLOOR", 1)
    narrow = _g2_msm(points, r_lo, r_hi, n_groups, groups)
    for a, b in zip(at_floor, narrow):
        assert a.shape == b.shape and a.shape[0] == n_groups
        assert np.array_equal(a, b)
    want = [g2_infinity() for _ in range(n_groups)]
    for p, lo, hi, g in zip(points, r_lo, r_hi, groups):
        want[g] = want[g] + p.mul((lo + hi * LAMBDA) % R)
    got = [C.dev_to_g2_point(*(c[i] for c in at_floor))
           for i in range(n_groups)]
    assert got == want


# ------------------------------------------------------- the traced program


def _loops(jaxpr):
    """Every scan / while of a jaxpr, nested ones included, as
    (steps or None, shapes of the arrays it carries from step to step)."""
    for eqn in jaxpr.eqns:
        p = eqn.params
        if eqn.primitive.name == "scan":
            body = p["jaxpr"].jaxpr
            lo = p["num_consts"]
            yield p["length"], [
                v.aval.shape for v in body.invars[lo: lo + p["num_carry"]]
            ]
        elif eqn.primitive.name == "while":
            body = p["body_jaxpr"].jaxpr
            yield None, [
                v.aval.shape for v in body.invars[p["body_nconsts"]:]
            ]
        for v in p.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    yield from _loops(sub)


def _limb_arrays(shapes):
    """Carried limb arrays: (26, *batch), at least one batch axis. (The
    Montgomery product's own scan carries its 27 column accumulators,
    which have the batch's shape and no limb axis.)"""
    return [s for s in shapes if len(s) >= 2 and s[0] == L.NLIMBS]


@pytest.fixture(scope="module")
def check_loops():
    args = _check_args(*_batch("valid"))
    return list(_loops(jax.make_jaxpr(_check)(*args).jaxpr))


def test_one_miller_loop_where_there_were_two(check_loops):
    """A scan of 63 steps that carries 18 limb arrays is a Miller loop (T:
    three Fp2, f: an Fp12); one that carries 12 is a ladder of the hard
    part, which has five."""
    by_carry = {}
    for steps, shapes in check_loops:
        if steps == 63:
            k = len(_limb_arrays(shapes))
            by_carry[k] = by_carry.get(k, 0) + 1
    assert by_carry == {18: 1, 12: 5}


def test_no_loop_of_the_check_carries_a_batch_under_the_floor(check_loops):
    carried = [s for _steps, shapes in check_loops
               for s in _limb_arrays(shapes)]
    assert carried, "the check has loops over limb arrays"
    narrow = sorted({s for s in carried if s[-1] < L.LANE_FLOOR})
    assert not narrow, narrow


def test_the_horner_of_one_group_carries_the_floor():
    plan = M.plan_msm([3, 5, 7, 11], [1, 2, 3, 4], np.zeros(4, bool), None,
                      1, window_bits=4, lanes=64)

    def kern(x, y, live, *arrs):
        return M.msm_bucket_scan(
            F.fp2_split(x), F.fp2_split(y), live, *arrs,
            windows=plan.windows, window_bits=plan.window_bits,
            n_groups=1, ops=C.FP2_OPS,
        )

    pts = np.zeros((8, 2, L.NLIMBS), np.int32)
    jaxpr = jax.make_jaxpr(kern)(pts, pts, np.ones(8, bool), *plan.arrays)
    horner = [
        shapes for steps, shapes in _loops(jaxpr.jaxpr)
        if steps == plan.windows
        and all(len(s) == 2 for s in _limb_arrays(shapes))
    ]
    assert len(horner) == 1
    assert _limb_arrays(horner[0]) == [(L.NLIMBS, L.LANE_FLOOR)] * 6
    out = jax.eval_shape(kern, pts, pts, np.ones(8, bool), *plan.arrays)
    assert {o.shape for o in jax.tree.leaves(out)} == {(L.NLIMBS, 1)}


def test_widen_lanes_pads_with_copies_and_leaves_a_wide_batch_alone():
    a = jnp.arange(L.NLIMBS * 3, dtype=jnp.int32).reshape(L.NLIMBS, 3)
    w = L.widen_lanes(a)
    assert w.shape == (L.NLIMBS, L.LANE_FLOOR)
    assert np.array_equal(w[:, :3], a)
    assert np.array_equal(w[:, 3:], np.repeat(a[:, 2:], L.LANE_FLOOR - 3, 1))
    wide = jnp.zeros((L.NLIMBS, L.LANE_FLOOR + 1), jnp.int32)
    assert L.widen_lanes(wide) is wide
