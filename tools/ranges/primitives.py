"""Transfer functions of the limb-range abstract interpreter.

The primitive layer of each limb plane (``tpu/limbs.py`` for the
26-limb BLS field, the primitive subset of ``tpu/ed25519.py`` for the
18-limb curve25519 field, plus the two canonicalization atomics of
``tpu/curve.py``) is replaced by hand-written transfer functions; every
composite above it (the Fp2/Fp6/Fp12 tower, the curve formulas, the
Miller loop, the MSM plan, the EdDSA ladder) executes its real Python
body over abstract :class:`LimbVal` values.

Each transfer discharges its theorem obligations at the *call site*
(nearest stack frame outside the primitive layer):

  (a) int32 safety — digit products and CIOS column accumulators from
      the exact interval simulation in :mod:`tools.ranges.fields`,
      raw digit sums of add/sub/neg, relax top-digit adds;
  (b) montmul operand precondition |v| < 20p (both planes), which keeps
      the Montgomery product's reduced value in (−0.1p, 2p);
  (c) canonicalization preconditions — |v| < 8p at zero tests and at
      ``_canonical_mod_p``, v ∈ [0, R) at ``canonical_digits``, and no
      digit plane extracted from a non-canonical value.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

from tools.ranges import engine
from tools.ranges.domain import Aff, AnalysisError, LimbVal, Opaque
from tools.ranges.fields import INT32_LIM

ACC_CLAIM = 1 << 22  # documented CIOS column-accumulator bound


def _fmt(x) -> str:
    try:
        return f"{float(x):.4g}"
    except OverflowError:
        f = Fraction(x)
        exp = f.numerator.bit_length() - f.denominator.bit_length()
        return f"~2^{exp}"


# --- site recording ---------------------------------------------------------


class Recorder:
    def __init__(self):
        #: (path, func, line, prim) → joined per-site stats
        self.sites = {}
        #: global input assumptions, listed in the certificate header
        self.assumptions = []
        #: >0 while a fixpoint is still iterating: transient iterates are
        #: not reachable program states, so nothing is recorded — each
        #: loop re-runs its body once at the converged carry to record.
        self.muted = 0

    def assume(self, text: str):
        if text not in self.assumptions:
            self.assumptions.append(text)

    def digit_plane(self, lv: LimbVal):
        hull = lv.val.hull(engine.CURRENT.tab)
        _rec(
            "digitrow", lv.fp,
            op_hull=max(-hull[0], hull[1]),
            violations=(
                "digit plane extracted from a non-canonical limb value "
                "(theorem c)",
            ),
        )


def _rec(prim, fp, *, op_hull=None, pre=None, max_prod=0, max_acc=0,
         out_hull=None, redundant=None, violations=()):
    eng = engine.CURRENT
    if eng.recorder.muted:
        return
    path, func, line = eng.site()
    sites = eng.recorder.sites
    key = (path, func, line, prim)
    s = sites.get(key)
    if s is None:
        s = {
            "prim": prim, "fp": fp.name, "count": 0, "op_hull": None,
            "pre": pre, "max_prod": 0, "max_acc": 0, "out_lo": None,
            "out_hi": None, "redundant": None, "violations": set(),
        }
        sites[key] = s
    s["count"] += 1
    if op_hull is not None:
        s["op_hull"] = (op_hull if s["op_hull"] is None
                        else max(s["op_hull"], op_hull))
    if pre is not None:
        s["pre"] = pre
    s["max_prod"] = max(s["max_prod"], max_prod)
    s["max_acc"] = max(s["max_acc"], max_acc)
    if out_hull is not None:
        lo, hi = out_hull
        s["out_lo"] = lo if s["out_lo"] is None else min(s["out_lo"], lo)
        s["out_hi"] = hi if s["out_hi"] is None else max(s["out_hi"], hi)
    if redundant is not None:
        s["redundant"] = (redundant if s["redundant"] is None
                          else (s["redundant"] and redundant))
    s["violations"].update(violations)


#: frames skipped during call-site attribution: the primitive layer
#: itself.  limbs.py is primitives throughout (composites like
#: pow_fixed/to_mont_dev attribute to *their* caller); ed25519.py only
#: below its composite section.
SKIP_WHOLE = {"grandine_tpu/tpu/limbs.py"}
SKIP_FUNCS = {
    "grandine_tpu/tpu/ed25519.py": {
        "relax", "add_mod", "sub_mod", "double_mod", "montmul",
        "canonical_digits", "is_zero_val", "select", "const_fp",
        "split", "merge",
    },
}


# --- lifting ----------------------------------------------------------------


def lift_concrete(arr, fp, like=None, axis=None) -> LimbVal:
    """Concrete digit array → exact LimbVal.  The limb axis is taken
    from ``axis``, or right-aligned against ``like``, falling back to
    device layout (leading axis of length NLIMBS)."""
    a = np.asarray(arr)
    if axis is None and like is not None:
        cand = a.ndim - (like.ndim - like.limb_axis)
        if 0 <= cand < a.ndim and a.shape[cand] == fp.nlimbs:
            axis = cand
    if axis is None and a.ndim >= 1 and a.shape[0] == fp.nlimbs:
        axis = 0
    if axis is None or not (0 <= axis < a.ndim) \
            or a.shape[axis] != fp.nlimbs:
        raise AnalysisError(
            f"cannot lift concrete array of shape {a.shape} to a "
            f"{fp.nlimbs}-limb value"
        )
    flat = np.moveaxis(a, axis, 0).reshape(fp.nlimbs, -1)
    if flat.shape[1] == 0:
        digits = [0] * fp.nlimbs
    elif np.all(flat == flat[:, :1]):
        digits = [int(x) for x in flat[:, 0]]
    else:
        # batch-varying constant table (e.g. the stacked Frobenius
        # coefficients): exact per-entry values, hull = their union.
        vals = [
            fp.value_of_digits(int(flat[i, k]) for i in range(fp.nlimbs))
            for k in range(flat.shape[1])
        ]
        lo = Fraction(min(vals), fp.p)
        hi = Fraction(max(vals), fp.p)
        form = (Aff.of_const(lo) if lo == hi
                else Aff.of_sym(engine.CURRENT.tab.fresh(lo, hi)))
        return LimbVal(
            fp, a.shape, axis,
            int(np.max(np.abs(flat[:-1]))) if fp.nlimbs > 1 else 0,
            int(np.max(np.abs(flat[-1]))),
            bool(np.all(flat >= 0)),
            bool(np.all((flat >= 0) & (flat <= fp.mask))
                 and max(vals) < fp.p),
            form,
        )
    value = fp.value_of_digits(digits)
    body = [abs(d) for d in digits[:-1]] or [0]
    return LimbVal(
        fp, a.shape, axis, max(body), abs(digits[-1]),
        all(d >= 0 for d in digits),
        all(0 <= d <= fp.mask for d in digits),
        Aff.of_const(Fraction(value, fp.p)),
    )


def zero_like_limb(x: LimbVal) -> LimbVal:
    return LimbVal(x.fp, x.shape, x.limb_axis, 0, 0, True, True,
                   Aff.of_const(Fraction(0)))


def _as_limb(x, fp, like=None, axis=None) -> LimbVal:
    if isinstance(x, LimbVal):
        if x.fp is not fp:
            raise AnalysisError(
                f"value of plane {x.fp.name} reached a {fp.name} primitive"
            )
        return x
    if isinstance(x, Opaque):
        raise AnalysisError(
            f"opaque (untracked) value of shape {x.shape} reached a limb "
            f"primitive"
        )
    return lift_concrete(x, fp, like=like, axis=axis)


def _hmag(hull) -> Fraction:
    return max(-hull[0], hull[1])


def _fresh_hull(lo, hi) -> Aff:
    return Aff.of_sym(engine.CURRENT.tab.fresh(lo, hi))


# --- raw digit operators on LimbVal -----------------------------------------


def _scalar_limb(c: int, like: LimbVal) -> LimbVal:
    fp = like.fp
    w = sum(1 << (fp.limb_bits * i) for i in range(fp.nlimbs))
    return LimbVal(
        fp, like.shape, like.limb_axis, abs(c), abs(c), c >= 0,
        0 <= c <= fp.mask, Aff.of_const(Fraction(c * w, fp.p)),
    )


def _coerce_operand(a: LimbVal, b):
    if isinstance(b, LimbVal):
        return b
    if isinstance(b, (int, np.integer)):
        return _scalar_limb(int(b), a)
    return _as_limb(b, a.fp, like=a)


def _raw_combine(a: LimbVal, b, sign: int) -> LimbVal:
    b = _coerce_operand(a, b)
    afr = a.ndim - a.limb_axis
    if b.ndim - b.limb_axis != afr:
        raise AnalysisError("raw op on values with mismatched limb axes")
    shape = np.broadcast_shapes(a.shape, b.shape)
    ax = len(shape) - afr
    dmag = a.dmag + b.dmag
    tmag = a.tmag + b.tmag
    viol = ()
    if max(dmag, tmag) >= INT32_LIM:
        viol = (f"raw digit sum bound {max(dmag, tmag)} >= 2^31 "
                f"(theorem a)",)
    _rec("raw", a.fp, max_acc=max(dmag, tmag), violations=viol)
    val = a.val + b.val if sign > 0 else a.val - b.val
    nonneg = sign > 0 and a.nonneg and b.nonneg
    return LimbVal(a.fp, shape, ax, dmag, tmag, nonneg, False, val)


def install_operators():
    if getattr(LimbVal, "_range_ops", False):
        return
    LimbVal.__add__ = lambda s, o: _raw_combine(s, o, +1)
    LimbVal.__radd__ = lambda s, o: _raw_combine(s, o, +1)
    LimbVal.__sub__ = lambda s, o: _raw_combine(s, o, -1)
    LimbVal.__rsub__ = lambda s, o: _raw_combine(_coerce_operand(s, o),
                                                 s, -1)

    def _neg(s):
        _rec("raw", s.fp, max_acc=max(s.dmag, s.tmag))
        return LimbVal(s.fp, s.shape, s.limb_axis, s.dmag, s.tmag,
                       False, False, s.val.scale(-1))

    def _mul(s, o):
        if not isinstance(o, (int, np.integer)):
            raise AnalysisError("raw digit product outside the primitive "
                                "layer")
        k = int(o)
        dmag, tmag = s.dmag * abs(k), s.tmag * abs(k)
        viol = ()
        if max(dmag, tmag) >= INT32_LIM:
            viol = (f"raw digit scale bound {max(dmag, tmag)} >= 2^31 "
                    f"(theorem a)",)
        _rec("raw", s.fp, max_acc=max(dmag, tmag), violations=viol)
        return LimbVal(s.fp, s.shape, s.limb_axis, dmag, tmag,
                       s.nonneg and k >= 0, False, s.val.scale(k))

    LimbVal.__neg__ = _neg
    LimbVal.__mul__ = _mul
    LimbVal.__rmul__ = _mul

    def _cmp(s, o):
        return Opaque(np.broadcast_shapes(s.shape, _shape(o)), np.bool_)

    def _shape(o):
        return tuple(getattr(o, "shape", ()))

    for name in ("__eq__", "__ne__", "__lt__", "__le__", "__gt__",
                 "__ge__"):
        setattr(LimbVal, name, _cmp)
    LimbVal.__hash__ = object.__hash__

    def _getitem(s, idx):
        from tools.ranges.engine import _relayout
        from tools.ranges.domain import _clean_key
        cidx = _clean_key(idx)
        return _relayout(s, lambda d: d[cidx])

    LimbVal.__getitem__ = _getitem

    def _reshape(s, *new):
        from tools.ranges.engine import _relayout
        if len(new) == 1 and isinstance(new[0], (tuple, list)):
            new = tuple(new[0])
        new = tuple(int(x) for x in new)
        return _relayout(s, lambda d: d.reshape(new))

    LimbVal.reshape = _reshape

    def _astype(s, dt):
        if np.dtype(dt) != np.dtype(np.int32):
            raise AnalysisError(f"limb value cast to {dt}")
        return s

    LimbVal.astype = _astype
    LimbVal.dtype = property(lambda s: np.dtype(np.int32))
    # `ndarray OP LimbVal` must reach our reflected dunders, not numpy's
    # elementwise broadcast over the object.
    LimbVal.__array_ufunc__ = None
    LimbVal._range_ops = True


# --- field-plane atomic transfers -------------------------------------------


def _relax_out(fp, v: LimbVal, prim: str, extra_viol=(),
               extra_acc=0) -> LimbVal:
    """Shared tail of every op that ends in one relax round: bounds from
    relax_bounds, top digit tightened by the value hull, value exactly
    preserved (relax never drops a carry — the top digit is unsplit)."""
    eng = engine.CURRENT
    body, top, topadd = fp.relax_bounds(v.dmag, v.tmag)
    viol = list(extra_viol)
    if topadd >= INT32_LIM:
        viol.append(f"relax top-digit add bound {topadd} >= 2^31 "
                    f"(theorem a)")
    hull = v.val.hull(eng.tab)
    redundant = v.canonical  # digits already in [0, 2^B): relax = identity
    top = min(top, fp.top_bound_from_value(_hmag(hull), body))
    _rec(prim, fp, max_acc=max(topadd, extra_acc), out_hull=hull,
         redundant=redundant, violations=viol)
    if redundant:
        return v
    return LimbVal(fp, v.shape, v.limb_axis, body, top, v.nonneg, False,
                   v.val)


def make_field_transfers(fp):
    """Atomic transfer functions for one limb plane's primitive layer,
    to be installed over the exec'd module namespace."""

    def t_relax(s):
        return _relax_out(fp, _as_limb(s, fp), "relax")

    def t_add_mod(a, b):
        a = _as_limb(a, fp, like=b if isinstance(b, LimbVal) else None)
        return _relax_out(fp, _raw_combine(a, b, +1), "add_mod")

    def t_sub_mod(a, b):
        a = _as_limb(a, fp, like=b if isinstance(b, LimbVal) else None)
        return _relax_out(fp, _raw_combine(a, b, -1), "sub_mod")

    def t_neg_mod(a):
        a = _as_limb(a, fp)
        neg = LimbVal(fp, a.shape, a.limb_axis, a.dmag, a.tmag, False,
                      False, a.val.scale(-1))
        return _relax_out(fp, neg, "neg_mod")

    def t_double_mod(a):
        a = _as_limb(a, fp)
        return _relax_out(fp, _raw_combine(a, a, +1), "double_mod")

    def t_montmul(a, b):
        eng = engine.CURRENT
        a = _as_limb(a, fp, axis=0)
        b = _as_limb(b, fp, axis=0)
        if a.limb_axis != 0 or b.limb_axis != 0:
            raise AnalysisError("montmul operand not in device layout")
        ah = a.val.hull(eng.tab)
        bh = b.val.hull(eng.tab)
        amag, bmag = _hmag(ah), _hmag(bh)
        viol = []
        for mag in sorted({amag, bmag}):
            if mag >= fp.montmul_pre:
                viol.append(
                    f"montmul operand value bound {_fmt(mag)}p exceeds "
                    f"the |v| < {int(fp.montmul_pre)}p precondition "
                    f"(theorem b)"
                )
        da = max(a.dmag, a.tmag)
        sim = fp.cios(da, b.dmag, b.tmag)
        if sim["max_prod"] >= INT32_LIM:
            viol.append(f"digit product bound {sim['max_prod']} >= 2^31 "
                        f"(theorem a)")
        if sim["max_acc"] >= ACC_CLAIM:
            viol.append(
                f"CIOS column accumulator bound {sim['max_acc']} exceeds "
                f"the documented 2^22 bound (theorem a)"
            )
        # value: (a·b)/R + m·p/R with m ∈ [0, R).  Error recovery: when an
        # operand exceeds the precondition we have already recorded the
        # theorem-(b) violation above — the output hull is computed from
        # the operands CLAMPED to the precondition so a single exceedance
        # does not cascade into quadratic interval blow-up (and spurious
        # findings) at every downstream site.
        pre = fp.montmul_pre
        ah = (max(ah[0], -pre), min(ah[1], pre))
        bh = (max(bh[0], -pre), min(bh[1], pre))
        cross = [ah[0] * bh[0], ah[0] * bh[1], ah[1] * bh[0],
                 ah[1] * bh[1]]
        s_lo = min(cross) / fp.r_over_p
        s_hi = max(cross) / fp.r_over_p
        val = _fresh_hull(s_lo, s_hi) + _fresh_hull(
            Fraction(0), Fraction(fp.r - 1, fp.r))
        out_top = min(
            sim["out_top"],
            fp.top_bound_from_value(max(-s_lo, s_hi + 1),
                                    sim["out_body"]),
        )
        batch = np.broadcast_shapes(a.shape[1:], b.shape[1:])
        _rec("montmul", fp, op_hull=max(amag, bmag), pre=fp.montmul_pre,
             max_prod=sim["max_prod"], max_acc=sim["max_acc"],
             out_hull=(s_lo, s_hi + 1), violations=viol)
        return LimbVal(fp, (fp.nlimbs,) + batch, 0, sim["out_body"],
                       out_top, False, False, val)

    def t_montsq(a):
        return t_montmul(a, a)

    def t_is_zero_val(a):
        eng = engine.CURRENT
        a = _as_limb(a, fp)
        hull = a.val.hull(eng.tab)
        viol = []
        if not (-fp.iszero_pre < hull[0] and hull[1] < fp.iszero_pre):
            viol.append(
                f"zero-test operand value bound [{_fmt(hull[0])}p, "
                f"{_fmt(hull[1])}p] exceeds the |v| < "
                f"{int(fp.iszero_pre)}p precondition (theorem c)"
            )
        # + 8p offset, then the canonicalization ripple
        acc = max(a.dmag + fp.mask, a.tmag + fp.mask)
        if 2 * acc + 1 >= INT32_LIM:
            viol.append(f"canonicalization ripple bound {2 * acc + 1} "
                        f">= 2^31 (theorem a)")
        _rec("iszero", fp, op_hull=_hmag(hull), pre=fp.iszero_pre,
             max_acc=2 * acc + 1, violations=viol)
        return Opaque(a.batch_shape(), np.bool_)

    def t_canonical_digits(t):
        eng = engine.CURRENT
        t = _as_limb(t, fp)
        hull = t.val.hull(eng.tab)
        viol = []
        if hull[0] < 0 or hull[1] >= fp.canon_hi:
            viol.append(
                f"canonical_digits operand value bound [{_fmt(hull[0])}p,"
                f" {_fmt(hull[1])}p] not within [0, R) (theorem c)"
            )
        acc = 2 * max(t.dmag, t.tmag) + 1
        if acc >= INT32_LIM:
            viol.append(f"canonicalization ripple bound {acc} >= 2^31 "
                        f"(theorem a)")
        hi = max(hull[1], Fraction(0))
        top = min(fp.mask,
                  int((hi * fp.p) / (1 << (fp.limb_bits *
                                           (fp.nlimbs - 1)))) + 1)
        _rec("canonical", fp, op_hull=hull[1], pre=fp.canon_hi,
             max_acc=acc, violations=viol)
        return LimbVal(fp, t.shape, t.limb_axis, fp.mask, top, True,
                       True, t.val)

    def t_select(cond, a, b):
        # cond has the batch shape (broadcast over limbs).  Lifting the
        # branches keeps constant branches (e.g. the ∞-point coordinate
        # tables in the MSM scan) in the limb plane even when the
        # condition is abstract — the generic ``where`` shim would
        # degrade a concrete/concrete pair to Opaque.
        abstract = any(isinstance(x, (LimbVal, Opaque))
                       for x in (cond, a, b))
        if not abstract:
            return np.where(np.asarray(cond)[None], np.asarray(a),
                            np.asarray(b))
        if isinstance(a, Opaque) or isinstance(b, Opaque):
            shape = np.broadcast_shapes(
                (1,) + tuple(getattr(cond, "shape", ())),
                tuple(getattr(a, "shape", ())),
                tuple(getattr(b, "shape", ())))
            return Opaque(shape, np.int32)
        a = _as_limb(a, fp, axis=0)
        b = _as_limb(b, fp, axis=0)
        j = engine.CURRENT.joinv(a, b)
        cshape = (1,) + tuple(getattr(cond, "shape", ()))
        shape = np.broadcast_shapes(j.shape, cshape)
        ax = j.limb_axis + (len(shape) - j.ndim)
        return j.with_layout(shape, ax)

    def t_unpack_words(w):
        # Input assumption: packed words hold a value < 2^384 (twelve
        # uint32 words and a zero thirteenth; wire payloads are masked
        # to 381 bits before reaching this point).
        engine.CURRENT.recorder.assume(
            f"unpack_words ({fp.name}): packed uint32 words hold a "
            f"non-negative value < 2^384 (twelve words and a zero "
            f"thirteenth; wire payloads are masked to 381 bits)"
        )
        batch = _shape_tail(w)
        hi = Fraction((1 << 384) - 1, fp.p)
        top = ((1 << 384) - 1) >> (fp.limb_bits * (fp.nlimbs - 1))
        return LimbVal(fp, (fp.nlimbs,) + batch, 0, fp.mask,
                       min(fp.mask, top), True, True,
                       _fresh_hull(Fraction(0), hi))

    def _shape_tail(w):
        return tuple(getattr(w, "shape", ()))[:-1]

    return {
        "relax": t_relax,
        "add_mod": t_add_mod,
        "sub_mod": t_sub_mod,
        "neg_mod": t_neg_mod,
        "double_mod": t_double_mod,
        "montmul": t_montmul,
        "montsq": t_montsq,
        "is_zero_val": t_is_zero_val,
        "canonical_digits": t_canonical_digits,
        "select": t_select,
        "unpack_words": t_unpack_words,
    }


# --- curve canonicalization atomics -----------------------------------------


def make_curve_transfers(fp):
    """``_canonical_mod_p`` correlates a ≥ k·p test with the matching
    subtraction (a jnp.where whose two branches are NOT independent), so
    a compositional join would include spurious negative values; its
    exact contract is |v| < 8p → canonical digits of v mod p.
    ``_bytes_to_canonical`` masks the top byte to 0x1F and appends a
    zero 13th word, so its output value is < 2^381 — a bound invisible
    to a per-op abstraction of the word shuffle."""

    top_p = int((fp.p - 1) >> (fp.limb_bits * (fp.nlimbs - 1)))

    def t_canonical_mod_p(a):
        eng = engine.CURRENT
        a = _as_limb(a, fp)
        hull = a.val.hull(eng.tab)
        viol = []
        if not (-fp.iszero_pre < hull[0] and hull[1] < fp.iszero_pre):
            viol.append(
                f"_canonical_mod_p operand value bound [{_fmt(hull[0])}p"
                f", {_fmt(hull[1])}p] exceeds the |v| < "
                f"{int(fp.iszero_pre)}p precondition (theorem c)"
            )
        acc = 2 * max(a.dmag + fp.mask, a.tmag + fp.mask) + 1
        if acc >= INT32_LIM:
            viol.append(f"canonicalization ripple bound {acc} >= 2^31 "
                        f"(theorem a)")
        _rec("canonmodp", fp, op_hull=_hmag(hull), pre=fp.iszero_pre,
             max_acc=acc, violations=viol)
        return LimbVal(fp, a.shape, a.limb_axis, fp.mask, top_p, True,
                       True, _fresh_hull(Fraction(0),
                                         Fraction(fp.p - 1, fp.p)))

    def t_bytes_to_canonical(payload):
        engine.CURRENT.recorder.assume(
            "_bytes_to_canonical: the 48-byte payload has its top byte "
            "masked to 0x1F by the caller, so the packed value is "
            "< 2^381"
        )
        batch = tuple(getattr(payload, "shape", ()))[:-1]
        hi = Fraction((1 << 381) - 1, fp.p)
        top = ((1 << 381) - 1) >> (fp.limb_bits * (fp.nlimbs - 1))
        return LimbVal(fp, (fp.nlimbs,) + batch, 0, fp.mask, top, True,
                       True, _fresh_hull(Fraction(0), hi))

    return {
        "_canonical_mod_p": t_canonical_mod_p,
        "_bytes_to_canonical": t_bytes_to_canonical,
    }
