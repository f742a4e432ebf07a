"""BLS12-381 curve groups G1 (E/Fp: y²=x³+4) and G2 (E'/Fp2: y²=x³+4(1+u)).

Jacobian-coordinate point arithmetic generic over the coordinate field
(Fq for G1, Fq2 for G2), plus subgroup checks and cofactor clearing.

Reference equivalents: blst's G1/G2 ops wrapped by `bls/src/public_key.rs`
(aggregation :35-55, subgroup validate :21-27) and `bls/src/secret_key.rs:82-86`
(signing = G2 scalar-mul). The TPU batched versions live in
grandine_tpu/tpu/curve.py and are differentially tested against this file.
"""

from __future__ import annotations

from typing import Generic, TypeVar

from benchmark.reference import constants
from benchmark.reference.fields import Fq, Fq2

F = TypeVar("F", Fq, Fq2)


class Point(Generic[F]):
    """Jacobian point (X, Y, Z): affine (X/Z², Y/Z³); Z=0 ⇒ infinity.

    `b` is the curve coefficient (y² = x³ + b); carried on the point so G1
    and G2 share one implementation.
    """

    __slots__ = ("x", "y", "z", "b")

    def __init__(self, x: F, y: F, z: F, b: F) -> None:
        self.x = x
        self.y = y
        self.z = z
        self.b = b

    # -- constructors ------------------------------------------------------
    @staticmethod
    def infinity(b: F) -> "Point[F]":
        one = b.__class__.one()
        return Point(one, one, b.__class__.zero(), b)

    @staticmethod
    def from_affine(x: F, y: F, b: F) -> "Point[F]":
        return Point(x, y, b.__class__.one(), b)

    # -- predicates --------------------------------------------------------
    def is_infinity(self) -> bool:
        return self.z.is_zero()

    def is_on_curve(self) -> bool:
        """Jacobian curve equation: Y² = X³ + b·Z⁶."""
        if self.is_infinity():
            return True
        z2 = self.z.square()
        z6 = z2.square() * z2
        return self.y.square() == self.x.square() * self.x + self.b * z6

    # -- affine view -------------------------------------------------------
    def to_affine(self) -> "tuple[F, F] | None":
        if self.is_infinity():
            return None
        zinv = self.z.inv()
        zinv2 = zinv.square()
        return (self.x * zinv2, self.y * zinv2 * zinv)

    # -- group law ---------------------------------------------------------
    def double(self) -> "Point[F]":
        if self.is_infinity() or self.y.is_zero():
            return Point.infinity(self.b)
        x, y, z = self.x, self.y, self.z
        a = x.square()
        bq = y.square()
        c = bq.square()
        t = (x + bq).square() - a - c
        d = t + t  # 4·x·y²
        e = a + a + a  # 3x²  (curve a-coefficient is 0)
        f = e.square()
        x3 = f - d - d
        eight_c = c + c
        eight_c = eight_c + eight_c
        eight_c = eight_c + eight_c
        y3 = e * (d - x3) - eight_c
        z3 = (y * z) + (y * z)
        return Point(x3, y3, z3, self.b)

    def __add__(self, o: "Point[F]") -> "Point[F]":
        if self.is_infinity():
            return o
        if o.is_infinity():
            return self
        z1z1 = self.z.square()
        z2z2 = o.z.square()
        u1 = self.x * z2z2
        u2 = o.x * z1z1
        s1 = self.y * o.z * z2z2
        s2 = o.y * self.z * z1z1
        if u1 == u2:
            if s1 == s2:
                return self.double()
            return Point.infinity(self.b)
        h = u2 - u1
        i = (h + h).square()
        j = h * i
        rr = (s2 - s1) + (s2 - s1)
        v = u1 * i
        x3 = rr.square() - j - v - v
        y3 = rr * (v - x3) - (s1 * j) - (s1 * j)
        z3 = ((self.z + o.z).square() - z1z1 - z2z2) * h
        return Point(x3, y3, z3, self.b)

    def __neg__(self) -> "Point[F]":
        return Point(self.x, -self.y, self.z, self.b)

    def __sub__(self, o: "Point[F]") -> "Point[F]":
        return self + (-o)

    def mul(self, k: int) -> "Point[F]":
        """Scalar multiplication (double-and-add; variable-time — fine for
        verification of public data; see SURVEY.md §7 on signing side-channels)."""
        if k < 0:
            return (-self).mul(-k)
        result = Point.infinity(self.b)
        base = self
        while k:
            if k & 1:
                result = result + base
            base = base.double()
            k >>= 1
        return result

    # -- subgroup ----------------------------------------------------------
    def in_subgroup(self) -> bool:
        """r-torsion membership by the definition: [r]P is the identity
        (a 255-bit ladder; the program's own check goes by an
        endomorphism instead)."""
        return self.mul(constants.R).is_infinity()

    def __eq__(self, o: object) -> bool:
        if not isinstance(o, Point):
            return NotImplemented
        if self.is_infinity() or o.is_infinity():
            return self.is_infinity() and o.is_infinity()
        z1z1 = self.z.square()
        z2z2 = o.z.square()
        return (
            self.x * z2z2 == o.x * z1z1
            and self.y * o.z * z2z2 == o.y * self.z * z1z1
        )

    def __repr__(self) -> str:
        aff = self.to_affine()
        return f"Point({aff!r})"


# --- canonical generators and curve parameters -----------------------------

B1 = Fq(constants.B_G1)
B2 = Fq2.from_ints(*constants.B_G2)

G1 = Point.from_affine(Fq(constants.G1_X), Fq(constants.G1_Y), B1)
G2 = Point.from_affine(
    Fq2.from_ints(*constants.G2_X), Fq2.from_ints(*constants.G2_Y), B2
)


def g1_infinity() -> Point[Fq]:
    return Point.infinity(B1)


def g2_infinity() -> Point[Fq2]:
    return Point.infinity(B2)


def clear_cofactor_g2(p: Point[Fq2]) -> Point[Fq2]:
    """h_eff·P, the scalar of RFC 9380 §8.8.2 (NOT the full twist
    cofactor h2), by one plain ladder."""
    return p.mul(constants.H_EFF_G2)
