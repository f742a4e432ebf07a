"""Hash-to-curve for BLS12-381 G2, following the RFC 9380 structure:

    hash_to_field (expand_message_xmd/SHA-256) → map_to_curve → clear_cofactor

G2 implements the canonical Ethereum suite BLS12381G2_XMD:SHA-256_SSWU_RO_
exactly: simplified SWU on the 3-isogenous curve E' (RFC 9380 §6.6.3,
constants §8.8.2 / Appendix E.3) followed by the published 3-isogeny back to
E and h_eff cofactor clearing. Known-answer conformance vectors:
tests/test_rfc9380_vectors.py (Appendix J.10.1 / K.1).

Reference equivalent: blst's hash-to-G2 invoked by `SecretKey::sign`
(bls/src/secret_key.rs:82-86) and by all verify paths.
"""

from __future__ import annotations

import hashlib

from benchmark.reference import constants
from benchmark.reference.curves import B2, Point, clear_cofactor_g2
from benchmark.reference.fields import Fq, Fq2

_B_IN_BYTES = 32  # SHA-256 output size
_R_IN_BYTES = 64  # SHA-256 block size
_L = 64  # ceil((381 + 128) / 8)


def expand_message_xmd(msg: bytes, dst: bytes, len_in_bytes: int) -> bytes:
    """RFC 9380 §5.3.1 expand_message_xmd with SHA-256."""
    ell = (len_in_bytes + _B_IN_BYTES - 1) // _B_IN_BYTES
    if ell > 255 or len_in_bytes > 65535 or len(dst) > 255:
        raise ValueError("expand_message_xmd parameter out of range")
    dst_prime = dst + len(dst).to_bytes(1, "big")
    z_pad = b"\x00" * _R_IN_BYTES
    l_i_b_str = len_in_bytes.to_bytes(2, "big")
    msg_prime = z_pad + msg + l_i_b_str + b"\x00" + dst_prime
    b0 = hashlib.sha256(msg_prime).digest()
    b = hashlib.sha256(b0 + b"\x01" + dst_prime).digest()
    uniform = b
    prev = b
    for i in range(2, ell + 1):
        prev = hashlib.sha256(
            bytes(x ^ y for x, y in zip(b0, prev)) + i.to_bytes(1, "big") + dst_prime
        ).digest()
        uniform += prev
    return uniform[:len_in_bytes]


def hash_to_field_fq2(msg: bytes, dst: bytes, count: int) -> "list[Fq2]":
    """RFC 9380 §5.2 hash_to_field with m=2, L=64."""
    len_in_bytes = count * 2 * _L
    uniform = expand_message_xmd(msg, dst, len_in_bytes)
    out = []
    for i in range(count):
        comps = []
        for j in range(2):
            off = _L * (j + i * 2)
            comps.append(int.from_bytes(uniform[off : off + _L], "big") % constants.P)
        out.append(Fq2.from_ints(*comps))
    return out


_SSWU_A = Fq2.from_ints(*constants.SSWU_A_G2)
_SSWU_B = Fq2.from_ints(*constants.SSWU_B_G2)
_SSWU_Z = Fq2.from_ints(*constants.SSWU_Z_G2)
_ISO3_K1 = tuple(Fq2.from_ints(*k) for k in constants.ISO3_K1)
_ISO3_K2 = tuple(Fq2.from_ints(*k) for k in constants.ISO3_K2)
_ISO3_K3 = tuple(Fq2.from_ints(*k) for k in constants.ISO3_K3)
_ISO3_K4 = tuple(Fq2.from_ints(*k) for k in constants.ISO3_K4)


def _map_to_curve_sswu_g2(u: Fq2) -> "tuple[Fq2, Fq2]":
    """RFC 9380 §6.6.2 simplified SWU onto E': y² = x³ + A'x + B'."""
    a, b, z = _SSWU_A, _SSWU_B, _SSWU_Z
    u2 = u.square()
    tv1 = z * u2
    tv2 = tv1.square() + tv1
    x1_num = b * (tv2 + Fq2.one())
    if tv2.is_zero():
        x1_den = a * z
    else:
        x1_den = -(a * tv2)
    # g(x) = x³ + a·x + b evaluated as fraction num/den³ to avoid inversions
    # is overkill for the anchor: invert directly (anchor favors clarity).
    x1 = x1_num * x1_den.inv()
    gx1 = x1.square() * x1 + a * x1 + b
    y = gx1.sqrt()
    if y is not None:
        x = x1
    else:
        x2 = tv1 * x1
        gx2 = x2.square() * x2 + a * x2 + b
        x, y = x2, gx2.sqrt()
    assert y is not None
    if u.sgn0() != y.sgn0():
        y = -y
    return x, y


def _horner(coeffs: "tuple[Fq2, ...]", x: Fq2) -> Fq2:
    acc = coeffs[-1]
    for c in reversed(coeffs[:-1]):
        acc = acc * x + c
    return acc


def _iso3_map(x: Fq2, y: Fq2) -> "tuple[Fq2, Fq2] | None":
    """The published 3-isogeny E' → E (RFC 9380 Appendix E.3).

    Returns None for inputs in the isogeny kernel (x_den/y_den = 0), which
    map to the identity — unreachable via hash_to_g2 (it would require
    inverting SHA-256) but map_to_curve_g2 accepts arbitrary field elements.
    """
    x_den = _horner(_ISO3_K2 + (Fq2.one(),), x)
    y_den = _horner(_ISO3_K4 + (Fq2.one(),), x)
    if x_den.is_zero() or y_den.is_zero():
        return None
    x_num = _horner(_ISO3_K1, x)
    y_num = _horner(_ISO3_K3, x)
    return x_num * x_den.inv(), y * y_num * y_den.inv()


def map_to_curve_g2(u: Fq2) -> Point[Fq2]:
    """SSWU + 3-isogeny — the BLS12381G2_XMD:SHA-256_SSWU_RO_ map."""
    xp, yp = _map_to_curve_sswu_g2(u)
    image = _iso3_map(xp, yp)
    if image is None:
        return Point.infinity(B2)
    x, y = image
    return Point.from_affine(x, y, B2)


def hash_to_g2(msg: bytes, dst: bytes = constants.DST_SIGNATURE) -> Point[Fq2]:
    """hash_to_curve for G2 (random-oracle construction: two maps + add)."""
    u0, u1 = hash_to_field_fq2(msg, dst, 2)
    q = map_to_curve_g2(u0) + map_to_curve_g2(u1)
    return clear_cofactor_g2(q)
