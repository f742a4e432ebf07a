"""Compressed-G2 edge cases shared by the decoders' differential tests:
the device's (tests/test_tpu_decompress.py, kernel tier, JAX) and the
native batch decoder's (tests/test_native_g2_decompress.py, no JAX). The
reference for both is `crypto.bls.g2_from_bytes(.., subgroup_check=False)`.
"""

from grandine_tpu.crypto import bls as A
from grandine_tpu.crypto.bls import _COMPRESSED_FLAG as COMPRESSED_FLAG
from grandine_tpu.crypto.bls import _INFINITY_FLAG as INFINITY_FLAG
from grandine_tpu.crypto.bls import _SIGN_FLAG as SIGN_FLAG
from grandine_tpu.crypto.constants import P
from grandine_tpu.crypto.curves import B2, g2_infinity
from grandine_tpu.crypto.fields import Fq, Fq2
from grandine_tpu.crypto.hash_to_curve import hash_to_g2


def encode_x(c0: int, c1: int, flags: int = COMPRESSED_FLAG) -> bytes:
    """The 96 wire bytes of an x = c0 + c1·u (either may be >= P, up to
    what 48 bytes hold) under `flags`."""
    raw = bytearray(c1.to_bytes(48, "big") + c0.to_bytes(48, "big"))
    raw[0] |= flags
    return bytes(raw)


def _rhs(x: Fq2) -> Fq2:
    return x.square() * x + B2


def g2_corpus():
    blobs = [A.g2_to_bytes(hash_to_g2(b"corpus-%d" % i)) for i in range(4)]
    # opposite sqrt branch in Fq2
    flip = bytearray(blobs[0])
    flip[0] ^= SIGN_FLAG
    blobs.append(bytes(flip))
    blobs.append(A.g2_to_bytes(g2_infinity()))
    bad = []
    b = bytearray(blobs[0])
    b[0] &= 0x7F
    bad.append(bytes(b))
    # non-canonical c1 (leading half) and c0 (trailing half)
    bad.append(encode_x(0, P + 2))
    bad.append(encode_x(P + 2, 0))
    # x whose rhs = x^3 + 4(1+i) is a non-residue in Fq2
    c0v = 0
    found = None
    while found is None:
        c0v += 1
        xx = Fq2.from_ints(c0v, 3)
        if _rhs(xx).sqrt() is None:
            found = xx
    bad.append(encode_x(found.c0.n, found.c1.n))
    ip = bytearray(blobs[0])
    ip[0] |= INFINITY_FLAG
    bad.append(bytes(ip))
    return blobs + bad


def _x_with_real_rhs():
    """Two x whose x^3 + 4(1+u) has c1 = 0: one with c0 a residue of Fq
    (the root is real), one with c0 a non-residue (the root is s·u). The
    single-coordinate branch of the square root."""
    want = {}
    x1 = 0
    while len(want) < 2:
        x1 += 1
        # c1 of the rhs: 3·x0²·x1 − x1³ + 4 = 0
        x0 = (Fq(x1 ** 3 - 4) * Fq(3 * x1).inv()).sqrt()
        if x0 is None:
            continue
        for x in (Fq2(x0, Fq(x1)), Fq2(-x0, Fq(x1))):
            rhs = _rhs(x)
            assert rhs.c1.is_zero()
            want.setdefault(rhs.c0.is_square(), x)
    return want[True], want[False]


def g2_corpus_extra():
    """(name, wire bytes) of what `g2_corpus` lacks."""
    sig = g2_corpus()[0]
    residue, non_residue = _x_with_real_rhs()
    cases = [
        ("x_c0_is_p", encode_x(P, 1)),
        ("x_c1_is_p", encode_x(1, P)),
        ("x_c0_is_p_minus_1", encode_x(P - 1, 1)),
        ("x_c1_is_p_minus_1", encode_x(1, P - 1)),
        ("x_c1_all_ones", encode_x(1, (1 << 381) - 1)),
        ("x_zero", encode_x(0, 0)),
        ("uncompressed_infinity", encode_x(0, 0, INFINITY_FLAG)),
        ("no_flags", encode_x(2, 1, 0)),
        ("infinity_with_sign", encode_x(0, 0, 0xE0)),
        ("infinity_with_low_bit_of_byte_0",
         bytes([0xC1]) + b"\x00" * 95),
        ("infinity_with_last_byte", encode_x(1, 0, 0xC0)),
        ("infinity_with_byte_48", encode_x(1 << 376, 0, 0xC0)),
        ("rhs_real_residue", encode_x(residue.c0.n, residue.c1.n)),
        ("rhs_real_residue_signed",
         encode_x(residue.c0.n, residue.c1.n, COMPRESSED_FLAG | SIGN_FLAG)),
        ("rhs_real_non_residue",
         encode_x(non_residue.c0.n, non_residue.c1.n)),
        ("rhs_real_non_residue_signed",
         encode_x(non_residue.c0.n, non_residue.c1.n,
                  COMPRESSED_FLAG | SIGN_FLAG)),
        ("sign_set", bytes([sig[0] | SIGN_FLAG]) + sig[1:]),
        ("sign_clear", bytes([sig[0] & ~SIGN_FLAG & 0xFF]) + sig[1:]),
        ("too_short", sig[:95]),
        ("too_long", sig + b"\x00"),
        ("empty", b""),
    ]
    # on the curve and outside G2: it has to DECODE (the device's ψ ladder
    # is what refuses it), and not be in the subgroup
    c0 = 0
    while True:
        c0 += 1
        x = Fq2.from_ints(c0, 1)
        if _rhs(x).sqrt() is not None:
            break
    cases.append(("on_curve_outside_g2", encode_x(c0, 1)))
    return cases
