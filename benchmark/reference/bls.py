"""BLS signatures (min_pk: 48-byte public keys in G1, 96-byte signatures in
G2) as far as the benchmark needs them: point serialisation, and the
verification of one aggregate over one message,

    e(-g1, sig) * e(sum of the members' keys, H(message)) == 1,

with the signature decompressed and checked for membership of G2 first.

Point serialisation is the ZCash/Ethereum compressed encoding (flag bits in
the top three bits of the first byte; the Fp2 x-coordinate as c1 then c0).
Plain Python throughout; nothing of the program is imported.
"""

from __future__ import annotations

from typing import Sequence

from benchmark.reference import constants
from benchmark.reference.curves import (
    B2,
    G1,
    Point,
    g1_infinity,
    g2_infinity,
)
from benchmark.reference.fields import Fq, Fq2
from benchmark.reference.hash_to_curve import hash_to_g2

P = constants.P

_COMPRESSED_FLAG = 0x80
_INFINITY_FLAG = 0x40
_SIGN_FLAG = 0x20


class BlsError(ValueError):
    pass


def g1_to_bytes(p: Point[Fq]) -> bytes:
    if p.is_infinity():
        return bytes([_COMPRESSED_FLAG | _INFINITY_FLAG]) + b"\x00" * 47
    x, y = p.to_affine()
    flags = _COMPRESSED_FLAG
    if y.n > P - y.n:
        flags |= _SIGN_FLAG
    raw = x.n.to_bytes(48, "big")
    return bytes([raw[0] | flags]) + raw[1:]


def _fq2_lex_larger(y: Fq2) -> bool:
    neg = -y
    return (y.c1.n, y.c0.n) > (neg.c1.n, neg.c0.n)


def g2_to_bytes(p: Point[Fq2]) -> bytes:
    if p.is_infinity():
        return bytes([_COMPRESSED_FLAG | _INFINITY_FLAG]) + b"\x00" * 95
    x, y = p.to_affine()
    flags = _COMPRESSED_FLAG
    if _fq2_lex_larger(y):
        flags |= _SIGN_FLAG
    raw = x.c1.n.to_bytes(48, "big") + x.c0.n.to_bytes(48, "big")
    return bytes([raw[0] | flags]) + raw[1:]


def g2_from_bytes(data: bytes, subgroup_check: bool = True) -> Point[Fq2]:
    if len(data) != 96:
        raise BlsError("G2 compressed point must be 96 bytes")
    flags = data[0]
    if not flags & _COMPRESSED_FLAG:
        raise BlsError("uncompressed G2 encoding not supported")
    if flags & _INFINITY_FLAG:
        if (flags & ~(_COMPRESSED_FLAG | _INFINITY_FLAG)) or any(data[1:]):
            raise BlsError("malformed G2 infinity encoding")
        return g2_infinity()
    c1 = int.from_bytes(bytes([data[0] & 0x1F]) + data[1:48], "big")
    c0 = int.from_bytes(data[48:96], "big")
    if c0 >= P or c1 >= P:
        raise BlsError("G2 x-coordinate out of range")
    x = Fq2.from_ints(c0, c1)
    y = (x.square() * x + B2).sqrt()
    if y is None:
        raise BlsError("G2 point not on curve")
    if bool(flags & _SIGN_FLAG) != _fq2_lex_larger(y):
        y = -y
    point = Point.from_affine(x, y, B2)
    if subgroup_check and not point.in_subgroup():
        raise BlsError("G2 point not in subgroup")
    return point


class PublicKey:
    __slots__ = ("point",)

    def __init__(self, point: Point[Fq]) -> None:
        self.point = point

    @staticmethod
    def aggregate(keys: "Sequence[PublicKey]") -> "PublicKey":
        acc = g1_infinity()
        for k in keys:
            acc = acc + k.point
        return PublicKey(acc)


class Signature:
    __slots__ = ("point",)

    def __init__(self, point: Point[Fq2]) -> None:
        self.point = point

    @staticmethod
    def from_bytes(data: bytes) -> "Signature":
        return Signature(g2_from_bytes(data, subgroup_check=True))

    def verify(self, message: bytes, public_key: PublicKey,
               dst: bytes = constants.DST_SIGNATURE) -> bool:
        """e(pk, H(m)) == e(g1, sig), as one product check."""
        from benchmark.reference.pairing import pairing_check

        if public_key.point.is_infinity():
            return False  # Eth2 rejects the identity public key
        return pairing_check(
            [(-G1, self.point), (public_key.point, hash_to_g2(message, dst))]
        )

    def fast_aggregate_verify(self, message: bytes,
                              public_keys: "Sequence[PublicKey]",
                              dst: bytes = constants.DST_SIGNATURE) -> bool:
        """All keys signed the same message (attestation aggregate)."""
        if not public_keys:
            return False
        if any(pk.point.is_infinity() for pk in public_keys):
            return False  # identity key would fake participation
        return self.verify(message, PublicKey.aggregate(public_keys), dst)
