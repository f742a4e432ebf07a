"""Validator keys in arithmetic progression, from the seed.

sk_v = (a + b*v) mod r, so the n public keys cost one G1 addition each and
a signature of validator v over a message with hash point H is
a*H + v*(b*H): two scalar multiplications per MESSAGE, then a handful of
additions per signer (copied in idea from bench.py `build_batch`,
tools/replay_bench.py `ApKeys` and chip_smoke.py `make_keys`; those
originals are listed in PERF.md for a later PR to delete). All arithmetic
is the benchmark's own plain BLS12-381 (benchmark/reference).
"""

from __future__ import annotations

import hashlib

from benchmark.reference import bls as ref
from benchmark.reference.constants import R
from benchmark.reference.curves import G1
from benchmark.reference.hash_to_curve import hash_to_g2


class ProgressionKeys:
    def __init__(self, n: int, seed: int) -> None:
        self.n = n
        self.a = self._draw(seed, b"a")
        self.b = self._draw(seed, b"b")
        self._points = self._bytes = None

    @staticmethod
    def _draw(seed: int, tag: bytes) -> int:
        h = hashlib.sha256(b"benchmark-keys|%d|" % seed + tag).digest()
        return int.from_bytes(h, "big") % (R - 1) + 1

    @property
    def points(self) -> list:
        """Public keys as curve points (the reference aggregates these);
        made on first use, since signing needs the scalars alone."""
        if self._points is None:
            points, acc, step = [], G1.mul(self.a), G1.mul(self.b)
            for _ in range(self.n):
                points.append(acc)
                acc = acc + step
            self._points = points
        return self._points

    def pubkey_bytes(self) -> "list[bytes]":
        if self._bytes is None:
            self._bytes = [ref.g1_to_bytes(p) for p in self.points]
        return self._bytes

    def sum_scalar(self, members) -> int:
        members = list(members)
        return (self.a * len(members) + self.b * sum(members)) % R


class MessageSigner:
    """Signatures of many validators over ONE message."""

    def __init__(self, keys: ProgressionKeys, message: bytes) -> None:
        self.keys = keys
        self.h = hash_to_g2(message)
        self._base = self.h.mul(keys.a)
        # powers[k] = 2^k * (b*H): v*(b*H) is then at most bit_length(v)
        # additions
        powers, acc = [], self.h.mul(keys.b)
        for _ in range(max(1, (keys.n - 1).bit_length())):
            powers.append(acc)
            acc = acc.double()
        self._powers = powers

    def single(self, v: int):
        """sk_v * H as a G2 point."""
        acc, k = self._base, 0
        while v:
            if v & 1:
                acc = acc + self._powers[k]
            v >>= 1
            k += 1
        return acc

    def aggregate(self, members):
        """(sum of the members' secret keys) * H: one scalar-mul."""
        return self.h.mul(self.keys.sum_scalar(members))
