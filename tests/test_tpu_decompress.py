"""Differential tests: device batched G1/G2 decompression vs the
pure-Python anchor (`crypto.bls.g1_from_bytes` / `g2_from_bytes`).

The compressed-ingest plane's contract is BYTE-IDENTICAL verdicts: for
every blob the device masks must accept/reject exactly like the host
decoder, and accepted points must land on the same affine coordinates.
The edge corpus walks all three failure classes (non-canonical value
>= p, well-formed x with no curve point / non-residue, infinity flag
with a non-zero payload), the sign bit on both sqrt branches, and the
canonical infinity encoding.
"""

import random

import pytest

pytestmark = pytest.mark.kernel

import jax
import numpy as np

from grandine_tpu.crypto import bls as A
from grandine_tpu.crypto.constants import P
from grandine_tpu.crypto.curves import G1, g1_infinity
from grandine_tpu.crypto.hash_to_curve import hash_to_g2
from grandine_tpu.tpu import curve as C
from grandine_tpu.tpu import limbs as L

from g2_corpus import g2_corpus

rng = random.Random(0xDEC0)

# one compile per decompressor across the whole module — a fresh
# jax.jit per test would recompile the same ladder four times
_g1_jit = jax.jit(C.g1_decompress_dev)
_g2_jit = jax.jit(C.g2_decompress_dev)


def _host_verdict_g1(blob: bytes):
    try:
        p = A.g1_from_bytes(blob, subgroup_check=False)
        return True, p.is_infinity(), p
    except A.BlsError:
        return False, False, None


def _host_verdict_g2(blob: bytes):
    try:
        p = A.g2_from_bytes(blob, subgroup_check=False)
        return True, p.is_infinity(), p
    except A.BlsError:
        return False, False, None


def _g1_corpus():
    blobs = [A.g1_to_bytes(G1.mul(k)) for k in (1, 2, 3, 5, 1234567)]
    # opposite sqrt branch: same x, negated y — flips the sign bit
    flip = bytearray(blobs[0])
    flip[0] ^= C.SIGN_FLAG
    blobs.append(bytes(flip))
    blobs.append(A.g1_to_bytes(g1_infinity()))
    bad = []
    # compressed flag cleared
    b = bytearray(blobs[0])
    b[0] &= 0x7F
    bad.append(bytes(b))
    # non-canonical: x >= p
    enc = bytearray((P + 1).to_bytes(48, "big"))
    enc[0] |= C.COMPRESSED_FLAG
    bad.append(bytes(enc))
    # smallest non-residue x (x^3 + 4 has no sqrt): not on the curve
    x = 1
    while pow((x**3 + 4) % P, (P - 1) // 2, P) == 1:
        x += 1
    nr = bytearray(x.to_bytes(48, "big"))
    nr[0] |= C.COMPRESSED_FLAG
    bad.append(bytes(nr))
    # infinity flag on a non-zero payload
    ip = bytearray(blobs[0])
    ip[0] |= C.INFINITY_FLAG
    bad.append(bytes(ip))
    # infinity with the sign bit set (non-canonical infinity)
    isf = bytearray(48)
    isf[0] = C.COMPRESSED_FLAG | C.INFINITY_FLAG | C.SIGN_FLAG
    bad.append(bytes(isf))
    return blobs + bad


def test_g1_decompress_matches_host_on_edge_corpus():
    blobs = _g1_corpus()
    rows = C.compressed_rows(blobs, 48)
    x_d, y_d, inf, ok, bad_enc, bad_curve, bad_inf = _g1_jit(rows)
    for i, blob in enumerate(blobs):
        h_ok, h_inf, hp = _host_verdict_g1(blob)
        assert bool(ok[i]) == h_ok, (i, "accept verdict diverged")
        assert bool(inf[i]) == h_inf, (i, "infinity verdict diverged")
        if h_ok and not h_inf:
            ax, ay = hp.to_affine()
            gx = L.from_mont(np.asarray(x_d[:, i])) % P
            gy = L.from_mont(np.asarray(y_d[:, i])) % P
            assert (gx, gy) == (ax.n, ay.n), (i, "coords diverged")
    # the three failure classes are each exercised and disjoint from ok
    assert int(np.asarray(bad_enc).sum()) >= 2  # flag cleared, x >= p
    assert int(np.asarray(bad_curve).sum()) >= 1  # non-residue x
    assert int(np.asarray(bad_inf).sum()) >= 2  # junk payload, sign bit
    assert not np.asarray(
        ok & (bad_enc | bad_curve | bad_inf)
    ).any(), "a row is both accepted and failed"


def test_g2_decompress_matches_host_on_edge_corpus():
    blobs = g2_corpus()
    rows = C.compressed_rows(blobs, 96)
    x_d, y_d, inf, ok, bad_enc, bad_curve, bad_inf = _g2_jit(rows)
    for i, blob in enumerate(blobs):
        h_ok, h_inf, hp = _host_verdict_g2(blob)
        assert bool(ok[i]) == h_ok, (i, "accept verdict diverged")
        assert bool(inf[i]) == h_inf, (i, "infinity verdict diverged")
        if h_ok and not h_inf:
            ax, ay = hp.to_affine()
            for comp, host in (
                (x_d[0][:, i], ax.c0.n),
                (x_d[1][:, i], ax.c1.n),
                (y_d[0][:, i], ay.c0.n),
                (y_d[1][:, i], ay.c1.n),
            ):
                assert L.from_mont(np.asarray(comp)) % P == host, (
                    i, "coords diverged"
                )
    assert int(np.asarray(bad_enc).sum()) >= 3
    assert int(np.asarray(bad_curve).sum()) >= 1
    assert int(np.asarray(bad_inf).sum()) >= 1


def test_g1_roundtrip_property_fuzz():
    """compress -> device decompress -> recompress == identity over
    random scalar multiples (both sqrt branches land here: the sign bit
    is data-dependent on y's parity)."""
    pts = [G1.mul(rng.randrange(1, 1 << 64)) for _ in range(12)]
    blobs = [A.g1_to_bytes(p) for p in pts]
    rows = C.compressed_rows(blobs, 48)
    x_d, y_d, inf, ok, *_ = _g1_jit(rows)
    assert bool(np.asarray(ok).all()) and not np.asarray(inf).any()
    for i, p in enumerate(pts):
        ax, ay = p.to_affine()
        gx = L.from_mont(np.asarray(x_d[:, i])) % P
        gy = L.from_mont(np.asarray(y_d[:, i])) % P
        assert (gx, gy) == (ax.n, ay.n)
        # recompress from the device coordinates: byte-identical wire
        sign = 1 if gy > (P - 1) // 2 else 0
        enc = bytearray(gx.to_bytes(48, "big"))
        enc[0] |= C.COMPRESSED_FLAG | (C.SIGN_FLAG if sign else 0)
        assert bytes(enc) == blobs[i]


def test_g2_roundtrip_property_fuzz():
    pts = [hash_to_g2(b"fuzz-%d" % rng.getrandbits(32)) for _ in range(11)]
    blobs = [A.g2_to_bytes(p) for p in pts]
    rows = C.compressed_rows(blobs, 96)
    x_d, y_d, inf, ok, *_ = _g2_jit(rows)
    assert bool(np.asarray(ok).all()) and not np.asarray(inf).any()
    for i, p in enumerate(pts):
        ax, ay = p.to_affine()
        got = (
            L.from_mont(np.asarray(x_d[0][:, i])) % P,
            L.from_mont(np.asarray(x_d[1][:, i])) % P,
            L.from_mont(np.asarray(y_d[0][:, i])) % P,
            L.from_mont(np.asarray(y_d[1][:, i])) % P,
        )
        assert got == (ax.c0.n, ax.c1.n, ay.c0.n, ay.c1.n)


def test_compressed_rows_rejects_wire_length():
    with pytest.raises(ValueError):
        C.compressed_rows([b"\x80" * 47], 48)
    with pytest.raises(ValueError):
        C.compressed_rows([b"\x80" * 95], 96)
    flags = C.compressed_infinity_flags(
        C.compressed_rows([b"\xc0" + b"\x00" * 47], 48)
    )
    assert list(flags) == [True]
