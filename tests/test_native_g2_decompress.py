"""The native batch G2 decoder (`gt_g2_decompress_batch`, reached through
`crypto.bls.g2_from_bytes_batch`) against its differential reference,
`crypto.bls.g2_from_bytes(.., subgroup_check=False)`: the same point or
the same BlsError, item for item, and the first bad item's error for a
batch. No JAX here: the decoder is host code.
"""

import ctypes
import random
import threading

import pytest

from grandine_tpu import native
from grandine_tpu.crypto import bls as A
from grandine_tpu.crypto.constants import P
from grandine_tpu.crypto.curves import B2
from grandine_tpu.crypto.fields import Fq2

from g2_corpus import (
    COMPRESSED_FLAG,
    SIGN_FLAG,
    encode_x,
    g2_corpus,
    g2_corpus_extra,
)

needs_native = pytest.mark.skipif(
    native.lib is None, reason="no toolchain built the runtime library"
)

#: seeded on-curve points a chunk, decoded as one batch
CHUNK = 128
CHUNKS = 8  # 8 x 128 on-curve points: 1,024 seeded signatures


def verdict(decode, blob):
    """What a decoder makes of one item: its point's coordinates, or its
    error's text."""
    try:
        p = decode(blob)
    except A.BlsError as e:
        return ("error", str(e))
    return ("point", p.x, p.y, p.z, p.b)


def anchor(blob):
    return A.g2_from_bytes(blob, subgroup_check=False)


def batch_of_one(blob):
    (p,) = A.g2_from_bytes_batch([blob])
    return p


def seeded_points(seed, n):
    """n compressed on-curve points from `seed`: random x of Fq2 that have
    a y, under a random sign bit. Nearly all lie outside G2, which the
    decoder neither knows nor checks."""
    rng = random.Random(seed)
    out = []
    while len(out) < n:
        x = Fq2.from_ints(rng.randrange(P), rng.randrange(P))
        if (x.square() * x + B2).is_square():
            flags = COMPRESSED_FLAG | (SIGN_FLAG * rng.getrandbits(1))
            out.append(encode_x(x.c0.n, x.c1.n, flags))
    return out


def real_signatures(n):
    keys = [A.SecretKey.keygen(bytes([i + 1]) * 32) for i in range(n)]
    return [
        A.g2_to_bytes(k.sign(b"vote-%d" % i).point)
        for i, k in enumerate(keys)
    ]


CORPUS = [("corpus_%d" % i, b) for i, b in enumerate(g2_corpus())]
EDGES = CORPUS + g2_corpus_extra()


@needs_native
@pytest.mark.parametrize("blob", [b for _, b in EDGES],
                         ids=[name for name, _ in EDGES])
def test_edge_case_decodes_as_the_anchor_does(blob):
    want = verdict(anchor, blob)
    assert verdict(batch_of_one, blob) == want
    # and in the middle of a batch, where its offset is not 0
    good = CORPUS[1][1]
    try:
        got = A.g2_from_bytes_batch([good, blob, good])
    except A.BlsError as e:
        assert want == ("error", str(e))
    else:
        assert verdict(lambda _b: got[1], blob) == want
        assert verdict(lambda _b: got[2], good) == verdict(anchor, good)


@needs_native
def test_the_corpus_has_every_verdict():
    texts = {v[1] for v in (verdict(batch_of_one, b) for _, b in EDGES)
             if v[0] == "error"}
    assert texts == {
        "G2 compressed point must be 96 bytes",
        "uncompressed G2 encoding not supported",
        "malformed G2 infinity encoding",
        "G2 x-coordinate out of range",
        "G2 point not on curve",
    }
    outside = dict(EDGES)["on_curve_outside_g2"]
    point = batch_of_one(outside)
    assert point.is_on_curve() and not point.in_subgroup()
    with pytest.raises(A.BlsError, match="not in subgroup"):
        A.g2_from_bytes_batch([outside], subgroup_check=True)
    assert batch_of_one(A.g2_to_bytes(point)).is_on_curve()
    assert A.g2_from_bytes_batch([]) == []


@needs_native
@pytest.mark.parametrize("chunk", range(CHUNKS))
def test_seeded_signatures_decode_point_for_point(chunk):
    blobs = seeded_points(0x29_0000 + chunk, CHUNK)
    got = A.g2_from_bytes_batch(blobs)
    assert len(got) == CHUNK
    for blob, p in zip(blobs, got):
        assert verdict(lambda _b: p, blob) == verdict(anchor, blob)
        assert A.g2_to_bytes(p) == blob  # the sign bit's y, not the other


@needs_native
def test_real_signatures_decode_point_for_point():
    blobs = real_signatures(16)
    for blob, p in zip(blobs, A.g2_from_bytes_batch(blobs)):
        assert verdict(lambda _b: p, blob) == verdict(anchor, blob)


@needs_native
@pytest.mark.parametrize("seed", range(4))
def test_random_x_gets_the_anchors_verdict(seed):
    """On the curve or not, in range or not: 64 random 96-byte strings a
    seed under the compressed flag, each decoded alone."""
    rng = random.Random(0x29_1000 + seed)
    for _ in range(64):
        raw = bytearray(rng.randbytes(96))
        raw[0] = (raw[0] & 0x3F) | COMPRESSED_FLAG
        if rng.getrandbits(2):  # mostly below P: the curve decides
            raw[0] &= 0xEF
            raw[48] &= 0x0F
        blob = bytes(raw)
        assert verdict(batch_of_one, blob) == verdict(anchor, blob)


@needs_native
@pytest.mark.parametrize("first_bad", [0, 3, 7])
def test_a_batch_raises_its_first_bad_items_error(first_bad):
    edges = dict(EDGES)
    good = [b for _, b in CORPUS[:4]]
    bad = [edges["x_c0_is_p"], edges["too_short"], edges["no_flags"],
           edges["infinity_with_sign"], CORPUS[9][1]]
    for i, first in enumerate(bad):
        rest = bad[i + 1:] + bad[:i]
        batch = (good * 2)[:first_bad] + [first] + good + rest
        with pytest.raises(A.BlsError) as e:
            A.g2_from_bytes_batch(batch)
        assert ("error", str(e.value)) == verdict(anchor, first)


@pytest.mark.parametrize("path", ["native", "python"])
def test_the_library_alone_chooses_the_path(monkeypatch, path):
    """No switch: the batch entry is native where the library loaded and
    the anchor's loop where it did not, and both give the same points."""
    if path == "python":
        monkeypatch.setattr(native, "lib", None)
    elif native.lib is None:
        pytest.skip("no toolchain built the runtime library")
    assert A.g2_batch_path() == path
    called = []
    monkeypatch.setattr(
        A, "g2_from_bytes",
        lambda d, subgroup_check=True, _f=A.g2_from_bytes: (
            called.append(subgroup_check), _f(d, subgroup_check))[1],
    )
    blobs = [b for _, b in CORPUS[:6]]
    got = A.g2_from_bytes_batch(blobs)
    assert called == ([] if path == "native" else [False] * 6)
    monkeypatch.undo()
    assert [verdict(lambda _b: p, None) for p in got] == [
        verdict(anchor, b) for b in blobs]
    assert got[5].is_infinity()


@needs_native
def test_the_native_call_holds_no_gil():
    """The mechanism, not a timing: while one thread is inside
    `gt_g2_decompress_batch`, another thread's pure-Python counter
    advances for the length of the call. The control is the same symbol
    called through `ctypes.PyDLL`, which keeps the GIL as a Python `pow`
    does: there the counter gets one switch interval, when the call is
    over."""
    blobs = seeded_points(0x29_2000, 8)
    n = 4096  # some hundred milliseconds of native work
    data = b"".join(blobs) * (n // len(blobs))
    out, status = native.out_buf(n * 192), native.out_buf(n)
    held = ctypes.PyDLL(native.lib._name).gt_g2_decompress_batch
    held.argtypes = native.lib.gt_g2_decompress_batch.argtypes
    held.restype = None
    counter = [0]
    counting, stop = threading.Event(), threading.Event()

    def count():
        while not stop.is_set():
            counter[0] += 1
            counting.set()

    def progress_during(call):
        # both reads are this thread's, with the GIL: what lies between
        # them was counted while `call` ran, and just after
        before = counter[0]
        call(data, n, out, status)
        return counter[0] - before

    other = threading.Thread(target=count, daemon=True)
    other.start()
    try:
        assert counting.wait(10)
        with_gil = progress_during(held)
        without = progress_during(native.lib.gt_g2_decompress_batch)
    finally:
        stop.set()
        other.join(10)
    assert not other.is_alive()
    assert status.raw == b"\x00" * n
    assert without > 0
    # the call is ~100 switch intervals long
    assert without > 10 * with_gil, (without, with_gil)
