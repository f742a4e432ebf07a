"""Grouped (message-deduplicated) batch verification: the
grouped_multi_verify_msm_kernel and the backend's automatic grouping path.

The grouping identity ∏ᵢ e(rᵢ·pkᵢ, H(mᵢ)) = ∏ⱼ e(Σᵢ∈ⱼ rᵢ·pkᵢ, H(mⱼ))
collapses Miller loops to the distinct-message count — this suite pins its
policy equivalence with the flat path / anchor."""

import numpy as np
import pytest

pytestmark = pytest.mark.kernel

from grandine_tpu.crypto import bls as A
from grandine_tpu.tpu.bls import TpuBlsBackend


@pytest.fixture(scope="module")
def backend():
    return TpuBlsBackend()


@pytest.fixture(scope="module")
def triples():
    msgs = [b"grouped-%d" % (i % 2) for i in range(8)]  # 2 distinct msgs
    sks = [A.SecretKey.keygen(bytes([40 + i]) * 32) for i in range(8)]
    sigs = [sk.sign(m) for sk, m in zip(sks, msgs)]
    pks = [sk.public_key() for sk in sks]
    return msgs, sigs, pks


@pytest.mark.slow
def test_grouped_path_taken_and_accepts(backend, triples, monkeypatch):
    msgs, sigs, pks = triples
    called = {}
    orig = backend._grouped_multi_verify_async

    def spy(*a, **kw):
        called["yes"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(backend, "_grouped_multi_verify_async", spy)
    assert backend.multi_verify(msgs, sigs, pks)
    assert called.get("yes"), "duplicate-message batch must take the grouped path"
    # anchor agreement
    assert A.multi_verify(msgs, sigs, pks)


@pytest.mark.slow
def test_grouped_rejects_bad_signature(backend, triples):
    msgs, sigs, pks = triples
    bad = list(sigs)
    bad[3] = sigs[4]  # same message group, wrong key's signature? ensure bad
    if msgs[3] == msgs[4]:
        bad[3] = A.SecretKey.keygen(b"\x99" * 32).sign(msgs[3])
    assert not backend.multi_verify(msgs, bad, pks)


@pytest.mark.slow
def test_grouped_rejects_cross_group_swap(backend, triples):
    msgs, sigs, pks = triples
    # swap two signatures across DIFFERENT message groups
    bad = list(sigs)
    bad[0], bad[1] = bad[1], bad[0]
    assert msgs[0] != msgs[1]
    assert not backend.multi_verify(msgs, bad, pks)


@pytest.mark.slow
def test_all_distinct_messages_stay_flat(backend, monkeypatch):
    """Slow tier: pays the flat-kernel compile to prove the verdict;
    the routing decision itself has the fast witness below."""
    msgs = [b"distinct-%d" % i for i in range(4)]
    sks = [A.SecretKey.keygen(bytes([60 + i]) * 32) for i in range(4)]
    sigs = [sk.sign(m) for sk, m in zip(sks, msgs)]
    pks = [sk.public_key() for sk in sks]

    def boom(*a, **kw):  # must not be called
        raise AssertionError("grouped path taken for distinct messages")

    monkeypatch.setattr(backend, "_grouped_multi_verify_async", boom)
    assert backend.multi_verify(msgs, sigs, pks)


class _FlatDispatch(Exception):
    """Sentinel: the flat kernel was about to be built."""


def test_distinct_messages_route_flat_without_kernel(backend, monkeypatch):
    """Fast routing witness for the slow flat-verdict test above: with
    all messages distinct the backend must NOT take the grouped path —
    asserted by intercepting the flat path at its kernel-build seam, so
    no compile is paid."""
    msgs = [b"route-%d" % i for i in range(4)]
    sks = [A.SecretKey.keygen(bytes([70 + i]) * 32) for i in range(4)]
    sigs = [sk.sign(m) for sk, m in zip(sks, msgs)]
    pks = [sk.public_key() for sk in sks]

    def boom(*a, **kw):
        raise AssertionError("grouped path taken for distinct messages")

    def flat_seam(*a, **kw):
        raise _FlatDispatch

    monkeypatch.setattr(backend, "_grouped_multi_verify_async", boom)
    monkeypatch.setattr(backend, "_jitted_msm", flat_seam)
    with pytest.raises(_FlatDispatch):
        backend.multi_verify(msgs, sigs, pks)


def test_duplicate_messages_route_grouped_without_kernel(
    backend, triples, monkeypatch
):
    """Fast routing witness for the slow grouped-verdict tests above:
    a duplicate-message batch must take the grouped path — asserted by
    intercepting the grouped seam before any kernel is built, so no
    compile is paid."""

    class _GroupedDispatch(Exception):
        pass

    def grouped_seam(*a, **kw):
        raise _GroupedDispatch

    msgs, sigs, pks = triples
    monkeypatch.setattr(
        backend, "_grouped_multi_verify_async", grouped_seam
    )
    with pytest.raises(_GroupedDispatch):
        backend.multi_verify(msgs, sigs, pks)
