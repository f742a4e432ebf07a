"""Device-resident pubkey registry + pipelined verify plane.

Three tiers in one module:
  - host-only unit tests: `_bucket` padding, the bounded `_LruCache`,
    registry lifecycle bookkeeping, controller staleness wiring;
  - a pipeline-overlap test driving the real AttestationVerifier with a
    stub backend whose settle is slow — the span timeline must show batch
    N+1's host_prep starting inside batch N's readback window;
  - kernel-tier differential tests (marked `kernel`): the index-gather
    verify kernels must agree with the upload-path kernels on the same
    batch, including after an incremental registry append and after an
    invalidation/refresh, with the warm path uploading no pubkey bytes.
"""

import random
import threading

import numpy as np
import pytest

from grandine_tpu.crypto import bls as A
from grandine_tpu.metrics import Metrics
from grandine_tpu.tpu.bls import (
    MAX_BUCKET,
    TpuBlsBackend,
    _JITTED,
    _LruCache,
    _bucket,
)
from grandine_tpu.tpu.registry import MIN_CAPACITY, DevicePubkeyRegistry

_seed_rng = random.Random(0x9E61)


def _rng_bytes(n: int) -> bytes:
    return bytes(_seed_rng.randrange(256) for _ in range(n))


class _Rng:
    """random.Random behind the secrets-style randbits interface the
    backend's RLC draw expects."""

    def __init__(self, seed: int) -> None:
        self._rng = random.Random(seed)

    def randbits(self, n: int) -> int:
        return self._rng.getrandbits(n)


# ------------------------------------------------------------- _bucket


def test_bucket_monotone_and_covers_range():
    prev = 0
    for n in range(1, 1025):
        b = _bucket(n)
        assert b >= n, "bucket must cover the batch"
        assert b >= prev, "buckets must be monotone in n"
        assert b & (b - 1) == 0, "buckets are powers of two"
        prev = b
    # lo floor and custom lo
    assert _bucket(1) == 4
    assert _bucket(1, lo=16) == 16


def test_bucket_covers_max_and_rejects_beyond():
    assert _bucket(MAX_BUCKET) == MAX_BUCKET
    assert _bucket(MAX_BUCKET - 1) == MAX_BUCKET
    with pytest.raises(ValueError):
        _bucket(MAX_BUCKET + 1)


# ------------------------------------------------------------ LRU cache


def test_lru_cache_bound_eviction_and_metrics():
    m = Metrics()
    c = _LruCache(3, "testcache", metrics=m)
    for i in range(5):
        c.put(i, i * 10)
    assert len(c) == 3
    ev = m.device_cache_events.value
    assert ev("testcache", "evict") == 2
    assert m.device_cache_size.value("testcache") == 3
    # oldest entries evicted, newest retained
    assert c.get(0) is None and c.get(1) is None
    assert c.get(4) == 40
    assert ev("testcache", "miss") == 2 and ev("testcache", "hit") == 1
    # LRU order: touching 2 protects it from the next eviction
    c.get(2)
    c.put(99, 990)
    assert c.get(2) == 20
    assert c.get(3) is None  # 3 was the least recent → evicted


def test_backend_h2c_cache_is_bounded():
    m = Metrics()
    backend = TpuBlsBackend(metrics=m)
    backend._h2c_cache.cap = 2  # shrink for the test
    for i in range(4):
        backend._hash_to_g2_dev(b"h2c-%d" % i, b"dst")
    assert len(backend._h2c_cache) == 2
    # repeat of the newest is a hit, no growth
    backend._hash_to_g2_dev(b"h2c-3", b"dst")
    assert len(backend._h2c_cache) == 2
    assert m.device_cache_events.value("hash_to_g2_dev", "hit") == 1
    assert m.device_cache_events.value("hash_to_g2_dev", "evict") == 2


# ------------------------------------------------- registry bookkeeping


def _fresh_keypairs(n: int):
    sks = [A.SecretKey.keygen(_rng_bytes(32)) for _ in range(n)]
    return sks, tuple(sk.public_key().to_bytes() for sk in sks)


def test_registry_lifecycle_hit_append_refresh():
    m = Metrics()
    reg = DevicePubkeyRegistry(metrics=m)
    _, pkb = _fresh_keypairs(5)
    assert not reg.ensure(())  # empty set: unusable
    first3 = pkb[:3]  # the SAME tuple object, as head-state columns are
    assert reg.ensure(first3)
    assert reg.count == 3 and reg.capacity == MIN_CAPACITY
    assert reg.stats["refreshes"] == 1
    # identity re-ensure is a free hit
    assert reg.ensure(first3)
    assert reg.stats["hits"] == 1
    # prefix growth appends without a refresh
    assert reg.ensure(pkb)
    assert reg.count == 5
    assert reg.stats["appends"] == 1 and reg.stats["refreshes"] == 1
    # same content under a NEW tuple object: miss, prefix-adopt, then hit
    clone = tuple(bytes(b) for b in pkb)
    assert clone is not pkb and reg.ensure(clone)
    assert reg.stats["appends"] == 1 and reg.stats["refreshes"] == 1
    assert reg.ensure(clone) and reg.stats["hits"] >= 2
    # mark_stale demotes the identity fast path exactly once
    reg.mark_stale()
    misses_before = reg.stats["misses"]
    assert reg.ensure(clone)
    assert reg.stats["misses"] == misses_before + 1
    assert reg.ensure(clone)
    assert reg.stats["misses"] == misses_before + 1  # hit again
    # a NON-prefix set forces a refresh
    _, other = _fresh_keypairs(2)
    assert reg.ensure(other)
    assert reg.count == 2 and reg.stats["refreshes"] == 2
    # invalidate drops everything
    reg.invalidate()
    assert reg.count == 0 and reg.capacity == 0
    assert m.pubkey_registry_events.value("invalidate") == 1
    assert m.pubkey_registry_size.value == 0


def test_registry_append_uploads_only_new_rows():
    m = Metrics()
    reg = DevicePubkeyRegistry(metrics=m)
    _, pkb = _fresh_keypairs(6)
    assert reg.ensure(pkb[:4])
    base = reg.stats["uploaded_bytes"]
    assert reg.ensure(pkb)  # +2 rows, within MIN_CAPACITY
    from grandine_tpu.tpu.registry import _next_pow2

    # compressed ingest: the append ships the RAW 48-byte rows (padded
    # to the decompress kernel's bucket), not decompressed limb planes
    assert reg.stats["uploaded_bytes"] - base == _next_pow2(2) * 48
    assert m.device_upload_bytes.value("pubkey_registry") == (
        reg.stats["uploaded_bytes"]
    )
    # host mirror serves the fallback path
    pks = reg.public_keys([5, 0])
    assert pks[0].to_bytes() == pkb[5] and pks[1].to_bytes() == pkb[0]


def test_registry_compressed_ingest_upload_ratio():
    """The compressed-ingest plane's traffic win, pinned: a registry
    build moves 48 B/row of wire bytes where the host-decompress path
    moved the 2 × NLIMBS × 4 B affine limb planes — a ≥ 3× (208/48 ≈
    4.3×) per-row drop in device_upload_bytes_total."""
    import grandine_tpu.tpu.limbs as L

    m = Metrics()
    reg = DevicePubkeyRegistry(metrics=m)
    _, pkb = _fresh_keypairs(6)
    assert reg.ensure(pkb)
    cap = reg.capacity
    raw_bytes = m.device_upload_bytes.value("pubkey_registry")
    assert raw_bytes == cap * 48  # one full raw upload at capacity
    limb_bytes = cap * 2 * L.NLIMBS * 4  # what the limb plane would move
    assert limb_bytes >= 3 * raw_bytes, (
        f"per-row upload {raw_bytes / cap:.0f} B is not a >=3x drop from "
        f"the {limb_bytes / cap:.0f} B limb plane"
    )


def test_verifier_wires_registry_staleness_hook():
    from grandine_tpu.consensus.verifier import NullVerifier
    from grandine_tpu.runtime import AttestationVerifier, Controller
    from grandine_tpu.transition.genesis import interop_genesis_state
    from grandine_tpu.types.config import Config

    cfg = Config.minimal()
    genesis = interop_genesis_state(32, cfg)
    ctrl = Controller(genesis, cfg, verifier_factory=NullVerifier)
    verifier = AttestationVerifier(ctrl, use_device=True, deadline_s=0.01)
    try:
        assert verifier.registry is not None
        assert ctrl.snapshot().validator_count == 32
        assert len(ctrl.on_validator_set_change) == 1
        _, pkb = _fresh_keypairs(2)
        assert verifier.registry.ensure(pkb)
        assert verifier.registry._stale is False
        # the controller-side hook demotes the next ensure to a recheck
        ctrl.on_validator_set_change[0](None, ctrl.snapshot())
        assert verifier.registry._stale is True
    finally:
        verifier.stop()
        ctrl.stop()


# ------------------------------------------------------ pipeline overlap


class _HandshakeBackend:
    """Async-seam stub: dispatch returns instantly; the settle of batch k
    (inside a `readback` span) does not return before batch k+1 has been
    DISPATCHED, which only a pipeline at least two deep lets happen. No
    sleep decides anything: `waited[k]` says whether the next dispatch
    came while settle k was held (the timeout only ends a run whose
    pipeline does not overlap)."""

    def __init__(self, tracer, batches: int, timeout_s: float = 20.0) -> None:
        self.tracer = tracer
        self.timeout_s = timeout_s
        self.dispatches = 0
        self.dispatched = [threading.Event() for _ in range(batches + 1)]
        self.dispatched[batches].set()  # the last batch has no successor
        self.waited: "list[bool]" = []

    def g2_subgroup_check_batch_async(self, points):
        n = len(points)
        return lambda: np.ones((n,), bool)

    def fast_aggregate_verify_batch_async(self, messages, sigs, members,
                                          bucket_floor=None):
        k = self.dispatches
        self.dispatches += 1
        self.dispatched[k].set()

        def settle() -> bool:
            with self.tracer.span("readback", {"stub": True}):
                self.waited.append(
                    self.dispatched[k + 1].wait(self.timeout_s))
            return True

        return settle


def test_pipelined_dispatch_overlaps_prep_with_readback():
    """Acceptance: with max_active=1 (no task-level parallelism), batch
    N+1's host_prep and dispatch must happen before batch N's readback
    ENDS — only the two-deep dispatch queue makes that possible. Judged by
    the ORDER of events (the stub's settle waits for the next dispatch),
    not by how two sleeping threads happen to share a loaded machine: the
    version that compared wall-clock span windows around a 0.25 s sleep
    failed under six xdist workers with the pipeline sound (ROADMAP D0)."""
    from grandine_tpu.consensus.verifier import NullVerifier
    from grandine_tpu.fork_choice.store import Tick, TickKind
    from grandine_tpu.runtime import AttestationVerifier, Controller
    from grandine_tpu.tracing import Tracer
    from grandine_tpu.transition.genesis import interop_genesis_state
    from grandine_tpu.types.config import Config
    from grandine_tpu.validator.duties import produce_attestations, produce_block

    cfg = Config.minimal()
    genesis = interop_genesis_state(32, cfg)
    tracer = Tracer()
    ctrl = Controller(genesis, cfg, verifier_factory=NullVerifier)
    stub = _HandshakeBackend(tracer, batches=4)
    verifier = AttestationVerifier(
        ctrl,
        backend=stub,
        use_device=True,
        use_registry=False,
        max_batch=1,
        max_active=1,
        deadline_s=0.005,
        tracer=tracer,
        # the settle watchdog must outlast the handshake's own timeout
        settle_timeout_s=60.0,
    )
    try:
        blk, post = produce_block(
            genesis, 1, cfg, full_sync_participation=False
        )
        ctrl.on_tick(Tick(1, TickKind.PROPOSE))
        ctrl.on_own_block(blk)
        ctrl.wait()
        att = produce_attestations(post, cfg, slot=1)[0]
        # four copies → four single-item batches through the pipeline
        verifier.submit_many([att, att, att, att])
        verifier.flush(timeout=120.0)
        assert verifier.stats["accepted"] == 4
        assert stub.dispatches == 4
    finally:
        verifier.stop()
        ctrl.stop()

    # every settle saw the next batch dispatched while it was held
    assert stub.waited == [True] * 4, (
        "a batch's settle timed out waiting for the next dispatch — the "
        "dispatch queue is not pipelining"
    )
    # and the spans say the same, by construction and not by the clock:
    # the settles run in dispatch order on one completion thread, and
    # batch k+1's host_prep precedes its dispatch, which precedes the
    # end of batch k's readback
    spans = tracer.finished_spans()
    readbacks = sorted((s for s in spans if s.name == "readback"),
                       key=lambda s: s.start)
    assert len(readbacks) == 4
    first_prep = {}
    for s in spans:
        if s.name == "host_prep":
            first_prep[s.trace_id] = min(
                s.start, first_prep.get(s.trace_id, s.start))
    for held, nxt in zip(readbacks, readbacks[1:]):
        assert held.trace_id != nxt.trace_id
        assert first_prep[nxt.trace_id] < held.end


# ----------------------------------------------------- kernel differential

kernel = pytest.mark.kernel


@pytest.fixture(scope="module")
def metrics():
    return Metrics()


@pytest.fixture(scope="module")
def backend(metrics):
    return TpuBlsBackend(metrics=metrics)


@pytest.fixture(scope="module")
def keyring():
    sks = [A.SecretKey.keygen(_rng_bytes(32)) for _ in range(6)]
    return sks, tuple(sk.public_key().to_bytes() for sk in sks)


@kernel
@pytest.mark.slow
def test_indexed_flat_verify_agrees_with_upload_path(
    backend, metrics, keyring
):
    sks, pkb = keyring
    pks = [sk.public_key() for sk in sks]
    reg = DevicePubkeyRegistry(metrics=metrics)
    assert reg.ensure(pkb[:4])

    msgs = [b"flat-%d" % i for i in range(3)]
    sigs = [sks[i].sign(msgs[i]) for i in range(3)]
    rng = _Rng(0xA1)
    assert backend.multi_verify_indexed(msgs, sigs, [0, 1, 2], reg, rng=rng)
    assert backend.multi_verify(msgs, sigs, pks[:3], rng=rng)
    # wrong signer index fails exactly like wrong key
    assert not backend.multi_verify_indexed(
        msgs, sigs, [1, 0, 2], reg, rng=rng
    )
    # an index the registry does not cover fails
    assert not backend.multi_verify_indexed(
        msgs, sigs, [0, 1, 5], reg, rng=rng
    )
    # after an incremental append the new rows verify
    assert reg.ensure(pkb)
    assert reg.stats["appends"] == 1
    msgs5 = [b"flat-append"]
    sigs5 = [sks[5].sign(msgs5[0])]
    assert backend.multi_verify_indexed(msgs5, sigs5, [5], reg, rng=rng)
    # after invalidation: unusable, then a refresh restores agreement
    reg.invalidate()
    assert not backend.multi_verify_indexed(msgs, sigs, [0, 1, 2], reg, rng=rng)
    assert reg.ensure(pkb)
    assert reg.stats["refreshes"] == 2
    assert backend.multi_verify_indexed(msgs, sigs, [0, 1, 2], reg, rng=rng)


def test_indexed_aggregate_edge_policies_without_device(backend, keyring):
    """Host-side edge policies of the indexed aggregate path — the
    fast tier-1 witness for the full differential below (slow tier):
    length mismatch and an empty committee are verification failures,
    the empty batch is vacuously true, all decided before any device
    work."""
    sks, pkb = keyring
    reg = DevicePubkeyRegistry()
    msg = b"edge"
    sig = A.Signature.aggregate([sks[0].sign(msg)])
    settle = backend.fast_aggregate_verify_batch_indexed_async(
        [msg], [sig], [[0], [1]], reg
    )
    assert settle() is False  # committees/messages length mismatch
    settle = backend.fast_aggregate_verify_batch_indexed_async(
        [msg], [sig], [[]], reg
    )
    assert settle() is False  # empty committee can't have signed
    settle = backend.fast_aggregate_verify_batch_indexed_async(
        [], [], [], reg
    )
    assert settle() is True  # vacuous batch


@kernel
@pytest.mark.slow
def test_indexed_aggregate_verify_agrees_and_skips_pubkey_upload(
    backend, metrics, keyring
):
    sks, pkb = keyring
    pks = [sk.public_key() for sk in sks]
    reg = DevicePubkeyRegistry(metrics=metrics)
    assert reg.ensure(pkb)

    committees = [[0, 1, 2], [3, 4], [5]]
    msgs = [b"agg-%d" % i for i in range(3)]
    aggs = [
        A.Signature.aggregate([sks[j].sign(msgs[i]) for j in committees[i]])
        for i in range(3)
    ]
    member_keys = [[pks[j] for j in c] for c in committees]
    rng = _Rng(0xB2)
    assert backend.fast_aggregate_verify_batch_indexed(
        msgs, aggs, committees, reg, rng=rng
    )
    assert backend.fast_aggregate_verify_batch(
        msgs, aggs, member_keys, rng=rng
    )
    # a committee missing a signer fails on both paths
    short = [c[:-1] or c for c in committees[:1]] + committees[1:]
    short[0] = [0, 1]  # signature includes sks[2]
    assert not backend.fast_aggregate_verify_batch_indexed(
        msgs, aggs, short, reg, rng=rng
    )
    assert not backend.fast_aggregate_verify_batch(
        msgs, aggs, [[pks[j] for j in c] for c in short], rng=rng
    )

    # WARM-PATH ACCOUNTING: the verifier's per-batch registry sync is an
    # ensure() on the same head-state tuple — an identity hit that uploads
    # zero registry bytes; the indexed verify then moves well under the
    # pubkey plane the upload path would carry.
    upload = metrics.device_upload_bytes.value
    hits_before = reg.stats["hits"]
    assert reg.ensure(pkb)  # what _sync_registry does on a warm batch
    assert reg.stats["hits"] == hits_before + 1
    reg_bytes = upload("pubkey_registry")
    idx_bytes = upload("agg_fast_verify_msm_idx")
    up_bytes = upload("agg_fast_verify_msm")
    assert backend.fast_aggregate_verify_batch_indexed(
        msgs, aggs, committees, reg, rng=rng
    )
    assert backend.fast_aggregate_verify_batch(
        msgs, aggs, member_keys, rng=rng
    )
    assert upload("pubkey_registry") == reg_bytes, (
        "warm verify re-uploaded registry bytes"
    )
    import grandine_tpu.tpu.limbs as L

    batch_bytes = upload("agg_fast_verify_msm_idx") - idx_bytes
    upload_path_bytes = upload("agg_fast_verify_msm") - up_bytes
    # the two arg tuples differ ONLY in mem_x+mem_y (the pubkey plane)
    # vs mem_idx (an int32 index plane) — the rest is shape-identical
    # per bucket, so the saving is exactly plane-minus-indices
    bm, bk = _bucket(3), _bucket(3, lo=4)
    pk_plane = bm * bk * 2 * L.NLIMBS * 4
    idx_plane = bm * bk * 4
    assert upload_path_bytes - batch_bytes == pk_plane - idx_plane, (
        f"warm indexed batch moved {batch_bytes} B vs upload path's "
        f"{upload_path_bytes} B; expected the {pk_plane} B pubkey plane "
        f"to be replaced by a {idx_plane} B index plane"
    )


@kernel
def test_one_compile_per_bucket(backend, keyring):
    """Varying batch sizes inside one padding bucket must NOT trigger new
    jit compiles: the padded shapes (and the data-independent MSM plan
    geometry) are identical, so each kernel compiles once per bucket."""
    sks, _ = keyring
    pks = [sk.public_key() for sk in sks]
    rng = _Rng(0xC3)

    def flat_verify(n: int) -> bool:
        msgs = [b"compile-%d" % i for i in range(n)]  # distinct → flat path
        sigs = [sks[i].sign(msgs[i]) for i in range(n)]
        return backend.multi_verify(msgs, sigs, pks[:n], rng=rng)

    def sizes(prefix: str) -> int:
        total = 0
        for key, fn in _JITTED.items():
            if key.startswith(prefix) and not key.startswith(prefix + "_idx"):
                total += int(fn._cache_size())
        return total

    assert flat_verify(3)  # bucket 4: compile happens here (or is cached)
    baseline = sizes("multi_verify_msm")
    assert baseline >= 1
    for n in (2, 4):  # both inside bucket 4
        assert flat_verify(n)
        assert sizes("multi_verify_msm") == baseline, (
            f"batch size {n} inside one bucket triggered a recompile"
        )


# --------------------------------------------- churn at registry scale


def _fake_decompress_dev(raw):
    """Synthetic device decompress keyed off the raw wire bytes — stands
    in for the G1 sqrt kernel so churn tests scale to mainnet row counts
    without compiling (or running) the real decompressor."""
    import jax.numpy as jnp

    import grandine_tpu.tpu.limbs as L

    ids = raw[:, -4:].astype(np.int64)
    ids = (ids[:, 0] << 24) | (ids[:, 1] << 16) | (ids[:, 2] << 8) | ids[:, 3]
    x = np.zeros((raw.shape[0], L.NLIMBS), np.int32)
    x[:, 0] = (ids & 0x7FFF_FFFF).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(x + 1)


def _synthetic_keys(n: int) -> tuple:
    """Wire-well-formed compressed pubkeys (flag byte 0x80, distinct
    payloads) — they pass `_raw_rows`'s flag screen; the fake device
    decompress above supplies the limb rows."""
    return tuple(b"\x80" + i.to_bytes(47, "big") for i in range(n))


def _churn(reg, keys_all, base_count, batch, batches):
    """Deposit-batch churn: `batches` prefix-appends of `batch` rows on
    top of `base_count`, returning (appended_rows, stats deltas)."""
    assert reg.ensure(keys_all[:base_count])
    cap0 = reg.capacity
    grows0 = reg.stats["host_grows"]
    up0 = reg.stats["uploaded_bytes"]
    refr0 = reg.stats["refreshes"]
    end = base_count
    for _ in range(batches):
        end += batch
        assert reg.ensure(keys_all[:end])
    return (
        end - base_count,
        cap0,
        reg.stats["host_grows"] - grows0,
        reg.stats["uploaded_bytes"] - up0,
        reg.stats["refreshes"] - refr0,
    )


def test_registry_churn_within_capacity_is_o_new(monkeypatch):
    """Fast witness for the mainnet churn invariant: prefix appends
    inside capacity upload exactly the new rows' raw bytes (bucketed to
    the decompress kernel's warm ladder), never regrow the host mirror,
    and never rebuild the device arrays."""
    from grandine_tpu.tpu.registry import _next_pow2

    m = Metrics()
    reg = DevicePubkeyRegistry(metrics=m)
    monkeypatch.setattr(reg, "_decompress_dev", _fake_decompress_dev)
    keys_all = _synthetic_keys(1024)
    appended, cap0, grows, uploaded, refreshes = _churn(
        reg, keys_all, base_count=1024 - 64, batch=8, batches=8
    )
    assert appended == 64
    assert reg.capacity == cap0 == 1024
    assert grows == 0, "within-capacity churn regrew the host mirror"
    assert refreshes == 0
    assert uploaded == 8 * _next_pow2(8) * 48, (
        "append upload is not O(new raw rows)"
    )
    assert m.pubkey_registry_host_bytes.value == reg._hraw.nbytes
    assert m.pubkey_registry_capacity.value == 1024


def test_registry_host_mirror_growth_is_geometric(monkeypatch):
    """Growing 4 → 4096 rows in 64-row appends must reallocate the host
    mirror O(log n) times, not O(appends)."""
    reg = DevicePubkeyRegistry()
    monkeypatch.setattr(reg, "_decompress_dev", _fake_decompress_dev)
    keys_all = _synthetic_keys(4096)
    assert reg.ensure(keys_all[:4])
    for end in range(64, 4097, 64):
        assert reg.ensure(keys_all[:end])
    assert reg.stats["host_grows"] <= 12  # log2(4096) = 12
    assert reg.count == 4096


@pytest.mark.slow
def test_registry_churn_at_mainnet_capacity(monkeypatch):
    """The 2^20 bucket itself: build the mainnet-size registry (synthetic
    limb rows), then run deposit-batch churn and hold the O(new)
    invariants at full scale. `test_registry_churn_within_capacity_is_
    o_new` is the fast witness for this path."""
    import grandine_tpu.tpu.limbs as L
    from grandine_tpu.tpu.registry import MAINNET_CAPACITY, _next_pow2

    m = Metrics()
    reg = DevicePubkeyRegistry(metrics=m)
    monkeypatch.setattr(reg, "_decompress_dev", _fake_decompress_dev)
    n = MAINNET_CAPACITY
    keys_all = _synthetic_keys(n)
    appended, cap0, grows, uploaded, refreshes = _churn(
        reg, keys_all, base_count=n - 512, batch=64, batches=8
    )
    assert appended == 512
    assert reg.capacity == cap0 == n
    assert grows == 0 and refreshes == 0
    assert uploaded == 8 * _next_pow2(64) * 48
    assert reg.count == n
    assert m.pubkey_registry_device_bytes.value == n * L.NLIMBS * 4 * 2
