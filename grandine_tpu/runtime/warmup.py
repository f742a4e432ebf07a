"""Manifest-driven kernel precompiler (tools/shapes contract).

First compiles of the device kernels cost minutes per bucket shape (they
land in the persistent XLA cache afterwards), and an uncompiled bucket
hit mid-chain stalls verification for the whole compile. The warmer
iterates the CHECKED-IN kernel manifest (`tools/shapes/manifest.txt`,
generated and verified by `python -m tools.shapes`) — the statically
proven universe of (kind, bucket) pairs the node's dispatch paths can
form — and runs each kernel once on shape-matched dummy inputs, one
entry at a time on the caller's thread, trimming host memory after each
(tpu/compile_scope.py). A cold entry of a pairing kernel costs minutes
and ~6 GB of host memory at its peak (CHANGES.md PR 22 has the table), so
the node warms the entries ITS lanes can dispatch (cli `_warm_firehose`),
before its first slot — never the whole manifest on a cold machine.

Compilation depends only on SHAPES; the dummy inputs are valid curve
points with nonsense provenance, so every warm call returns False —
irrelevant, the compile cache is the product.

When warming finishes it SEALS the shape ledger
(`tpu.bls.declare_warmup_complete`): any novel shape signature
dispatched afterwards increments `verify_recompiles_total`, making
"zero steady-state recompiles" an assertable invariant (bench soaks,
tests/test_shapes.py). The built-in bucket ladders below are only the
fallback for a checkout whose manifest is missing.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Optional

#: FALLBACK ladders when tools/shapes/manifest.txt is absent — kept in
#: sync with the analyzer's derived rows (firehose bound = max of
#: attestation MAX_BATCH and the widest scheduler lane max_batch).
FIREHOSE_BUCKETS = (4, 8, 16, 32, 64, 128)
MULTI_VERIFY_BUCKETS = (64, 256, 1024, 4096)
# sign-plane lanes deadline-flush at any n ≤ max_batch (512): warm the
# full pow-2 ladder so first-duty signing never compiles at slot time
SIGN_BUCKETS = (4, 8, 16, 32, 64, 128, 256, 512)
SUBGROUP_BUCKETS = (4, 8, 16, 32, 64, 128)

#: warm kinds the runner understands, in manifest order. The sharded_*
#: kinds compile the multi-chip dispatch targets (tpu/bls.py
#: sharded_multi_verify / sharded_multi_verify_msm) and are skipped with
#: a progress note on a mesh-less node — the MULTICHIP dryruns measured
#: a cold 2m51s sharded compile, which warmup must eat at startup so a
#: restart never pays it mid-chain.
WARM_KINDS = ("aggregate", "aggregate_idx", "multi_verify", "sign",
              "subgroup", "rlc_partition", "sharded_multi_verify",
              "sharded_multi_verify_msm", "span_update",
              "registry_capacity", "ed25519_verify", "kzg_blob",
              "aggregate_comp", "aggregate_idx_comp", "multi_verify_comp",
              "g1_decompress", "g2_aggregate", "g1_aggregate")


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def manifest_file_path() -> str:
    return os.path.join(_repo_root(), "tools", "shapes", "manifest.txt")


def load_manifest(
    path: "Optional[str]" = None,
) -> "Optional[list[tuple[str, int]]]":
    """(kind, bucket) pairs from the checked-in shape manifest's `warm`
    rows, or None when the file is missing/unparseable (fallback ladders
    apply)."""
    path = path or manifest_file_path()
    try:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
    except OSError:
        return None
    out: "list[tuple[str, int]]" = []
    for raw in text.splitlines():
        line = raw.strip()
        if not line.startswith("warm "):
            continue
        cols = [c.strip() for c in line.split("|")]
        kind = cols[0][len("warm "):].strip()
        buckets = None
        for col in cols[1:]:
            if col.startswith("buckets "):
                try:
                    buckets = [
                        int(b) for b in col[len("buckets "):].split(",")
                    ]
                except ValueError:
                    return None
        if not buckets or kind not in WARM_KINDS:
            return None
        out.extend((kind, b) for b in buckets)
    return out or None


def manifest() -> "list[tuple[str, int]]":
    loaded = load_manifest()
    if loaded is not None:
        return loaded
    out = [("aggregate", b) for b in FIREHOSE_BUCKETS]
    out += [("aggregate_idx", b) for b in FIREHOSE_BUCKETS]
    out += [("multi_verify", b) for b in MULTI_VERIFY_BUCKETS]
    out += [("sign", b) for b in SIGN_BUCKETS]
    out += [("subgroup", b) for b in SUBGROUP_BUCKETS]
    out += [("rlc_partition", b) for b in FIREHOSE_BUCKETS]
    # sharded rows are no-ops without a mesh (skipped with a note)
    out += [("sharded_multi_verify", b) for b in MULTI_VERIFY_BUCKETS]
    out += [("sharded_multi_verify_msm", b) for b in MULTI_VERIFY_BUCKETS]
    # compressed-ingest twins ride the same dispatch-bound ladders
    out += [("aggregate_comp", b) for b in FIREHOSE_BUCKETS]
    out += [("aggregate_idx_comp", b) for b in FIREHOSE_BUCKETS]
    out += [("multi_verify_comp", b) for b in MULTI_VERIFY_BUCKETS]
    out += [("g1_decompress", b) for b in (16, 64, 256, 1024)]
    # aggregate-construction sums (signing plane duty aggregation)
    out += [("g2_aggregate", b) for b in (64, 256)]
    out += [("g1_aggregate", b) for b in (64, 256)]
    return out


def jit_cache_dir() -> str:
    """Where compiled kernels persist: `JAX_COMPILATION_CACHE_DIR` when
    the environment sets it, else `<checkout>/.jax_cache` (git-ignored).
    A fixed path, because the path is part of the cache key: a directory
    that moves never hits. Imports no JAX."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _repo_root(), ".jax_cache"
    )


def enable_persistent_cache() -> str:
    """Turn XLA's persistent compilation cache on at `jit_cache_dir()`.
    Warm compiles land there, so a RESTART pays cache loads, not fresh
    compiles (~minutes each). With `JAX_COMPILATION_CACHE_DIR` set, JAX
    has already read the directory from the environment and no directory
    is set in code. Idempotent; a cache that cannot be placed raises."""
    import jax

    cache_dir = jit_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir


def warm_all(
    buckets: "Optional[list[tuple[str, int]]]" = None,
    progress: "Optional[Callable[[str], None]]" = None,
    backend=None,
    registry=None,
    metrics=None,
    seal: bool = True,
    enable_cache: bool = True,
    mesh=None,
    committee_width: int = 1,
) -> int:
    """Compile-and-run every entry of `buckets` (default: the whole
    manifest) once, ONE AT A TIME, each inside `compiling()` so host
    memory is trimmed after every compile. Returns the number of entries
    warmed. All state here is thread-local; the shared ledger/cache
    seams take their own locks. A cold entry of a pairing kernel costs
    minutes and ~6 GB of host memory at its peak, so a caller names the
    entries its lanes can dispatch (cli `_firehose_warm_plan`: the
    attestation firehose's ONE batch bucket x its committee widths; the
    manifest's batch ladders are the scheduler lanes', which flush at any
    size) rather than warming the whole manifest on a cold machine.

    `committee_width` is the member count of the widest committee the
    indexed aggregate rows will see: the member axis is bucketed too, and
    a row warmed at width 1 does not cover a 130-member committee.

    `registry` (a DevicePubkeyRegistry with at least one key) unlocks
    the aggregate_idx kind; without it those rows are skipped with a
    progress note. `mesh` (a VerifyMesh, cli --devices) unlocks the
    sharded_* kinds, warmed through a mesh-attached backend so the
    multi-chip dispatch targets compile at startup; single-device kinds
    still warm through a plain backend (they stay the fallback for
    batches the mesh gates reject). With `seal` the shape ledger is
    sealed on completion so later novel shapes count as recompiles."""
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import schemes
    from grandine_tpu.tpu.compile_scope import compiling
    from grandine_tpu.tpu.mesh import mesh_or_none

    if enable_cache:
        enable_persistent_cache()
    # pre-load the MSM autotune table (tools/shapes/msm_tune.json, when a
    # sweep on THIS platform wrote one) so the window widths baked into
    # the warmed plans are the measured ones — a table loaded after
    # warmup would re-plan, and re-compile, mid-slot
    if B.load_msm_tuning() and progress:
        progress("msm autotune table loaded (%s)" % B.msm_tune_path())
    mesh_backend = (
        backend if getattr(backend, "mesh", None) is not None else None
    )
    if mesh_backend is None and mesh_or_none(mesh) is not None:
        mesh_backend = schemes.get("bls").make_backend(
            metrics=metrics, mesh=mesh
        )
    if backend is None:
        backend = schemes.get("bls").make_backend(metrics=metrics)
    pk = A.PublicKey(G1)
    h = hash_to_g2(b"warmup")
    sig = A.Signature(h)
    sig_c = A.g2_to_bytes(h)  # compressed wire bytes (compressed-ingest)
    sk = A.SecretKey(0x1234_5678)
    #: lazily-built non-BLS scheme backends (tpu/schemes.py table),
    #: shared across that scheme's warm rows so each gets one jit cache
    scheme_backends: "dict[str, object]" = {}
    done = 0
    members = [[0] * max(1, int(committee_width))]
    for kind, b in buckets if buckets is not None else manifest():
        t0 = time.time()
        try:
            with compiling():
                if kind == "aggregate":
                    backend.fast_aggregate_verify_batch(
                        [b"warm-%d" % i for i in range(b)],
                        [sig] * b,
                        [[pk]] * b,
                    )
                elif kind == "aggregate_idx":
                    if registry is None or registry.arrays()[0] is None:
                        if progress:
                            progress(
                                f"warm {kind}/{b} skipped: no device registry"
                            )
                        continue
                    backend.fast_aggregate_verify_batch_indexed(
                        [b"warm-%d" % i for i in range(b)],
                        [sig] * b,
                        members * b,
                        registry,
                    )
                elif kind == "multi_verify":
                    # bm distinct messages x bk signatures each: the grouped
                    # kernel's shape (bm = b//8 groups exercises the MSM path)
                    n_groups = max(2, b // 8)
                    backend.multi_verify(
                        [b"warm-%d" % (i % n_groups) for i in range(b)],
                        [sig] * b,
                        [pk] * b,
                    )
                elif kind == "sign":
                    backend.batch_sign([b"warm-%d" % i for i in range(b)],
                                       [sk] * b)
                elif kind == "subgroup":
                    backend.g2_subgroup_check_batch([h] * b)
                elif kind == "rlc_partition":
                    # fault localization dispatches each bucket at every
                    # rung of its fixed group ladder (runtime/isolation.py);
                    # warm all (bucket, groups) variants so an adversarial
                    # incident never compiles mid-descent
                    from grandine_tpu.runtime.isolation import ladder

                    for g in ladder(b):
                        backend.rlc_partition_verify(
                            [b"warm-%d" % i for i in range(b)],
                            [sig] * b,
                            [[pk]] * b,
                            g,
                        )
                elif kind == "sharded_multi_verify":
                    if mesh_backend is None:
                        if progress:
                            progress(f"warm {kind}/{b} skipped: no mesh")
                        continue
                    # ALL-distinct messages defeat the grouping heuristic so
                    # dispatch takes the flat sharded-RLC path
                    mesh_backend.multi_verify(
                        [b"warm-%d" % i for i in range(b)],
                        [sig] * b,
                        [pk] * b,
                    )
                elif kind == "sharded_multi_verify_msm":
                    if mesh_backend is None:
                        if progress:
                            progress(f"warm {kind}/{b} skipped: no mesh")
                        continue
                    # grouped messages route to the sharded grouped-MSM path
                    # (both group axes divide any power-of-two mesh)
                    n_groups = max(2, b // 8)
                    mesh_backend.multi_verify(
                        [b"warm-%d" % (i % n_groups) for i in range(b)],
                        [sig] * b,
                        [pk] * b,
                    )
                elif kind == "span_update":
                    # slasher bulk-replay span grid (tpu/spans.py): buckets
                    # are row widths; the epoch axis is fixed, so one merge
                    # per bucket compiles the whole kernel surface
                    import numpy as np

                    from grandine_tpu.tpu import spans as SP

                    plane = SP.SpanPlane(metrics=metrics)
                    plane.update(
                        np.full(
                            (b, SP.SPAN_GRID_EPOCHS), SP.INT32_UNSET, np.int32
                        ),
                        np.zeros((b, SP.SPAN_GRID_EPOCHS), np.int32),
                        np.full((b,), 8, np.int32),
                        np.full((b,), 9, np.int32),
                        0,
                    )
                elif kind == "registry_capacity":
                    # the registry arrays' row count is part of the indexed
                    # gather kernel's jit signature: one small dispatch
                    # against a zeros shim at mainnet capacity compiles the
                    # 2^20-row gather without holding a million real keys
                    import jax
                    import numpy as np

                    from grandine_tpu.tpu import limbs as L

                    zx = jax.device_put(np.zeros((b, L.NLIMBS), np.int32))
                    zy = jax.device_put(np.zeros((b, L.NLIMBS), np.int32))
                    cap_rows = b

                    class _ShimRegistry:
                        @staticmethod
                        def arrays():
                            return zx, zy, cap_rows

                    backend.fast_aggregate_verify_batch_indexed(
                        [b"warm-%d" % i for i in range(4)],
                        [sig] * 4,
                        [[0]] * 4,
                        _ShimRegistry(),
                    )
                elif kind == "aggregate_comp":
                    # compressed-ingest firehose twin: signatures stay raw
                    # 96-byte wire rows, decompressed inside the kernel
                    backend.fast_aggregate_verify_batch_compressed(
                        [b"warm-%d" % i for i in range(b)],
                        [sig_c] * b,
                        [[pk]] * b,
                    )
                elif kind == "aggregate_idx_comp":
                    if registry is None or registry.arrays()[0] is None:
                        if progress:
                            progress(
                                f"warm {kind}/{b} skipped: no device registry"
                            )
                        continue
                    backend.fast_aggregate_verify_batch_indexed_compressed(
                        [b"warm-%d" % i for i in range(b)],
                        [sig_c] * b,
                        members * b,
                        registry,
                    )
                elif kind == "multi_verify_comp":
                    backend.multi_verify_compressed(
                        [b"warm-%d" % i for i in range(b)],
                        [sig_c] * b,
                        [pk] * b,
                    )
                elif kind in ("g2_aggregate", "g1_aggregate"):
                    # aggregate CONSTRUCTION (duty aggregation, signing
                    # plane): the kernel signature is (flat bucket n, group
                    # count g) — like rlc_partition, warm every (n, g) split
                    # the contiguous-sum dispatch can form at this bucket so
                    # slot-time committee mixes never compile
                    g = 4
                    while b // g >= 4:  # spans below the bucket floor (4)
                        span = b // g   # re-bucket to a different n
                        if kind == "g2_aggregate":
                            B.g2_aggregate_groups(
                                [[sig] * span] * g, metrics
                            )
                        else:
                            B.g1_aggregate_groups(
                                [[pk] * span] * g, metrics
                            )
                        g <<= 1
                elif kind == "g1_decompress":
                    # the registry's device decompress runs at append buckets
                    # and capacity shapes (tpu/registry.py _decompress_dev) —
                    # warm the jit entry directly against dummy rows
                    import numpy as np

                    rows = np.zeros((b, 48), np.uint8)
                    rows[:, 0] = 0xC0  # canonical infinity: valid, neutral
                    B.g1_decompress_rows(rows, metrics)
                elif kind == "ed25519_verify":
                    # the manifest bucket is the KERNEL batch (point rows
                    # m = 1 + 2n for n items, pow-4 ladder): n = b//2 - 1
                    # items land exactly on bucket b
                    from grandine_tpu.crypto import ed25519 as ED
                    from grandine_tpu.runtime.verify_scheduler import (
                        VerifyItem,
                    )

                    ed_backend = scheme_backends.get("ed25519")
                    if ed_backend is None:
                        ed_backend = scheme_backends["ed25519"] = schemes.get(
                            "ed25519"
                        ).make_backend(metrics=metrics)
                    ed_sk = b"\x42" * 32
                    ed_pk = ED.secret_to_public(ed_sk)
                    ed_sig = ED.sign(ed_sk, b"warmup")
                    n_items = max(1, b // 2 - 1)
                    status, prep = ed_backend.prepare([
                        VerifyItem(b"warmup", ed_sig, public_keys=(ed_pk,))
                    ] * n_items)
                    if status != "ok":
                        raise RuntimeError(f"ed25519 warm prep: {status}")
                    ed_backend.verify_batch_async(prep)()
                elif kind == "kzg_blob":
                    # bucket = _bucket(n_blobs, lo=4, hi=8); the kernel
                    # shape is blob-width independent (width only sizes the
                    # host barycentric prep), so the small dev setup warms
                    # the same executable mainnet blobs dispatch to
                    from grandine_tpu.kzg import eip4844 as KZ
                    from grandine_tpu.kzg.setup import dev_setup
                    from grandine_tpu.runtime.verify_scheduler import (
                        VerifyItem,
                    )

                    kzg_backend = scheme_backends.get("blob_kzg")
                    if kzg_backend is None:
                        kzg_backend = scheme_backends["blob_kzg"] = (
                            schemes.get("blob_kzg").make_backend(
                                metrics=metrics
                            )
                        )
                    kzg_setup = dev_setup(8)
                    blob = b"\x00" * (
                        8 * KZ.BYTES_PER_FIELD_ELEMENT
                    )
                    commitment = KZ.blob_to_kzg_commitment(blob, kzg_setup)
                    proof = KZ.compute_blob_kzg_proof(
                        blob, commitment, kzg_setup
                    )
                    status, prep = kzg_backend.prepare([
                        VerifyItem(blob, proof, public_keys=(commitment,))
                    ] * b)
                    if status != "ok":
                        raise RuntimeError(f"kzg warm prep: {status}")
                    kzg_backend.verify_blobs_async(prep)()
        except Exception as e:  # a failed warm is a lost optimization only
            if progress:
                progress(f"warm {kind}/{b} FAILED: {e!r}")
            continue
        done += 1
        if progress:
            progress(f"warm {kind}/{b}: {time.time() - t0:.1f}s")
    if seal:
        B.declare_warmup_complete()
        if progress:
            progress(f"warm complete: {done} shapes, ledger sealed")
    return done


__all__ = ["manifest", "load_manifest", "manifest_file_path",
           "jit_cache_dir", "enable_persistent_cache", "warm_all",
           "WARM_KINDS", "FIREHOSE_BUCKETS", "MULTI_VERIFY_BUCKETS",
           "SIGN_BUCKETS", "SUBGROUP_BUCKETS"]
