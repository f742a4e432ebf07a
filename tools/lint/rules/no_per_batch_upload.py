"""Runtime rule: the warm-registry verify path must not re-upload the
pubkey plane per batch.

Unlike the AST rules this one EXECUTES the backend: it builds a small
device pubkey registry, runs the indexed verify path twice, and audits
the backend's own `device_upload_bytes_total{kernel=...}` accounting
(the `_upload` seam in tpu/bls.py). kind="runtime" — it compiles
kernels and needs a working JAX, so it only runs under
`python -m tools.lint --runtime` (or `--rules no-per-batch-upload`),
in a process of its own: it takes whatever device JAX finds (the CPU,
unless the environment says otherwise), and a chip belongs to one process
at a time — never import it into a process that later needs the chip
(chip_smoke.py does not).

Checks:
  1. The second warm verify uploads zero registry bytes (identity hit).
  2. The indexed path's per-batch upload equals the upload-path
     kernel's minus exactly the pubkey plane (bm·bk·2·26·4 B) plus the
     int32 index plane (bm·bk·4 B).
"""

from __future__ import annotations

import os
import sys

from tools.lint.core import Context, Finding, Rule


class _Rng:
    """random.Random with the secrets-style randbits interface."""

    def __init__(self, seed: int) -> None:
        import random

        self._rng = random.Random(seed)

    def randbits(self, n: int) -> int:
        return self._rng.getrandbits(n)


class NoPerBatchUploadRule(Rule):
    name = "no-per-batch-upload"
    kind = "runtime"
    description = (
        "warm registry-indexed verify transfers O(batch) bytes — no "
        "pubkey limbs and no registry re-upload on the per-batch clock"
    )
    default_paths = ()  # executes code; no files to scan

    def files(self, ctx: Context, targets):
        return []

    def check(self, ctx: Context, files):
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if ctx.root not in sys.path:
            sys.path.insert(0, ctx.root)

        from grandine_tpu.runtime.warmup import enable_persistent_cache

        enable_persistent_cache()  # pairing compiles are slow cold

        from grandine_tpu.crypto import bls as A
        from grandine_tpu.metrics import Metrics
        from grandine_tpu.tpu import limbs as L
        from grandine_tpu.tpu.bls import TpuBlsBackend, _bucket
        from grandine_tpu.tpu.registry import DevicePubkeyRegistry

        path = "grandine_tpu/tpu/bls.py"  # the seam under audit

        def fail(slug: str, msg: str) -> Finding:
            return Finding(self.name, path, 0, msg,
                           key=f"{self.name}:{path}:{slug}")

        rng = _Rng(0x5EED)
        metrics = Metrics()
        backend = TpuBlsBackend(metrics=metrics)
        registry = DevicePubkeyRegistry(metrics=metrics)

        n_keys, m = 8, 3
        sks = [
            A.SecretKey.keygen(bytes([i + 1]) * 32) for i in range(n_keys)
        ]
        pubkeys = tuple(sk.public_key().to_bytes() for sk in sks)
        committees = [[0, 1, 2], [3, 4], [5, 6, 7]]
        messages = [b"upload-guard-%d" % i for i in range(m)]
        aggs = [
            A.Signature.aggregate(
                [sks[j].sign(messages[i]) for j in committees[i]]
            )
            for i in range(m)
        ]

        if not registry.ensure(pubkeys):
            return [fail("registry-build", "registry build failed")]

        upload = metrics.device_upload_bytes.value
        idx_kernel = "agg_fast_verify_msm_idx"

        def run_indexed() -> bool:
            return backend.fast_aggregate_verify_batch_indexed(
                messages, aggs, committees, registry, rng=rng
            )

        out: "list[Finding]" = []
        # warm-up (compiles); then measure a warm batch
        if not run_indexed():
            return [fail("cold-reject",
                         "indexed verify rejected a valid batch")]
        b0, r0 = upload(idx_kernel), upload("pubkey_registry")
        if not run_indexed():
            return [fail("warm-reject",
                         "indexed verify rejected a valid batch (warm)")]
        batch_bytes = upload(idx_kernel) - b0
        registry_bytes = upload("pubkey_registry") - r0

        bm = _bucket(m)
        bk = _bucket(max(len(c) for c in committees), lo=4)
        pk_plane_bytes = bm * bk * 2 * L.NLIMBS * 4  # x+y int32 limb rows
        idx_plane_bytes = bm * bk * 4  # int32 index plane replacing it

        if registry_bytes != 0:
            out.append(fail(
                "registry-reupload",
                f"warm verify re-uploaded {registry_bytes} registry "
                f"bytes (expected 0: identity hit)",
            ))

        # the upload-path kernel on the same batch: its arg tuple
        # differs from the indexed path's ONLY in pubkey plane vs index
        # plane, so the byte saving must be exactly plane-minus-indices
        member_keys = [registry.public_keys(c) for c in committees]
        u0 = upload("agg_fast_verify_msm")
        if not backend.fast_aggregate_verify_batch(
            messages, aggs, member_keys, rng=rng
        ):
            return out + [fail(
                "upload-path-reject",
                "upload-path verify rejected a valid batch",
            )]
        upload_path_bytes = upload("agg_fast_verify_msm") - u0
        saving = upload_path_bytes - batch_bytes
        if saving != pk_plane_bytes - idx_plane_bytes:
            out.append(fail(
                "pubkey-plane-rides-batch",
                f"indexed path saved {saving} B over the upload path; "
                f"expected the {pk_plane_bytes} B pubkey plane replaced "
                f"by the {idx_plane_bytes} B index plane "
                f"({pk_plane_bytes - idx_plane_bytes} B) — pubkey limbs "
                f"are riding the per-batch clock",
            ))

        print(
            f"no-per-batch-upload: warm indexed batch {batch_bytes} B "
            f"(upload-path kernel moved {upload_path_bytes} B; pubkey "
            f"plane {pk_plane_bytes} B -> index plane {idx_plane_bytes} "
            f"B; registry re-upload {registry_bytes} B)"
        )
        return out
