"""Shape-contract analyzer + manifest lifecycle + warmup sealing.

Covers the tools/shapes tentpole end to end: the repo itself proves
clean, seeded fixtures trip each hazard class, the checked-in manifest
round-trips byte-identically and stale copies are detected, the warmer
consumes the manifest's warm rows, and a warmed CPU batch-verify holds
`verify_recompiles_total` at zero.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.lint.__main__ import main as lint_main  # noqa: E402
from tools.shapes import MANIFEST_PATH, analyze  # noqa: E402
from tools.shapes.__main__ import main as shapes_main  # noqa: E402


def lint(tmp_path, source, *extra):
    fixture = tmp_path / "fixture.py"
    fixture.write_text(source)
    return lint_main([
        "fixture.py", "--rules", "shape-contract", "--no-baseline",
        "--root", str(tmp_path), *extra,
    ])


# a minimal backend-shaped fixture following the real dispatch idiom:
# kernel registered under a literal name, dims bucketed before allocation
_CLEAN_FIXTURE = """
import numpy as np


def k_kernel(a):
    return a


def _bucket(n, lo=4, hi=16384):
    b = lo
    while b < n:
        b <<= 1
    return b


class Backend:
    def _jitted(self, name, fn):
        return fn

    def _run_kernel(self, kernel, fn, args):
        return fn(*args)

    def go(self, items):
        n = len(items)
        b = _bucket(n)
        buf = np.zeros((b, 26), np.int32)
        fn = self._jitted("k", k_kernel)
        return self._run_kernel("k", fn, (buf,))
"""


def test_shape_contract_clean_fixture(tmp_path):
    assert lint(tmp_path, _CLEAN_FIXTURE) == 0


def test_shape_contract_dynamic_dim_fixture(tmp_path):
    # raw batch length reaching an allocation = recompile hazard
    bad = _CLEAN_FIXTURE.replace(
        "buf = np.zeros((b, 26), np.int32)",
        "buf = np.zeros((n, 26), np.int32)",
    )
    assert lint(tmp_path, bad) == 1


def test_shape_contract_unregistered_kernel_fixture(tmp_path):
    bad = _CLEAN_FIXTURE.replace(
        'self._run_kernel("k", fn, (buf,))',
        'self._run_kernel("other", fn, (buf,))',
    )
    assert lint(tmp_path, bad) == 1


def test_shape_contract_bucket_floor_split_fixture(tmp_path):
    # two sites dispatching one kernel with different bucket floors:
    # gratuitously distinct shapes splitting the compile cache
    bad = _CLEAN_FIXTURE + """
    def go_wide(self, items):
        n = len(items)
        b = _bucket(n, lo=16)
        buf = np.zeros((b, 26), np.int32)
        fn = self._jitted("k", k_kernel)
        return self._run_kernel("k", fn, (buf,))
"""
    assert lint(tmp_path, bad) == 1


def test_shape_contract_suppression(tmp_path):
    bad = _CLEAN_FIXTURE.replace(
        "buf = np.zeros((n, 26), np.int32)",
        "buf = np.zeros((n, 26), np.int32)"
        "  # lint: disable=shape-contract",
    ).replace(
        "buf = np.zeros((b, 26), np.int32)",
        "buf = np.zeros((n, 26), np.int32)"
        "  # lint: disable=shape-contract",
    )
    assert lint(tmp_path, bad) == 0


def test_shapes_clean_on_repo():
    """`python -m tools.shapes` proves every jit entry point enumerable
    and the checked-in manifest current."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.shapes"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "findings=0" in proc.stdout


def test_manifest_round_trip(tmp_path):
    out = tmp_path / "manifest.txt"
    rc = shapes_main(["--write-manifest", "--out", str(out)])
    assert rc == 0
    with open(os.path.join(REPO, MANIFEST_PATH), encoding="utf-8") as fh:
        checked_in = fh.read()
    assert out.read_text() == checked_in


def test_stale_manifest_detected(tmp_path):
    stale = tmp_path / "stale.txt"
    with open(os.path.join(REPO, MANIFEST_PATH), encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    # tamper one bound row: the analyzer must notice the drift
    lines = [
        ln.replace("= 64", "= 63") if ln.startswith("bound") else ln
        for ln in lines
    ]
    stale.write_text("\n".join(lines) + "\n")
    findings, _ = analyze(
        root=REPO, check_manifest=True,
        manifest_path=os.path.relpath(str(stale), REPO),
    )
    assert any("stale" in f.key for f in findings)


def test_analysis_covers_dispatch_universe():
    findings, analysis = analyze(root=REPO, check_manifest=False)
    assert findings == []
    kernels = {e.kernel for e in analysis.entries}
    for expected in (
        "multi_verify_msm", "grouped_multi_verify_msm",
        "agg_fast_verify_msm", "agg_fast_verify_msm_idx",
        "multi_verify_msm_idx", "g2_subgroup_check", "batch_sign",
        "make_sharded_multi_verify", "make_sharded_multi_verify_msm",
    ):
        assert expected in kernels
    # every _run_kernel dispatch resolves to a registered entry
    assert {s.kernel for s in analysis.sites} <= kernels
    assert analysis.bounds["attestation_verifier.MAX_BATCH"] == 64
    assert any(k.startswith("scheduler.lane.") for k in analysis.bounds)


def test_warmup_loads_manifest():
    from grandine_tpu.runtime import warmup

    pairs = warmup.load_manifest()
    assert pairs is not None
    kinds = {k for k, _ in pairs}
    assert "aggregate_idx" in kinds
    assert kinds <= set(warmup.WARM_KINDS)
    assert len(warmup.manifest()) >= 10
    # malformed manifest -> None (fallback ladders apply)
    assert warmup.load_manifest(path="/nonexistent/manifest.txt") is None


def test_shape_tracking_ledger():
    import numpy as np

    from grandine_tpu.metrics import Metrics
    from grandine_tpu.tpu import bls as B

    B.reset_shape_tracking()
    try:
        m = Metrics()
        a = np.zeros((4, 26), np.int32)
        assert B.note_dispatch_shapes("k", (a,), m) is True
        assert B.note_dispatch_shapes("k", (a,), m) is False  # warm hit
        assert not B.warmup_declared()
        B.declare_warmup_complete()
        assert B.warmup_declared()
        assert B.note_dispatch_shapes("k", (a,), m) is False
        assert B.post_warmup_recompiles() == 0
        b = np.zeros((8, 26), np.int32)
        assert B.note_dispatch_shapes("k", (b,), m) is True
        assert B.post_warmup_recompiles() == 1
        assert m.verify_recompiles.value == 1.0
        assert "verify_recompiles_total" in m.expose()
    finally:
        B.reset_shape_tracking()


def test_warmed_batch_verify_zero_recompiles():
    """After warm_all seals the ledger, a live batch whose bucket the
    manifest covers dispatches with verify_recompiles_total == 0."""
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.runtime import warmup
    from grandine_tpu.tpu import bls as B

    B.reset_shape_tracking()
    try:
        m = Metrics()
        backend = B.TpuBlsBackend(metrics=m)
        warmed = warmup.warm_all(
            buckets=[("aggregate", 4)], backend=backend,
            metrics=m, seal=True, enable_cache=False,
        )
        assert warmed == 1
        assert B.warmup_declared()
        pk = A.PublicKey(G1)
        sig = A.Signature(hash_to_g2(b"post-warm"))
        backend.fast_aggregate_verify_batch(
            [b"live-%d" % i for i in range(3)], [sig] * 3, [[pk]] * 3
        )
        assert B.post_warmup_recompiles() == 0
        assert m.verify_recompiles.value == 0.0
    finally:
        B.reset_shape_tracking()


def test_compile_cache_can_be_placed(monkeypatch, tmp_path):
    """One function places the cache: JAX_COMPILATION_CACHE_DIR when the
    environment sets it — and then NO directory is set in code — else
    `<checkout>/.jax_cache`."""
    import jax

    from grandine_tpu.runtime import warmup

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert warmup.jit_cache_dir() == os.path.join(REPO, ".jax_cache")
        assert warmup.enable_persistent_cache() == warmup.jit_cache_dir()
        assert jax.config.jax_compilation_cache_dir == warmup.jit_cache_dir()
        # placed from outside: code sets nothing (JAX reads the variable
        # itself at start-up; here the config must simply stay untouched)
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert warmup.enable_persistent_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "sentinel"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("validators,bucket,networked,want", [
    (64, 64, False, [(64, 4)]),
    (64, 64, True, [(64, 4)]),
    # 512 validators: 4 committees a slot of 16 members
    (512, 64, False, [(64, 16)]),
    (512, 64, True, [(64, 4), (64, 8), (64, 16)]),
    # the batch axis is the verifier's one bucket, whatever it is
    (512, 8, True, [(8, 4), (8, 8), (8, 16)]),
])
def test_firehose_warm_plan_reads_the_head_state(validators, bucket,
                                                 networked, want):
    """`cli run --use-device` warms what ITS firehose can dispatch, not
    the manifest: ONE batch bucket (the verifier pads a batch of any size
    into `batch_bucket`) x the committee widths the head state can
    produce: the committees' own for a gossip-less devnet, every width up
    to the widest for a networked node."""
    from grandine_tpu.cli import _firehose_warm_plan
    from grandine_tpu.transition.genesis import interop_genesis_state
    from grandine_tpu.types.config import Config

    cfg = Config.minimal()
    state = interop_genesis_state(validators, cfg)
    assert _firehose_warm_plan(state, cfg, bucket, networked) == want
