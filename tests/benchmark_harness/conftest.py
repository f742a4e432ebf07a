"""Fixtures of the benchmark's own tests: a temporary benchmark root (a
copy of benchmark/ with the tiny rehearsal cells of tests/benchmark_harness/
tiny/ laid over it), so the tests drive run.py's own functions on the CPU
without touching the real cells."""

import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def make_root(dst: str) -> str:
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(os.path.dirname(__file__), "tiny"), dst,
                    dirs_exist_ok=True)
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The tiny cells state batches of 4, and the driver refuses a node
    whose verifier is not as its configuration states: so while these
    tests run the verifier is built with the tiny configuration's sizes
    (64 would make the CPU compile a kernel sixteen times as wide)."""
    import json

    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    root = make_root(str(tmp_path_factory.mktemp("bench_root")))
    with open(os.path.join(root, "benchmark", "configs",
                           "firehose-tiny.json")) as fh:
        shapes = json.load(fh)["shapes"]
    sizes = {k: shapes[k] for k in ("max_batch", "deadline_s",
                                    "pipeline_depth")}
    init = AttestationVerifier.__init__

    def tiny_init(self, *args, **kwargs):
        init(self, *args, **{**sizes, **kwargs})

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(AttestationVerifier, "__init__", tiny_init)
        yield root
