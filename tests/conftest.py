"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh: multi-chip sharding tests run
here, and no test may ever take the chip. A chip belongs to one process at
a time, the suite runs under several xdist workers, and on a machine that
has one, JAX picks the TPU unless told otherwise. The driver's command sets
JAX_PLATFORMS=cpu (which JAX 0.9.0 honours); the jax.config update below
makes the same choice for a bare `pytest` that forgot to, before any
backend use.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache (runtime/warmup.py jit_cache_dir():
# JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache — the one directory
# the node, the benchmark and chip_smoke.py share; CPU and TPU entries
# coexist under different keys).
import sys as _sys  # noqa: E402

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from grandine_tpu.runtime.warmup import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

import pytest  # noqa: E402

_last_kernel_module = [None]


@pytest.fixture(autouse=True)
def _drop_jit_memory_between_kernel_modules(request):
    """Release compiled-executable memory when the suite crosses from one
    kernel-tier module to the next. A full single-process run
    (`pytest tests/ -x -q`, the driver's invocation) accumulates every
    heavy pairing/MSM executable on the 8-device mesh and can abort in
    XLA's allocator; dropping caches at module boundaries bounds the
    high-water mark. Warm recompiles come from the persistent on-disk
    cache, so the cost is seconds, not minutes."""
    if request.node.get_closest_marker("kernel") is not None:
        module = request.node.module.__name__
        if _last_kernel_module[0] not in (None, module):
            from grandine_tpu.tpu.compile_scope import trim_host_memory

            jax.clear_caches()
            trim_host_memory()  # or the freed pages stay in the allocator
        _last_kernel_module[0] = module
    yield
