"""Deviceless v5e compiles of the two verify executables of the benchmark's
cells that `tests/test_tpu_compile.py` does not already compile:
`agg_fast_verify_msm_idx[64x4]` over 65,536 registry rows (cell
firehose-50k.singles-backlog) and `multi_verify_msm[512]` (the replay
window of 480 sets). `slow`: minutes each, ~8 GB of host memory. The
topology is described inside a fixture, never at import (one process at a
time may load libtpu; see the on-chip-measurement guide). Seconds and
`memory_analysis()` of the last run are in PERF.md.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark_harness/test_v5e_compiles.py -m slow -s
"""

from __future__ import annotations

import contextlib
import time

import numpy as np
import pytest

#: generated code + temp + args + output of one executable, bytes
EXECUTABLE_BUDGET = 1_500_000_000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@contextlib.contextmanager
def captured_dispatches():
    """The backend's dispatch replaced by a recorder: routing, plans and
    uploads run for real on the CPU, the jitted kernel is not called."""
    from grandine_tpu.tpu import bls as B

    seen = []
    real = B.TpuBlsBackend._run_kernel

    def record(self, kernel, fn, args, sigs=0, block=True, **_kw):
        seen.append((kernel, fn, tuple(args)))
        return np.True_

    B.TpuBlsBackend._run_kernel = record
    try:
        yield seen
    finally:
        B.TpuBlsBackend._run_kernel = real


def cell_kernels():
    """[(label, jitted fn, args)] as the program itself routes the two
    cells' calls, donation as the backend defaults it on the chip."""
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import limbs as L

    backend = B.TpuBlsBackend(donate_buffers=True)
    pk = A.PublicKey(G1)
    sig = A.Signature(hash_to_g2(b"shape"))

    class Rows:  # 50,000 keys at the registry's pow-2 capacity
        _a = np.zeros((65536, L.NLIMBS), np.int32)

        def arrays(self):
            return self._a, self._a, 50_000

    with captured_dispatches() as seen:
        backend.fast_aggregate_verify_batch_indexed_async(
            [b"a%d" % (i % 12) for i in range(64)], [sig] * 64,
            [[i] for i in range(64)], Rows(),
        )
        backend.multi_verify_async(
            [b"m%d" % i for i in range(480)], [sig] * 480, [pk] * 480,
        )
    out = []
    for kernel, fn, args in seen:
        shape = "x".join(str(d) for d in args[2].shape)
        out.append((f"{kernel}[{shape}]", fn, args))
    return out


def test_cells_route_to_the_expected_kernels():
    labels = [label for label, _fn, _args in cell_kernels()]
    assert labels[0] == "agg_fast_verify_msm_idx[64x4]", labels
    assert labels[1].startswith("multi_verify_msm"), labels


@pytest.mark.slow
@pytest.mark.parametrize("i", range(2))
def test_cell_kernel_compiles_for_v5e(i, one_chip, no_persistent_cache):
    import jax

    label, fn, args = cell_kernels()[i]
    structs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
               for a in args]
    t0 = time.perf_counter()
    compiled = fn.lower(*structs).compile()
    dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    total = (ma.generated_code_size_in_bytes + ma.temp_size_in_bytes
             + ma.argument_size_in_bytes + ma.output_size_in_bytes)
    print(f"{label}: {dt:.1f} s, code {ma.generated_code_size_in_bytes} "
          f"temp {ma.temp_size_in_bytes} args {ma.argument_size_in_bytes} "
          f"out {ma.output_size_in_bytes} total {total} bytes")
    assert total < EXECUTABLE_BUDGET, (label, total)
