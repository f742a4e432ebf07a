"""Compile the main path for a DESCRIBED TPU v5e — no chip attached.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached (`on-chip-measurement` guide, section 2).
What it refuses here it would refuse on the chip, so these tests guard
every later PR at no chip time.

Tier-1: the main path's building blocks at real width (each compiles in
seconds). `slow`: the four verify executables `chip_smoke.py` dispatches
(~3 min and ~6 GB of host memory each — run by hand before a chip run:
`pytest tests/test_tpu_compile.py -m slow`), each held to the device
budget of CHANGES.md PR 22.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load libtpu, and xdist workers
each import every test file. Compiles run in the test's own process, with
the persistent cache (on for the whole suite, tests/conftest.py) off: a
deviceless executable is written to it but cannot be read back.
"""

import contextlib
import re
import time

import numpy as np
import pytest

import chip_smoke as S

#: device budget per verify executable (generated code + temp + args +
#: output), bytes. Measured 0.2–0.9 GB (CHANGES.md PR 22 table); the smoke
#: holds four of them plus the registry inside half of a 16 GB chip.
EXECUTABLE_BUDGET = 1_500_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _structs(args, sharding):
    import jax

    return [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        for a in args
    ]


def _compile(fn, structs):
    """Lower + compile `fn` for the described chip; returns (compiled,
    seconds, device bytes: generated code + temp + args + output)."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jitted.lower(*structs).compile()
    dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    total = (
        ma.generated_code_size_in_bytes + ma.temp_size_in_bytes
        + ma.argument_size_in_bytes + ma.output_size_in_bytes
    )
    return compiled, dt, total


# ------------------------------------------------- tier-1: building blocks


def _limbs(*batch):
    from grandine_tpu.tpu import limbs as L

    return np.zeros((L.NLIMBS,) + batch, np.int32)


def _case_montmul():
    from grandine_tpu.tpu import limbs as L

    return L.montmul, (_limbs(16384), _limbs(16384))


def _case_point_add():
    from grandine_tpu.tpu import curve as C

    def add(px, py, pz, qx, qy, qz):
        return C.point_add_complete((px, py, pz), (qx, qy, qz), C.FP_OPS)

    return add, tuple(_limbs(16384) for _ in range(6))


def _case_registry_gather():
    """The indexed kernels' first step at the 50k-validator capacity: a
    row gather of 64 committees x 256 members out of 65,536 rows."""
    import jax.numpy as jnp

    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import limbs as L

    m, k = S.AGG_ITEMS, 256
    cap = S.registry_capacity(S.REGISTRY_KEYS)

    def gather(reg_x, reg_y, mem_idx):
        idx = B._flat_km(mem_idx, m, k)
        return B._g1_in(
            jnp.take(reg_x, idx, axis=0), jnp.take(reg_y, idx, axis=0)
        )

    reg = np.zeros((cap, L.NLIMBS), np.int32)
    return gather, (reg, reg, np.zeros((m, k), np.int32))


def _case_g1_decompress():
    from grandine_tpu.tpu import bls as B

    return B.g1_decompress_kernel, (np.zeros((1024, 48), np.uint8),)


BLOCKS = {
    "montmul_16384": _case_montmul,
    "point_add_complete_g1_16384": _case_point_add,
    "registry_gather_cap65536_64x256": _case_registry_gather,
    "g1_decompress_1024": _case_g1_decompress,
}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_building_block_compiles_for_v5e(name, one_chip):
    fn, args = BLOCKS[name]()
    _compiled, dt, total = _compile(fn, _structs(args, one_chip))
    assert total < EXECUTABLE_BUDGET, (name, total)
    print(f"{name}: {dt:.1f}s, {total} bytes")


# ----------------------- tier-1: the batch stays on the lanes (LANE_FLOOR)


def _miller_structs(n, sharding):
    import jax

    def fp():
        return jax.ShapeDtypeStruct((26, n), np.int32, sharding=sharding)

    def fp2():
        return (fp(), fp())

    inf = jax.ShapeDtypeStruct((n,), np.bool_, sharding=sharding)
    return (fp(), fp(), fp()), (fp2(), fp2(), fp2()), inf


#: a 2-D limb array with the limbs on the lanes, `s32[26,n]{0,1:...}`
_LIMBS_ON_LANES = re.compile(r"s32\[26,\d+\]\{0,1[:}]")


@pytest.mark.parametrize("width, where", [(65, "anywhere"), (1, "in_a_loop")])
def test_miller_loop_keeps_the_batch_on_the_lanes(width, where, one_chip):
    """A narrow (26, n) array the compiler lays out {0,1}: the 26 limbs on
    the 128 lanes, the slow design of limbs.py's docstring (38.2 ms a
    Miller loop at width 1 against 13.6 at 64, ledger PR 32). At the
    verify call's folded width (64 message pairs + the signature pair)
    no limb array of the program is laid out so; at width 1 the loop pads
    itself to limbs.LANE_FLOOR, so no loop CARRIES one (the arguments,
    one lane wide, still are)."""
    import jax

    from grandine_tpu.tpu import pairing as TP

    t0 = time.perf_counter()
    compiled = jax.jit(TP.miller_loop).lower(
        *_miller_structs(width, one_chip)
    ).compile()
    text = compiled.as_text()
    print(f"miller_loop[{width}]: {time.perf_counter() - t0:.1f}s")
    if where == "in_a_loop":
        text = "\n".join(
            line.split(" while(")[0] for line in text.splitlines()
            if " while(" in line
        )
    assert "s32[26," in text
    assert not sorted(set(_LIMBS_ON_LANES.findall(text)))


# ------------------------------------- slow: the smoke's verify executables


@contextlib.contextmanager
def captured_dispatches():
    """Replace the backend's dispatch with a recorder: host prep, plans
    and uploads run for real on the CPU, the jitted kernel is not called.
    Yields the list of (kernel, jitted fn, args) the path would dispatch.
    This is how the smoke's (kernel, bucket, capacity) set is FOUND, from
    the program's own routing, instead of being written down twice."""
    from grandine_tpu.tpu import bls as B

    seen = []
    real = B.TpuBlsBackend._run_kernel

    def record(self, kernel, fn, args, sigs=0, block=True,
               mesh_operands=False):
        seen.append((kernel, fn, tuple(args)))
        return np.True_

    B.TpuBlsBackend._run_kernel = record
    try:
        yield seen
    finally:
        B.TpuBlsBackend._run_kernel = real


def smoke_verify_kernels():
    """[(label, jitted fn, args)] — the verify executables chip_smoke.py
    compiles, in its order, at its widths, with donation as the backend
    defaults it on the chip. Points are one valid point repeated: only
    shapes matter to a compile."""
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu import limbs as L

    backend = B.TpuBlsBackend(donate_buffers=True)
    pk = A.PublicKey(G1)
    sig = A.Signature(hash_to_g2(b"shape"))

    class _Rows:  # a registry of the right capacity, all-zero rows
        def __init__(self, count):
            cap = S.registry_capacity(count)
            self._a = np.zeros((cap, L.NLIMBS), np.int32)
            self._n = count

        def arrays(self):
            return self._a, self._a, self._n

    out = []
    with captured_dispatches() as seen:
        n = S.PLANE_N
        backend.multi_verify_async(
            [b"m%d" % (i % S.PLANE_MSGS) for i in range(n)],
            [sig] * n, [pk] * n,
        )
        backend.fast_aggregate_verify_batch_indexed_async(
            [b"a%d" % i for i in range(S.AGG_ITEMS)],
            [sig] * S.AGG_ITEMS,
            [list(range(S.AGG_WIDTH))] * S.AGG_ITEMS,
            _Rows(S.REGISTRY_KEYS),
        )
        per_slot, width = S.NODE_COMMITTEES_PER_SLOT, S.NODE_COMMITTEE_SIZE
        backend.fast_aggregate_verify_batch_indexed_async(
            [b"n%d" % i for i in range(per_slot)],
            [sig] * per_slot,
            [list(range(width))] * per_slot,
            _Rows(S.NODE_VALIDATORS),
            # as the node's verifier names it: its one batch bucket
            bucket_floor=(S.AGG_ITEMS, 0),
        )
    for kernel, fn, args in seen:
        shape = "x".join(str(d) for d in args[2].shape)
        out.append((f"{kernel}[{shape}]", fn, args))
    return out


def test_smoke_routes_to_the_expected_kernels():
    """Cheap (no compile): the widest batch reaches the grouped MSM
    kernel at (256, 64) and the two registry batches the indexed
    aggregate kernel — the executables the budget below is taken for."""
    labels = [label for label, _fn, _args in smoke_verify_kernels()]
    assert labels == [
        "grouped_multi_verify_msm[256x64]",
        "agg_fast_verify_msm_idx[64x256]",
        "agg_fast_verify_msm_idx[64x4]",
    ]


@pytest.mark.slow
@pytest.mark.parametrize("i", range(3))
def test_smoke_verify_kernel_fits_budget(i, one_chip):
    label, fn, args = smoke_verify_kernels()[i]
    _compiled, dt, total = _compile(fn, _structs(args, one_chip))
    print(f"{label}: {dt:.1f}s, {total} bytes")
    assert total < EXECUTABLE_BUDGET, (label, total)


def mesh_verify_kernel(topo):
    """(label, jitted fn, arg structs) of the sharded grouped verify that
    `chip_smoke.py --chips 4` dispatches, built over a four-device mesh of
    the DESCRIBED chips: the backend's own routing and plans, with the
    placement recorded instead of performed (nothing can be put on a
    device that is not attached)."""
    import jax

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.curves import G1
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu.mesh import VerifyMesh

    backend = B.TpuBlsBackend(mesh=VerifyMesh(topo.devices))
    placed = {}

    def record_upload(args, shardings, kernel):
        placed["shardings"] = tuple(shardings)
        return tuple(args)

    backend._upload_sharded = record_upload
    pk = A.PublicKey(G1)
    sig = A.Signature(hash_to_g2(b"shape"))
    n = S.MESH_N
    with captured_dispatches() as seen:
        backend.multi_verify_async(
            [b"m%d" % (i % S.MESH_MSGS) for i in range(n)],
            [sig] * n, [pk] * n,
        )
    ((kernel, fn, args),) = seen
    structs = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
        for a, sh in zip(args, placed["shardings"])
    ]
    return f"{kernel}[{S.MESH_MSGS}x{n // S.MESH_MSGS}]", fn, structs


@pytest.mark.slow
def test_sharded_verify_compiles_for_four_chips(topo):
    label, fn, structs = mesh_verify_kernel(topo)
    assert label == "sharded_multi_verify_msm[64x64]"
    compiled, dt, total = _compile(fn, structs)
    print(f"{label}: {dt:.1f}s, {total} bytes/device")
    assert total < EXECUTABLE_BUDGET, (label, total)
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text, (
        "no collective in a four-chip program"
    )
