"""The verify plane's device-mesh seam.

Every multi-device decision in the verify plane flows through ONE object
built here: a `VerifyMesh` wrapping a 1-D `jax.sharding.Mesh` over the
`"batch"` axis (the SNIPPETS [1]-[3] pjit/shard_map exemplars). The seam
exists so that

  - device topology is INJECTED, never discovered, inside dispatch paths
    (`tools/lint` forbids `jax.devices()` calls there — `VerifyMesh.build`
    below is the single sanctioned enumeration point);
  - the single-device node is the degenerate case: `device_count == 1`
    makes every consumer behave exactly as if no mesh existed (no
    `NamedSharding` placements, same jit cache keys, same executables),
    so `verify_recompiles_total == 0` steady-state and all single-chip
    behavior hold unchanged;
  - sharding layouts are named once: batch-dim sharding for per-signature
    operands and registry rows, `P(None, "batch")` for (M, K) grouped
    member arrays, replication for per-group messages.

The mesh is 1-D on purpose. The workload's only cross-chip reduction is
the pairing-product all-gather (a few KB per chip — see
`tpu/bls.py make_sharded_multi_verify`); a second mesh axis buys nothing
until single-axis scaling saturates ICI (no chip reading yet: ROADMAP
R-B5).
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

#: the one mesh axis name the verify plane shards over
BATCH_AXIS = "batch"


class VerifyMesh:
    """An injected device mesh + its named sharding vocabulary.

    Construction is lazy-import friendly: building a `VerifyMesh` touches
    jax (backend initialization), so runtime modules hold `mesh=None`
    until a caller that already owns a jax backend hands one in.
    """

    def __init__(self, devices: "Sequence", axis: str = BATCH_AXIS) -> None:
        from jax.sharding import Mesh

        devices = list(devices)
        if not devices:
            raise ValueError("VerifyMesh needs at least one device")
        n = len(devices)
        if n & (n - 1):
            raise ValueError(
                f"VerifyMesh needs a power-of-two device count, got {n}"
            )
        self.axis = axis
        self.mesh = Mesh(np.array(devices), (axis,))
        self.devices = tuple(devices)

    # ----------------------------------------------------------- topology

    @property
    def device_count(self) -> int:
        return len(self.devices)

    @property
    def is_single(self) -> bool:
        """True for the degenerate 1-device mesh — consumers must treat
        this exactly like `mesh is None` (no placements, no sharded
        kernels) so single-chip behavior stays byte-identical."""
        return self.device_count == 1

    def describe(self) -> str:
        """Stable shape string for flight records / bench JSON (a field,
        never a Prometheus label)."""
        return f"{self.axis}:{self.device_count}"

    def divides(self, n: int) -> bool:
        """True when a length-n batch axis shards evenly over the mesh."""
        return n >= self.device_count and n % self.device_count == 0

    # ---------------------------------------------------------- shardings

    def batch_sharding(self):
        """Rows sharded over the mesh: per-signature operands, registry
        rows, per-chip plan stacks — `P("batch")` on axis 0."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(self.axis))

    def member_sharding(self):
        """(M, K, ...) grouped member arrays sharded over K —
        `P(None, "batch")`."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(None, self.axis))

    def replicated(self):
        """One full copy per device: per-group messages, small scalars."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    def put(self, arrays: tuple, sharding) -> tuple:
        """Place a tuple of host arrays with one explicit sharding."""
        import jax

        return tuple(jax.device_put(a, sharding) for a in arrays)

    # -------------------------------------------------------- construction

    @classmethod
    def build(cls, count: "Optional[int]" = None,
              platform: "Optional[str]" = None) -> "VerifyMesh":
        """Enumerate devices and build the mesh — the ONE place the verify
        plane calls `jax.devices()`. `count=None` takes every visible
        device (rounded down to a power of two); an explicit `count` must
        be satisfiable or this raises.

        On the CPU platform the visible device count comes from
        `XLA_FLAGS=--xla_force_host_platform_device_count=N`, which XLA
        parses once per process BEFORE the first backend call — callers
        wanting an N-device CPU mesh must set it pre-import
        (`__graft_entry__.dryrun_multichip` asks for a fresh process for
        exactly this reason).
        """
        import jax

        devices = jax.devices(platform) if platform else jax.devices()
        if count is None:
            count = 1 << (len(devices).bit_length() - 1)
        if count < 1 or count > len(devices):
            raise ValueError(
                f"mesh of {count} devices requested, platform has "
                f"{len(devices)}"
            )
        return cls(devices[:count])


def mesh_or_none(mesh: "Optional[VerifyMesh]") -> "Optional[VerifyMesh]":
    """Normalize the degenerate mesh: a 1-device VerifyMesh and None are
    the SAME configuration to every consumer; collapsing here keeps the
    `mesh is None or mesh.is_single` predicate out of call sites."""
    if mesh is None or mesh.is_single:
        return None
    return mesh


__all__ = ["VerifyMesh", "mesh_or_none", "BATCH_AXIS"]
