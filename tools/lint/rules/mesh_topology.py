"""Rule: no device-topology discovery outside the mesh seam.

The verify plane's multi-device behavior is decided by ONE injected
object — the `VerifyMesh` built in `tpu/mesh.py` and threaded through
node → scheduler/verifier → backend → registry. A stray `jax.devices()`
(or `jax.local_devices()` / `jax.device_count()`) inside the plane makes
topology an ambient global again: dispatch paths would disagree with the
injected mesh about the fleet, single-device degeneracy becomes
unprovable, and tests cannot pin a smaller mesh than the platform
exposes.

Sanctioned exception, by (path, qualname):
  - `VerifyMesh.build` — the one enumeration point the seam itself owns.
"""

from __future__ import annotations

import ast

from tools.lint.core import Context, Finding, Rule, dotted, walk_functions

#: dotted call names that discover device topology ambiently
TOPOLOGY_CALLS = {
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count",
}

#: (path, qualname) pairs allowed to enumerate devices
SANCTIONED = {
    ("grandine_tpu/tpu/mesh.py", "VerifyMesh.build"),
}


class MeshTopologyRule(Rule):
    name = "mesh-topology"
    description = (
        "no jax.devices()/device_count() in the verify plane outside "
        "VerifyMesh.build — topology comes from the injected mesh seam"
    )
    default_paths = (
        "grandine_tpu/tpu/bls.py",
        "grandine_tpu/tpu/mesh.py",
        "grandine_tpu/tpu/registry.py",
        "grandine_tpu/runtime/attestation_verifier.py",
        "grandine_tpu/runtime/verify_scheduler.py",
        "grandine_tpu/runtime/health.py",
        "grandine_tpu/runtime/node.py",
        "grandine_tpu/runtime/replay.py",
        "grandine_tpu/runtime/warmup.py",
    )

    def check(self, ctx: Context, files):
        out: "list[Finding]" = []
        for path in files:
            tree = ctx.tree(path)
            if tree is None:
                continue
            #: node -> owning (cls, fn) for qualname attribution
            owners: "dict[ast.AST, str]" = {}
            for cls, fn in walk_functions(tree):
                qual = f"{cls}.{fn.name}" if cls else fn.name
                for node in ast.walk(fn):
                    owners.setdefault(node, qual)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                name = dotted(node.func)
                if name not in TOPOLOGY_CALLS:
                    continue
                qual = owners.get(node, "<module>")
                if (path, qual) in SANCTIONED:
                    continue
                out.append(Finding(
                    self.name, path, node.lineno,
                    f"{qual} discovers device topology via {name}() — "
                    "the verify plane must take its mesh from the "
                    "injected VerifyMesh seam (tpu/mesh.py)",
                    key=f"{self.name}:{path}:{qual}:{name}",
                ))
        return out
