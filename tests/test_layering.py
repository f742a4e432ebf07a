"""The package's import graph, pinned: SURVEY.md section 1's layer map made
executable.

Every top-level package of `grandine_tpu` has a rank in ONE table
(`RANK`); a package imports only packages of a strictly lower rank, and
nothing in `grandine_tpu` imports `tools`, `benchmark`, `tests` or a
script at the repository root. The arrows that point up today are listed
by `file -> module` in `KNOWN_INVERSIONS` (ROADMAP D10); a second test
fails when a listed arrow no longer exists, so the list only shrinks.

Reads source with `ast`: imports inside functions count like any other
(most of the listed arrows are lazy imports), nothing is executed.
"""

from __future__ import annotations

import ast
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "grandine_tpu"
ROOT = os.path.join(REPO, PACKAGE)

#: bottom up; a package may import only what stands on a LOWER line
LAYERS = (
    # primitives: no imports inside the package (core -> native apart)
    ("native",),
    ("core", "crypto", "tracing", "features", "metrics", "execution"),
    ("ssz",),
    ("types",),
    # spec functions and external-chain clients
    ("consensus", "eth1", "http_clients"),
    ("transition", "kzg"),
    ("fork_choice", "storage", "pools"),
    # the device plane, then what is built on it
    ("tpu",),
    ("slasher",),
    ("runtime",),
    ("p2p",),
    ("validator",),
    ("builder_api", "http_api"),
    ("cli",),
    # test support: importable by no other package
    ("testing", "spec_tests"),
)
RANK = {name: rank for rank, names in enumerate(LAYERS) for name in names}
TEST_SUPPORT = frozenset(LAYERS[-1])

#: never imported from inside the package
OUTSIDE = frozenset({
    "tools", "benchmark", "tests", "chip_smoke", "__graft_entry__",
})

#: arrows that point up today, `file -> imported module` (ROADMAP D10).
#: Straightening one deletes its line here; adding one is a review
#: question, not an edit of this list.
KNOWN_INVERSIONS = frozenset({
    # production code in a test-support package: move snappy out first
    "grandine_tpu/storage/database.py -> grandine_tpu.spec_tests.snappy",
    "grandine_tpu/p2p/network.py -> grandine_tpu.spec_tests.snappy",
    # the Verifier seam builds its device backend itself
    "grandine_tpu/consensus/verifier.py -> grandine_tpu.tpu.bls",
    # kzg's device path lives in kzg/, its scheme entry in tpu/
    "grandine_tpu/kzg/eip4844.py -> grandine_tpu.tpu",
    "grandine_tpu/kzg/eip4844.py -> grandine_tpu.tpu.bls",
    # the compile-phase counters are pulled from tpu/compile_scope.py
    "grandine_tpu/metrics.py -> grandine_tpu.tpu",
    # the device plane reaches up for the node's profiler and for the
    # scheduler's host leaf (host_check_item)
    "grandine_tpu/tpu/bls.py -> grandine_tpu.runtime",
    "grandine_tpu/tpu/schemes.py -> grandine_tpu.runtime",
    # the assembly (node.py) lives in runtime/, under what it assembles
    "grandine_tpu/runtime/node.py -> grandine_tpu.validator",
    "grandine_tpu/runtime/node.py -> grandine_tpu.validator.duties",
})


def _top_level_packages() -> "list[str]":
    names = set()
    for entry in os.listdir(ROOT):
        if entry.startswith("__"):
            continue
        path = os.path.join(ROOT, entry)
        if entry.endswith(".py"):
            names.add(entry[:-3])
        elif os.path.isfile(os.path.join(path, "__init__.py")):
            names.add(entry)
    return sorted(names)


PACKAGES = _top_level_packages()


def _imported_modules(path: str, module: str):
    """Absolute dotted names of everything `path` imports, relative
    imports resolved against `module`'s package."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    is_package = os.path.basename(path) == "__init__.py"
    base = module.split(".") if is_package else module.split(".")[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                prefix = base[: len(base) - (node.level - 1)]
                stem = ".".join(prefix + ([node.module] if node.module else []))
            else:
                stem = node.module
            if stem == PACKAGE:
                # `from grandine_tpu import tracing as _tracing`
                for alias in node.names:
                    yield f"{PACKAGE}.{alias.name}"
            else:
                yield stem


def _arrows():
    """(importing package, file, imported module, imported package) for
    every import that crosses a top-level package boundary or leaves the
    package for the repository's own trees."""
    out = []
    for dirpath, _dirs, files in os.walk(ROOT):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, REPO).replace(os.sep, "/")
            module = rel[:-3].replace("/", ".")
            if module.endswith(".__init__"):
                module = module[: -len(".__init__")]
            parts = module.split(".")
            if len(parts) < 2:
                continue  # grandine_tpu/__init__.py
            owner = parts[1]
            for imported in _imported_modules(path, module):
                head = imported.split(".")
                if head[0] in OUTSIDE:
                    out.append((owner, rel, imported, head[0]))
                elif (head[0] == PACKAGE and len(head) > 1
                        and head[1] in RANK and head[1] != owner):
                    out.append((owner, rel, imported, head[1]))
    return out


ARROWS = _arrows()


def _points_up(owner: str, target: str) -> bool:
    return target in TEST_SUPPORT or RANK[target] >= RANK[owner]


def test_every_package_has_a_rank():
    assert PACKAGES == sorted(RANK), (
        "a new top-level package needs a line in LAYERS (and a removed "
        "one loses it)"
    )


@pytest.mark.parametrize("package", PACKAGES)
def test_package_imports_only_lower_layers(package):
    bad = sorted({
        f"{rel} -> {imported}"
        for owner, rel, imported, target in ARROWS
        if owner == package and (
            target in OUTSIDE
            or (_points_up(owner, target)
                and f"{rel} -> {imported}" not in KNOWN_INVERSIONS)
        )
    })
    assert not bad, (
        f"{package} imports what is not below it (or outside the "
        "package):\n  " + "\n  ".join(bad)
    )


def test_known_inversions_only_shrink():
    """A listed arrow that is gone must leave the list, and a listed
    arrow that now points down was never an inversion."""
    live = {
        f"{rel} -> {imported}"
        for owner, rel, imported, target in ARROWS
        if target not in OUTSIDE and _points_up(owner, target)
    }
    stale = sorted(KNOWN_INVERSIONS - live)
    assert not stale, "straightened: delete from KNOWN_INVERSIONS:\n  " + \
        "\n  ".join(stale)


def test_entry_points_import_without_bench_or_jax():
    """The driver's entry (`__graft_entry__`) imports with no `bench`
    module anywhere on the path and WITHOUT taking JAX: a process that
    has touched JAX holds the chip, and `dryrun_multichip` has to choose
    its platform before any backend exists. The suite's own conftest
    imports without `bench` too."""
    code = (
        "import importlib.util, sys\n"
        "assert importlib.util.find_spec('bench') is None, 'bench is back'\n"
        "import __graft_entry__ as g\n"
        "assert callable(g.entry) and callable(g.dryrun_multichip)\n"
        "assert 'jax' not in sys.modules, 'the entry imported JAX'\n"
        "import tests.conftest\n"
        "assert 'bench' not in sys.modules\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout
