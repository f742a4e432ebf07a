"""Rule: gossip handlers route signature checks through the verify
scheduler, never inline.

No `_on_gossip_*` method may call `.verify(...)` /
`.fast_aggregate_verify(...)` / `.aggregate_verify(...)` or reference
`SingleVerifier` — the only sanctioned eager path is the whitelisted
fallback helper `_eager_verify_items`, reached via `_dispatch_verify`
when no scheduler is wired. The `Network` class must keep that helper
so the rule cannot be "passed" by deleting the degradation path.
"""

from __future__ import annotations

import ast

from tools.lint.core import Context, Finding, Rule

#: eager-verification surface a handler must not touch directly
FORBIDDEN_CALLS = {"verify", "fast_aggregate_verify", "aggregate_verify"}
FORBIDDEN_NAMES = {"SingleVerifier"}
#: the sanctioned degradation path (reached through _dispatch_verify)
WHITELISTED_HELPERS = {"_eager_verify_items"}


def _violations_in(method: ast.FunctionDef):
    for node in ast.walk(method):
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr in FORBIDDEN_CALLS:
                yield node.lineno, f".{fn.attr}(...)"
            if isinstance(fn, ast.Name) and fn.id in FORBIDDEN_NAMES:
                yield node.lineno, f"{fn.id}(...)"
        elif isinstance(node, ast.Name) and node.id in FORBIDDEN_NAMES:
            yield node.lineno, node.id


class NoInlineGossipVerifyRule(Rule):
    name = "no-inline-gossip-verify"
    description = (
        "gossip handlers must submit signatures to the verify scheduler "
        "(or the whitelisted eager fallback), never verify inline"
    )
    default_paths = ("grandine_tpu/p2p/network.py",)

    def check(self, ctx: Context, files):
        out: "list[Finding]" = []
        for path in files:
            tree = ctx.tree(path)
            if tree is None:
                continue
            classes = [
                n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)
            ]
            for cls in classes:
                methods = {
                    n.name: n for n in cls.body
                    if isinstance(n, ast.FunctionDef)
                }
                handlers = {
                    k: v for k, v in methods.items()
                    if k.startswith("_on_gossip_")
                }
                for name, method in sorted(handlers.items()):
                    for lineno, what in _violations_in(method):
                        out.append(Finding(
                            self.name, path, lineno,
                            f"{cls.name}.{name} verifies inline via {what}"
                            " — submit to the verify scheduler (or let "
                            "_dispatch_verify degrade to the whitelisted "
                            "fallback)",
                            key=f"{self.name}:{path}:{name}:{what}",
                        ))
                if cls.name == "Network" and handlers:
                    for missing in sorted(
                        WHITELISTED_HELPERS - set(methods)
                    ):
                        out.append(Finding(
                            self.name, path, cls.lineno,
                            f"whitelisted fallback helper "
                            f"Network.{missing} is gone — the "
                            f"no-scheduler degradation path must keep "
                            f"existing",
                            key=f"{self.name}:{path}:missing:{missing}",
                        ))
        return out
