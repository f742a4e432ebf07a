"""Rule: labeled-metric call sites match their declaration.

Prometheus series explode when a label value is unbounded (a slot, a
block root, an f-string). The metrics module already validates ARITY at
runtime; this rule proves it statically at every call site and adds the
check the runtime cannot do: that label VALUES come from bounded sets
(string literals, enum/attribute constants, plain variables that a
human can audit) — never from f-strings, string concatenation, or
str()/format()/hex()/repr() conversions of protocol data.

Declarations are parsed from grandine_tpu/metrics.py (`self.name =
LabeledCounter/LabeledGauge/LabeledHistogram(...)`) and, so fixtures
are self-contained, from each scanned file. Checked operations:
`.labels(...)` plus the family-level shorthands `.inc/.set/.observe/
.time/.value(*label_values, ...)`. Plain (unlabeled) families are also
tracked so a `.labels(...)` call on one is flagged.

Two further checks ride on the same parse:

- identity label NAMES (peer_id, origin, validator_index, ...) are
  banned at the declaration site — one series per network actor is
  unbounded by construction. Per-origin failure attribution belongs in
  the flight recorder's bounded top-K OriginTable, not in a label.
- families listed in _ENUM_LABELS must pass the named label from a
  CLOSED enum: literal values at call sites are checked against the
  tuple constant (e.g. flight.SLO_CAUSES) parsed from source, so a
  typo'd or ad-hoc `cause` can never mint a new series. The `op` label
  of verify_stage_seconds reaches its one `.labels()` call through the
  stage helpers, so `op="..."` literals of `stage(...)` / `_stage(...)`
  calls are held to tracing.STAGE_OPS too.
"""

from __future__ import annotations

import ast
import os

from tools.lint.core import Context, Finding, Rule, dotted

DECLARATIONS = "grandine_tpu/metrics.py"

_LABELED_FACTORIES = {"LabeledCounter", "LabeledGauge", "LabeledHistogram"}
_PLAIN_FACTORIES = {"Counter", "Gauge", "Histogram"}
#: family-level ops whose positional args are label values; the value
#: maps op -> keyword args that are NOT label values
_OPS = {
    "labels": set(),
    "inc": {"amount"},
    "set": {"value"},
    "observe": {"value"},
    "time": set(),
    "value": set(),
}
#: conversions that turn protocol data into unbounded label values
_FORBIDDEN_CONVERSIONS = {"str", "repr", "hex", "format", "bin", "oct"}
#: label names that identify an individual network actor; declaring one
#: makes series count scale with peer/validator population
_IDENTITY_LABELS = {
    "peer", "peer_id", "origin", "sender", "remote",
    "validator", "validator_index", "pubkey", "node_id",
    # profiler capture sessions are monotonically numbered — a
    # session-id label would grow one series per start()
    "session", "session_id", "sid",
}
#: family attr -> (label name(s), canonical module, enum constant name):
#: literal values of those labels must be members of the tuple constant.
#: The first element may be one label name or a tuple of them sharing
#: the same enum (e.g. a transition counter's from/to pair). The
#: constant is parsed from the canonical module and, so fixtures are
#: self-contained, from each scanned file (last parse wins).
_ENUM_LABELS = {
    "verify_slo_miss": (
        "cause", "grandine_tpu/runtime/flight.py", "SLO_CAUSES"
    ),
    "verify_brownout_transitions": (
        ("from", "to"), "grandine_tpu/runtime/brownout.py", "LEVELS"
    ),
    "verify_stage_seconds": (
        "op", "grandine_tpu/tracing.py", "STAGE_OPS"
    ),
    "verify_stage_cpu_seconds": (
        "op", "grandine_tpu/tracing.py", "STAGE_OPS"
    ),
    "verify_device_idle_seconds": (
        "cause", "grandine_tpu/runtime/profiler.py", "IDLE_CAUSES"
    ),
}
#: the stage helpers (tracing.stage and the `_stage` methods that bind a
#: tracer, metrics and a lane to it) take the `op` label as a keyword and
#: hand it to verify_stage_seconds: a literal there is held to the same
#: closed enum as one at a `.labels()` call
_STAGE_HELPERS = {"stage", "_stage"}
_STAGE_OP_ENUM = "verify_stage_seconds"


def _enum_label_tuple(labels) -> "tuple[str, ...]":
    return (labels,) if isinstance(labels, str) else tuple(labels)


class _Family:
    def __init__(self, name: str, labelnames: "tuple[str, ...]",
                 defaults: "frozenset[str]") -> None:
        self.name = name
        self.labelnames = labelnames
        self.defaults = defaults
        # only TRAILING defaulted labels may be omitted positionally
        # (labels() fills the tail from `defaults`)
        omittable = 0
        for n in reversed(labelnames):
            if n not in defaults:
                break
            omittable += 1
        self.min_arity = len(labelnames) - omittable
        self.max_arity = len(labelnames)


def _const_str_tuple(node: "ast.AST | None") -> "tuple[str, ...] | None":
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
            else:
                return None
        return tuple(out)
    return None


def _parse_declarations(tree: ast.AST) -> "dict[str, _Family | None]":
    """attr name -> _Family for labeled families, None for plain ones."""
    out: "dict[str, _Family | None]" = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        target = node.targets[0]
        if not (
            isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Name)
            and target.value.id == "self"
        ):
            continue
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        factory = dotted(call.func)
        factory = factory.rsplit(".", 1)[-1] if factory else None
        if factory in _PLAIN_FACTORIES:
            out[target.attr] = None
            continue
        if factory not in _LABELED_FACTORIES:
            continue
        labelnames = None
        if len(call.args) >= 3:
            labelnames = _const_str_tuple(call.args[2])
        defaults: "set[str]" = set()
        for kw in call.keywords:
            if kw.arg == "labelnames":
                labelnames = _const_str_tuple(kw.value)
            elif kw.arg == "defaults" and isinstance(kw.value, ast.Dict):
                for k in kw.value.keys:
                    if isinstance(k, ast.Constant) and isinstance(
                        k.value, str
                    ):
                        defaults.add(k.value)
        if labelnames is not None:
            out[target.attr] = _Family(
                target.attr, labelnames, frozenset(defaults)
            )
    return out


def _declared_labelnames(tree: ast.AST):
    """(lineno, attr, labelnames) per labeled-family declaration —
    the positional walk _parse_declarations does, kept separate because
    this one needs source positions for declaration-site findings."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        target = node.targets[0]
        if not (
            isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Name)
            and target.value.id == "self"
        ):
            continue
        call = node.value
        if not isinstance(call, ast.Call):
            continue
        factory = dotted(call.func)
        factory = factory.rsplit(".", 1)[-1] if factory else None
        if factory not in _LABELED_FACTORIES:
            continue
        labelnames = None
        if len(call.args) >= 3:
            labelnames = _const_str_tuple(call.args[2])
        for kw in call.keywords:
            if kw.arg == "labelnames":
                labelnames = _const_str_tuple(kw.value)
        if labelnames:
            yield node.lineno, target.attr, labelnames


def _parse_enum_consts(
    tree: ast.AST, wanted: "set[str]"
) -> "dict[str, frozenset[str]]":
    """Module-level `NAME = ("a", "b", ...)` string-tuple assignments
    for the constant names in `wanted`."""
    out: "dict[str, frozenset[str]]" = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        target = node.targets[0]
        if not (isinstance(target, ast.Name) and target.id in wanted):
            continue
        vals = _const_str_tuple(node.value)
        if vals is not None:
            out[target.id] = frozenset(vals)
    return out


def _bad_value(node: ast.AST) -> "str | None":
    """Why this label-value expression is unbounded, or None if OK."""
    if isinstance(node, ast.JoinedStr):
        return "an f-string"
    if isinstance(node, ast.BinOp):
        return "string arithmetic"
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _FORBIDDEN_CONVERSIONS:
            return f"{fn.id}(...)"
        if isinstance(fn, ast.Attribute) and fn.attr == "format":
            return ".format(...)"
    if isinstance(node, ast.IfExp):
        return _bad_value(node.body) or _bad_value(node.orelse)
    return None


class MetricsCardinalityRule(Rule):
    name = "metrics-cardinality"
    description = (
        "labeled-metric call sites pass exactly the declared label "
        "names/arity, with values from bounded sets (no f-strings or "
        "str()-of-protocol-data); no identity labels (peer_id, "
        "validator_index, ...); enum-bounded labels stay in their enum"
    )

    def files(self, ctx: Context, targets):
        if targets:
            return [t for t in targets if ctx.source(t) is not None]
        out = []
        pkg = os.path.join(ctx.root, "grandine_tpu")
        for dirpath, _dirnames, filenames in os.walk(pkg):
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                rel = os.path.relpath(
                    os.path.join(dirpath, fname), ctx.root
                ).replace(os.sep, "/")
                if rel != DECLARATIONS:
                    out.append(rel)
        return out

    def check(self, ctx: Context, files):
        families: "dict[str, _Family | None]" = {}
        decl_tree = ctx.tree(DECLARATIONS)
        if decl_tree is not None:
            families.update(_parse_declarations(decl_tree))
        for path in files:
            tree = ctx.tree(path)
            if tree is not None:
                families.update(_parse_declarations(tree))

        # closed-enum members for _ENUM_LABELS: canonical modules
        # first, then scanned files so fixtures stay self-contained
        wanted = {const for _lbl, _src, const in _ENUM_LABELS.values()}
        enum_consts: "dict[str, frozenset[str]]" = {}
        sources = sorted({src for _lbl, src, _c in _ENUM_LABELS.values()})
        for src in sources:
            tree = ctx.tree(src)
            if tree is not None:
                enum_consts.update(_parse_enum_consts(tree, wanted))
        for path in files:
            tree = ctx.tree(path)
            if tree is not None:
                enum_consts.update(_parse_enum_consts(tree, wanted))
        enums: "dict[str, tuple[tuple[str, ...], frozenset[str]]]" = {}
        for attr, (labels, _src, const) in _ENUM_LABELS.items():
            allowed = enum_consts.get(const)
            if allowed:
                enums[attr] = (_enum_label_tuple(labels), allowed)

        out: "list[Finding]" = []
        decl_paths = [DECLARATIONS] + [p for p in files
                                       if p != DECLARATIONS]
        for path in decl_paths:
            tree = ctx.tree(path)
            if tree is None:
                continue
            for lineno, attr, labelnames in _declared_labelnames(tree):
                bad = [n for n in labelnames if n in _IDENTITY_LABELS]
                if bad:
                    out.append(Finding(
                        self.name, path, lineno,
                        f"{attr} declares identity label(s) {bad} — "
                        f"one series per peer/validator is unbounded; "
                        f"attribute per-origin data through the flight "
                        f"recorder's bounded top-K table instead",
                        key=(f"{self.name}:{path}:{attr}:identity:"
                             f"{','.join(bad)}"),
                    ))
        for path in files:
            tree = ctx.tree(path)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    out.extend(
                        self._check_call(path, node, families, enums)
                    )
        return out

    def _check_stage_op(self, path, call: ast.Call, enums):
        """`op="..."` literals of stage-helper calls, against STAGE_OPS."""
        fn = call.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None
        )
        enum = enums.get(_STAGE_OP_ENUM)
        if name not in _STAGE_HELPERS or enum is None:
            return
        for kw in call.keywords:
            if (
                kw.arg == "op"
                and isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, str)
                and kw.value.value not in enum[1]
            ):
                yield Finding(
                    self.name, path, kw.value.lineno,
                    f"stage helper called with op={kw.value.value!r} — "
                    f"not a member of the closed enum STAGE_OPS "
                    f"(grandine_tpu/tracing.py); it would read \"other\"",
                    key=(f"{self.name}:{path}:{_STAGE_OP_ENUM}:enum:op:"
                         f"{kw.value.value}"),
                )

    def _check_call(self, path, call: ast.Call, families, enums):
        yield from self._check_stage_op(path, call, enums)
        fn = call.func
        if not (isinstance(fn, ast.Attribute) and fn.attr in _OPS):
            return
        owner = fn.value
        if not isinstance(owner, ast.Attribute):
            return
        fam = families.get(owner.attr, "absent")
        if fam == "absent":
            return
        op = fn.attr
        if fam is None:
            if op == "labels":
                yield Finding(
                    self.name, path, call.lineno,
                    f"{owner.attr} is an unlabeled family — .labels() "
                    f"does not exist on it",
                    key=f"{self.name}:{path}:{owner.attr}:labels-on-plain",
                )
            return

    # ---- labeled family: arity, names, value boundedness
        non_label_kw = _OPS[op]
        label_args = list(call.args)
        label_kwargs = [
            kw for kw in call.keywords
            if kw.arg is not None and kw.arg not in non_label_kw
        ]
        if any(isinstance(a, ast.Starred) for a in label_args) or any(
            kw.arg is None for kw in call.keywords
        ):
            return  # *values / **kw: not statically checkable

        if op == "labels" and label_kwargs:
            names = {kw.arg for kw in label_kwargs}
            unknown = names - set(fam.labelnames)
            required = {
                n for n in fam.labelnames if n not in fam.defaults
            }
            missing = required - names
            if unknown:
                yield Finding(
                    self.name, path, call.lineno,
                    f"{fam.name}.labels() passes undeclared label(s) "
                    f"{sorted(unknown)} (declared: "
                    f"{list(fam.labelnames)})",
                    key=(f"{self.name}:{path}:{fam.name}:unknown:"
                         f"{','.join(sorted(unknown))}"),
                )
            if missing:
                yield Finding(
                    self.name, path, call.lineno,
                    f"{fam.name}.labels() omits required label(s) "
                    f"{sorted(missing)}",
                    key=(f"{self.name}:{path}:{fam.name}:missing:"
                         f"{','.join(sorted(missing))}"),
                )
            values = [kw.value for kw in label_kwargs]
        else:
            if label_kwargs and op != "labels":
                # e.g. observe(stage="x", value=...) — shorthand ops
                # take label values positionally only
                yield Finding(
                    self.name, path, call.lineno,
                    f"{fam.name}.{op}() passes label values by keyword "
                    f"({[kw.arg for kw in label_kwargs]}) — the "
                    f"shorthand ops take them positionally",
                    key=f"{self.name}:{path}:{fam.name}:{op}:kwargs",
                )
            n = len(label_args)
            if not (fam.min_arity <= n <= fam.max_arity):
                expect = (
                    str(fam.max_arity)
                    if fam.min_arity == fam.max_arity
                    else f"{fam.min_arity}..{fam.max_arity}"
                )
                yield Finding(
                    self.name, path, call.lineno,
                    f"{fam.name}.{op}() passes {n} label value(s), "
                    f"declaration {list(fam.labelnames)} expects "
                    f"{expect}",
                    key=f"{self.name}:{path}:{fam.name}:{op}:arity:{n}",
                )
            values = label_args

        for v in values:
            why = _bad_value(v)
            if why:
                yield Finding(
                    self.name, path, v.lineno,
                    f"{fam.name}.{op}() label value built from {why} — "
                    f"unbounded label cardinality; use a literal or "
                    f"enum value",
                    key=(f"{self.name}:{path}:{fam.name}:{op}:"
                         f"unbounded:{why}"),
                )

        # ---- closed-enum labels: literal values must be members
        enum = enums.get(owner.attr)
        if enum is not None:
            labels, allowed = enum
            for label in labels:
                value_node = None
                if op == "labels" and label_kwargs:
                    for kw in label_kwargs:
                        if kw.arg == label:
                            value_node = kw.value
                elif label in fam.labelnames:
                    i = fam.labelnames.index(label)
                    if i < len(label_args):
                        value_node = label_args[i]
                if (
                    isinstance(value_node, ast.Constant)
                    and isinstance(value_node.value, str)
                    and value_node.value not in allowed
                ):
                    yield Finding(
                        self.name, path, value_node.lineno,
                        f"{fam.name}.{op}() passes "
                        f"{label}={value_node.value!r} — not a member "
                        f"of the closed enum {sorted(allowed)}",
                        key=(f"{self.name}:{path}:{fam.name}:enum:"
                             f"{label}:{value_node.value}"),
                    )
