"""Limb-range abstract interpreter (tools/ranges).

Covers the whole-program run (repo-wide findings = 0 after inline
suppressions), the certificate round-trip and staleness cycle, seeded
per-theorem violation fixtures driven through the actual transfer
functions, suppression scoping, the lint-rule registration, and the
ed25519-vs-BLS constants parametrization.
"""

import os

import numpy as np
import pytest
from fractions import Fraction

from tools.lint.core import Context, Finding
from tools import ranges
from tools.ranges.domain import Aff, LimbVal
from tools.ranges.fields import load_field_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def run():
    ctx = Context(REPO)
    findings, analysis = ranges.analyze(ctx=ctx, check_cert=True)
    return ctx, findings, analysis


# --- whole-program run ------------------------------------------------------


def test_repo_is_clean(run):
    ctx, findings, analysis = run
    assert analysis.root_errors == []
    live = [f for f in findings if not ctx.suppressed(f)]
    assert live == [], [f.render() for f in live]


def test_suppressed_sites_are_the_documented_ones(run):
    """The inline `# lint: disable=limb-range` escape hatch is only used
    at the Fp2-chain sites the README documents — a new suppression has
    to be added here deliberately."""
    ctx, findings, _ = run
    suppressed = {
        (f.path, f.line) for f in findings if ctx.suppressed(f)
    }
    assert {p for p, _ in suppressed} == {
        "grandine_tpu/tpu/field.py",
        "grandine_tpu/tpu/curve.py",
        "grandine_tpu/tpu/pairing.py",
    }
    # every suppressed line carries the annotation in source
    for path, line in suppressed:
        src = ctx.source(path).splitlines()
        assert "lint: disable=limb-range" in src[line - 1]


def test_every_montmul_site_discharges_theorem_a(run):
    """Int32 digit/accumulator safety — the theorem overflow rides on —
    holds at EVERY recorded site, including the suppressed ones."""
    _, _, analysis = run
    assert analysis.rows, "no sites recorded"
    for r in analysis.rows:
        assert not any("theorem a" in v for v in r["violations"]), (
            r["sitekey"], r["violations"])
        if r["max_prod"]:
            assert r["max_prod"] < 1 << 31
        if r["prim"] == "montmul":
            assert 0 < r["max_acc"] < 1 << 22, r["sitekey"]


def test_both_planes_are_analyzed(run):
    _, _, analysis = run
    planes = {r["fp"] for r in analysis.rows}
    assert planes == {"bls", "ed25519"}
    ed_mont = [r for r in analysis.rows
               if r["fp"] == "ed25519" and r["prim"] == "montmul"]
    assert ed_mont, "no ed25519 montmul site recorded"


# --- certificate ------------------------------------------------------------


def test_cert_round_trip_and_determinism(run):
    ctx, _, analysis = run
    want = analysis.cert_text()
    assert want == analysis.cert_text()  # deterministic within a run
    assert ctx.source(ranges.CERT_PATH) == want
    assert "[headroom<=50%]" in want
    assert "[tightest]" in want
    assert "[no-relax-needed]" in want
    # site keys are line-number free: path:function:primitive#ordinal
    for r in analysis.rows:
        assert str(r["line"]) not in r["sitekey"].split(":")


def test_cert_staleness_cycle(run):
    ctx, _, _ = run
    have = ctx.source(ranges.CERT_PATH)

    stale = Context(REPO)
    stale._sources[ranges.CERT_PATH] = have + "# doctored\n"
    findings, _ = ranges.analyze(ctx=stale, check_cert=True)
    assert any(f.key.endswith(":stale") for f in findings)

    missing = Context(REPO)
    missing._sources[ranges.CERT_PATH] = None
    findings, _ = ranges.analyze(ctx=missing, check_cert=True)
    assert any(f.key.endswith(":missing") for f in findings)

    fresh = Context(REPO)
    findings, _ = ranges.analyze(ctx=fresh, check_cert=True)
    assert not any(":stale" in f.key or ":missing" in f.key
                   for f in findings)


# --- seeded per-theorem violations ------------------------------------------


@pytest.fixture()
def live_engine():
    """A live engine outside any root, mirroring ranges._run wiring, so
    transfer functions can be driven directly with seeded bad states."""
    from tools.ranges import engine as eng_mod
    from tools.ranges.engine import Engine
    from tools.ranges.primitives import Recorder, install_operators

    install_operators()
    fields = load_field_params(REPO)
    eng = Engine(REPO, fields, Recorder())
    eng.current_root = "fixture"
    prev = eng_mod.CURRENT
    eng_mod.CURRENT = eng
    yield eng, fields
    eng_mod.CURRENT = prev


def _limb(eng, fp, *, dmag, tmag, hull, canonical=False):
    lo, hi = Fraction(hull[0]), Fraction(hull[1])
    form = Aff.of_sym(eng.tab.fresh(lo, hi))
    return LimbVal(fp, (fp.nlimbs, 4), 0, dmag, tmag, False, canonical,
                   form)


def _violations(eng):
    return [
        v for s in eng.recorder.sites.values() for v in s["violations"]
    ]


def test_seeded_oversized_digit_product_theorem_a(live_engine):
    from tools.ranges.primitives import make_field_transfers

    eng, (bls, _) = live_engine
    t = make_field_transfers(bls)
    big = _limb(eng, bls, dmag=1 << 17, tmag=1 << 17, hull=(-1, 2))
    t["montmul"](big, big)
    viol = _violations(eng)
    assert any("2^31" in v and "theorem a" in v for v in viol), viol


def test_seeded_missing_relax_before_montmul_theorem_b(live_engine):
    from tools.ranges.primitives import make_field_transfers

    eng, (bls, _) = live_engine
    t = make_field_transfers(bls)
    hot = _limb(eng, bls, dmag=bls.lmax, tmag=1 << 11, hull=(-25, 25))
    ok = _limb(eng, bls, dmag=bls.lmax, tmag=1 << 11, hull=(-1, 2))
    t["montmul"](hot, ok)
    viol = _violations(eng)
    assert any("theorem b" in v for v in viol), viol
    # the in-range operand alone must NOT fire
    eng.recorder.sites.clear()
    t["montmul"](ok, ok)
    assert not _violations(eng)


def test_seeded_noncanonical_value_at_equality_fold_theorem_c(live_engine):
    from tools.ranges.primitives import make_field_transfers

    eng, (bls, _) = live_engine
    t = make_field_transfers(bls)
    wide = _limb(eng, bls, dmag=bls.lmax, tmag=1 << 11, hull=(-10, 10))
    t["is_zero_val"](wide)
    viol = _violations(eng)
    assert any("theorem c" in v for v in viol), viol

    eng.recorder.sites.clear()
    negative = _limb(eng, bls, dmag=bls.lmax, tmag=1 << 11, hull=(-1, 2))
    t["canonical_digits"](negative)
    viol = _violations(eng)
    assert any("not within [0, R)" in v for v in viol), viol

    eng.recorder.sites.clear()
    fine = _limb(eng, bls, dmag=bls.lmax, tmag=1 << 11, hull=(-3, 3))
    t["is_zero_val"](fine)
    assert not _violations(eng)


def test_montmul_output_contracts(live_engine):
    """For operands inside the 20p working bound the abstract Montgomery
    product contracts back under 2p — the fact the bound rides on."""
    from tools.ranges.primitives import make_field_transfers

    eng, (bls, _) = live_engine
    t = make_field_transfers(bls)
    a = _limb(eng, bls, dmag=bls.lmax, tmag=1 << 11, hull=(-19, 19))
    out = t["montmul"](a, a)
    lo, hi = out.val.hull(eng.tab)
    assert Fraction(-1) < lo and hi < Fraction(2)
    assert not _violations(eng)


# --- suppression scoping ----------------------------------------------------


def test_suppression_is_line_scoped(run):
    ctx, _, _ = run
    src = ctx.source("grandine_tpu/tpu/field.py").splitlines()
    annotated = next(
        i + 1 for i, l in enumerate(src)
        if "lint: disable=limb-range" in l
    )
    hit = Finding(ranges.RULE, "grandine_tpu/tpu/field.py", annotated,
                  "x", key="limb-range:test:x")
    assert ctx.suppressed(hit)
    # one line off: not suppressed
    miss = Finding(ranges.RULE, "grandine_tpu/tpu/field.py", annotated + 1,
                   "x", key="limb-range:test:y")
    assert not ctx.suppressed(miss)
    # a different rule at the same line: not suppressed
    other = Finding("host-sync", "grandine_tpu/tpu/field.py", annotated,
                    "x", key="host-sync:test:x")
    assert not ctx.suppressed(other)


# --- lint-rule integration --------------------------------------------------


def test_rule_registered_in_default_suite():
    from tools.lint.registry import all_rules

    rules = {r.name: r for r in all_rules()}
    assert "limb-range" in rules
    rule = rules["limb-range"]
    assert rule.kind == "ast"  # rides the default run
    assert tuple(rule.default_paths) == tuple(ranges.DEFAULT_FILES)


def test_rule_findings_have_baseline_stable_keys(run):
    _, findings, _ = run
    for f in findings:
        assert f.key.startswith("limb-range:")
        assert str(f.line) not in f.key.split(":"), f.key


# --- constants parametrization ----------------------------------------------


def test_field_params_parsed_from_source():
    bls, ed = load_field_params(REPO)
    assert (bls.limb_bits, bls.nlimbs) == (15, 26)
    assert (ed.limb_bits, ed.nlimbs) == (15, 18)
    assert bls.p.bit_length() == 381
    assert ed.p == 2**255 - 19
    # R/p: the ed25519 plane contracts much harder (R = 2^270, p ~ 2^255)
    assert bls.r_over_p < 1 << 11
    assert ed.r_over_p > 1 << 14
    # parametrization witness: the same seeded digit bound is int32-safe
    # on the 18-limb plane but oversized on neither/both consistently
    sim_bls = bls.cios(bls.lmax, bls.lmax, bls.lmax)
    sim_ed = ed.cios(ed.lmax, ed.lmax, ed.lmax)
    assert sim_bls["max_prod"] == sim_ed["max_prod"] == bls.lmax**2
    assert sim_bls["max_acc"] > sim_ed["max_acc"]  # 26 vs 18 rows
    assert sim_bls["max_acc"] < 1 << 22
