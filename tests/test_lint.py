"""grandine-lint suite tests: the repo itself is clean, every rule
fires on a seeded violation, allowlisted idioms stay quiet, and the
suppression/baseline mechanisms work. Plus regression tests for the two
sync-gossip validation gaps the suite's introduction fixed: forged
aggregator selection proofs / outer SignedContributionAndProof
signatures are rejected, and sync-committee membership resolves from
the message slot's period rather than the head state's.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(tmp_path, source: str, rule: str, *extra: str) -> int:
    """Write one fixture file into an isolated root and run one rule
    over it through the real CLI; returns the exit code."""
    from tools.lint.__main__ import main

    fixture = tmp_path / "fixture.py"
    fixture.write_text(source)
    return main([
        "fixture.py", "--rules", rule, "--no-baseline",
        "--root", str(tmp_path), *extra,
    ])


# ------------------------------------------------------------ full suite


def test_lint_clean_on_repo():
    """`python -m tools.lint` exits 0 on the repo: every finding fixed,
    suppressed with a reason, or baselined."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint"], cwd=REPO,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout


# --------------------------------------------------- seeded violations


def test_host_sync_flags_dispatch_path_readback(tmp_path):
    assert lint(tmp_path, """
import numpy as np
import jax

class Backend:
    def verify_batch_async(self, sigs):
        dev = self._run(sigs)
        out = np.asarray(dev)
        dev.block_until_ready()
        return out
""", "host-sync") == 1


def test_host_sync_allows_settle_closure_and_jnp(tmp_path):
    """The sanctioned idiom: forcing lives in the nested settle closure;
    jnp.asarray is a device-side tracer, not a readback."""
    assert lint(tmp_path, """
import numpy as np
import jax.numpy as jnp

class Backend:
    def verify_batch_async(self, sigs):
        dev = self._run(jnp.asarray(sigs))
        def settle():
            return bool(np.asarray(dev).all())
        return settle
""", "host-sync") == 0


def test_lock_order_flags_cycle_and_bare_read(tmp_path):
    assert lint(tmp_path, """
import threading

class Sched:
    def __init__(self):
        self.a = threading.Lock()
        self.b = threading.Lock()
        self.depth = 0

    def submit(self):
        with self.a:
            with self.b:
                self.depth += 1

    def drain(self):
        with self.b:
            with self.a:
                self.depth -= 1

    def peek(self):
        return self.depth
""", "lock-order") == 1


def test_lock_order_allows_lock_held_private_helper(tmp_path):
    """A private method called only from locked regions is lock-held by
    contract — its bare reads are guarded (registry._append idiom)."""
    assert lint(tmp_path, """
import threading

class Reg:
    def __init__(self):
        self.lock = threading.RLock()
        self.rows = None

    def ensure(self, rows):
        with self.lock:
            self.rows = rows
            self._grow()

    def _grow(self):
        return len(self.rows)
""", "lock-order") == 0


def test_metrics_cardinality_flags_arity_names_and_fstrings(tmp_path):
    code = lint(tmp_path, """
from grandine_tpu.metrics import LabeledCounter

class M:
    def __init__(self):
        self.hits = LabeledCounter("hits_total", "h", ("kind",))

class U:
    def use(self, m, slot):
        m.hits.inc("block", "extra")
        m.hits.labels(kindd="block")
        m.hits.inc(f"slot-{slot}")
        m.hits.inc(str(slot))
""", "metrics-cardinality")
    assert code == 1


def test_metrics_cardinality_allows_defaults_and_literals(tmp_path):
    """Omitting a trailing defaulted label and passing literal/attribute
    values is the declared contract (verify_stage_seconds idiom)."""
    assert lint(tmp_path, """
from grandine_tpu.metrics import LabeledHistogram

class M:
    def __init__(self):
        self.stage = LabeledHistogram(
            "stage_seconds", "h", ("stage", "lane"),
            defaults={"lane": "attestation"},
        )

class U:
    def use(self, m, lane_cfg):
        m.stage.labels("execute")
        m.stage.labels("execute", "sync_message")
        m.stage.observe("readback", lane_cfg.name, value=0.1)
""", "metrics-cardinality") == 0


def test_metrics_cardinality_flags_identity_labels(tmp_path):
    """Declaring a per-actor label mints one series per peer — the
    bounded home for that attribution is the flight recorder's
    OriginTable, never a Prometheus label."""
    code = lint(tmp_path, """
from grandine_tpu.metrics import LabeledCounter

class M:
    def __init__(self):
        self.rejects = LabeledCounter(
            "gossip_rejects_total", "h", ("topic", "peer_id"),
        )
""", "metrics-cardinality")
    assert code == 1


def test_metrics_cardinality_flags_slo_cause_outside_enum(tmp_path):
    """Literal `cause` values on verify_slo_miss must be members of
    the SLO_CAUSES tuple (parsed from source, here the fixture's own
    module-level constant)."""
    code = lint(tmp_path, """
from grandine_tpu.metrics import LabeledCounter

SLO_CAUSES = ("queue_wait", "device", "bisection", "breaker_open")

class M:
    def __init__(self):
        self.verify_slo_miss = LabeledCounter(
            "verify_slo_miss_total", "h", ("lane", "cause"),
        )

class U:
    def use(self, m):
        m.verify_slo_miss.inc("block", "coffee_break")
""", "metrics-cardinality")
    assert code == 1


def test_metrics_cardinality_allows_enum_members_and_variables(tmp_path):
    """In-enum literals, variable cause values (the flight recorder's
    own idiom), and kwarg labels() spellings all stay quiet."""
    assert lint(tmp_path, """
from grandine_tpu.metrics import LabeledCounter

SLO_CAUSES = ("queue_wait", "device", "bisection", "breaker_open")

class M:
    def __init__(self):
        self.verify_slo_miss = LabeledCounter(
            "verify_slo_miss_total", "h", ("lane", "cause"),
        )

class U:
    def use(self, m, rec):
        m.verify_slo_miss.inc("block", "device")
        m.verify_slo_miss.inc(rec.lane, rec.slo_cause)
        m.verify_slo_miss.labels(lane="block", cause="queue_wait")
""", "metrics-cardinality") == 0


_STAGE_OP_FIXTURE = """
from grandine_tpu.metrics import LabeledHistogram

STAGE_OPS = ("", "prevalidate", "deliver", "other")

class M:
    def __init__(self):
        self.verify_stage_seconds = LabeledHistogram(
            "verify_stage_seconds", "h", ("stage", "lane", "op"),
            defaults={"lane": "attestation", "op": ""},
        )

class U:
    def use(self, m, tracer, op):
        m.verify_stage_seconds.labels("host_prep", "attestation", <lit>)
        with self._stage("host_prep", op=<lit>, items=4):
            pass
        with stage(tracer, m, "feedback", "attestation", op=op):
            pass
"""


@pytest.mark.parametrize("literal,code", [
    ('"prevalidate"', 0), ('"deliver"', 0), ('""', 0),
    ('"coffee_break"', 1),
])
def test_metrics_cardinality_holds_stage_op_to_its_enum(tmp_path, literal,
                                                        code):
    """The `op` label of verify_stage_seconds is a closed enum
    (tracing.STAGE_OPS): a literal outside it is flagged at a `.labels()`
    call AND where it enters a stage helper (`_stage(..., op="...")`); a
    variable stays quiet."""
    src = _STAGE_OP_FIXTURE.replace("<lit>", literal)
    assert lint(tmp_path, src, "metrics-cardinality") == code


def test_jit_purity_flags_clock_global_and_config_update(tmp_path):
    assert lint(tmp_path, """
import time
import jax

_tuning = {"unroll": 4}

def kernel(x):
    global _seen
    return x * _tuning["unroll"] + time.monotonic()

run = jax.jit(kernel)

def setup(flag):
    jax.config.update("jax_enable_x64", flag)
""", "jit-purity") == 1


def test_jit_purity_allows_constant_tables_and_partial_alias(tmp_path):
    """UPPERCASE module tables are constants by convention; jit targets
    reached through functools.partial aliases are still scanned."""
    assert lint(tmp_path, """
import functools
import jax

WINDOW = [4, 8, 16]

def kernel(x, w):
    return x * WINDOW[w]

_k = functools.partial(kernel, w=1)
run = jax.jit(_k)
""", "jit-purity") == 0


def test_no_inline_gossip_verify_flags_handler_verify(tmp_path):
    assert lint(tmp_path, """
class Network:
    def _on_gossip_block(self, msg):
        if not msg.pubkey.verify(msg.signature, msg.root):
            raise ValueError("bad sig")

    def _eager_verify_items(self, items):
        return True
""", "no-inline-gossip-verify") == 1


_DONATE_FIXTURE = """
class Backend:
    def dispatch(self, sig_x, sig_y):
        fn = self._jitted("k", _body, donate=(0, 1))
        args = self._upload((sig_x, sig_y))
        out = self._run_kernel(fn, args, kernel="k")

        def settle():
            return out() and %s
        return settle
"""


def test_donated_buffer_reuse_flags_settle_read(tmp_path):
    """Reading a donated operand inside the settle closure — the exact
    bug class: the closure runs after XLA owns (and deleted) the
    buffer."""
    assert lint(
        tmp_path, _DONATE_FIXTURE % "sig_x.sum() > 0",
        "donated-buffer-reuse",
    ) == 1


def test_donated_buffer_reuse_allows_output_reads(tmp_path):
    assert lint(
        tmp_path, _DONATE_FIXTURE % "True", "donated-buffer-reuse"
    ) == 0


def test_donated_buffer_reuse_flags_args_var_too(tmp_path):
    """The upload-result tuple itself is donated memory: re-dispatching
    it is as fatal as touching an element."""
    assert lint(tmp_path, """
class Backend:
    def dispatch(self, sig_x):
        fn = self._jitted("k", _body, donate=(0,))
        args = self._upload((sig_x,))
        out = self._run_kernel(fn, args)
        return self._run_kernel(fn, args), out
""", "donated-buffer-reuse") == 1


def test_donated_buffer_reuse_rebind_ends_lifetime(tmp_path):
    assert lint(tmp_path, """
class Backend:
    def dispatch(self, sig_x):
        fn = self._jitted("k", _body, donate=(0,))
        args = self._upload((sig_x,))
        out = self._run_kernel(fn, args)
        sig_x = out()
        return sig_x + 1
""", "donated-buffer-reuse") == 0


def test_donated_buffer_reuse_ignores_undonated_kernels(tmp_path):
    assert lint(tmp_path, """
class Backend:
    def dispatch(self, sig_x):
        fn = self._jitted("k", _body, donate=())
        args = self._upload((sig_x,))
        out = self._run_kernel(fn, args)
        return out() and sig_x.sum() > 0
""", "donated-buffer-reuse") == 0


def test_donated_buffer_reuse_is_flow_sensitive(tmp_path):
    """An early UNDONATED dispatch through a variable name that is
    LATER rebound to a donated factory must not be treated as donated
    (the bls.py sharded-branch pattern): operand reads between the two
    dispatches are legal."""
    assert lint(tmp_path, """
class Backend:
    def dispatch(self, sig_x, use_sharded):
        if use_sharded:
            fn = self._jitted("s", _body, donate=())
            args = self._upload_sharded((sig_x,))
            return self._run_kernel(fn, args)
        fn = self._jitted("k", _body, donate=(0,))
        args = self._upload((sig_x,))
        out = self._run_kernel(fn, args)
        return out()
""", "donated-buffer-reuse") == 0


def test_thread_crash_containment_flags_uncontained_loop(tmp_path):
    assert lint(tmp_path, """
import threading

class Sched:
    def __init__(self):
        self._t = threading.Thread(target=self._dispatch, daemon=True)

    def _dispatch(self):
        while True:
            self.step()  # an exception here kills the daemon silently
""", "thread-crash-containment") == 1


def test_thread_crash_containment_narrow_handler_still_flags(tmp_path):
    """A narrow per-iteration handler is not containment — anything
    outside (ValueError, KeyError) still kills the thread."""
    assert lint(tmp_path, """
import threading

class Sched:
    def __init__(self):
        self._t = threading.Thread(target=self._dispatch, daemon=True)

    def _dispatch(self):
        while True:
            try:
                self.step()
            except (ValueError, KeyError):
                pass
""", "thread-crash-containment") == 1


def test_thread_crash_containment_allows_contained_loop(tmp_path):
    """The sanctioned idiom (_dispatch_loop / _collect): a direct-child
    broad try per iteration."""
    assert lint(tmp_path, """
import threading

class Sched:
    def __init__(self):
        self._t = threading.Thread(target=self._dispatch, daemon=True)

    def _dispatch(self):
        while True:
            try:
                self.step()
            except Exception:
                self.count_failure()
""", "thread-crash-containment") == 0


def test_thread_crash_containment_ignores_for_loops_and_nonthreads(tmp_path):
    """Bounded for-loops end on their own; a while loop in a plain
    (non-thread-target) function is not a daemon hazard."""
    assert lint(tmp_path, """
import threading

def warm_all(progress=None):
    for kind in ("a", "b"):
        compile(kind)

def helper():
    while True:
        step()

class W:
    def __init__(self):
        self._t = threading.Thread(target=warm_all, daemon=True)
""", "thread-crash-containment") == 0


def test_scheme_dispatch_flags_direct_backend_construction(tmp_path):
    """runtime/ building a device backend class behind the scheme
    table's back — through any import alias — is the seed violation."""
    assert lint(tmp_path, """
from grandine_tpu.tpu import bls as B

def make_verifier(metrics):
    return B.TpuBlsBackend(metrics=metrics)
""", "scheme-dispatch") == 1
    assert lint(tmp_path, """
def lane_backend():
    from grandine_tpu.kzg.eip4844 import KzgDeviceBackend

    return KzgDeviceBackend(metrics=None)
""", "scheme-dispatch") == 1


def test_scheme_dispatch_flags_kernel_entry_imports(tmp_path):
    """Cross-scheme kernel entry points (``*_kernel``, the jit-cache
    factory) must not leak into runtime/ imports."""
    assert lint(tmp_path, """
from grandine_tpu.tpu.ed25519 import verify_kernel

def check(prep):
    return verify_kernel(*prep)
""", "scheme-dispatch") == 1
    assert lint(tmp_path, """
from grandine_tpu.tpu.bls import _jitted_global
""", "scheme-dispatch") == 1


def test_scheme_dispatch_allows_table_and_host_helpers(tmp_path):
    """The sanctioned idioms: schemes.get(...).make_backend(...), host
    verdict twins, and constants/setup helpers from kernel modules."""
    assert lint(tmp_path, """
from grandine_tpu.kzg.eip4844 import (
    BYTES_PER_FIELD_ELEMENT,
    _setup_for_width,
)
from grandine_tpu.tpu import schemes


def make_verifier(metrics, tracer):
    return schemes.get("bls").make_backend(metrics=metrics, tracer=tracer)


def host_leaf(item):
    return schemes.get("blob_kzg").host_check(item)
""", "scheme-dispatch") == 0


def test_scheme_dispatch_clean_on_runtime():
    """The repo's runtime/ package itself satisfies the rule (default
    path set = grandine_tpu/runtime/*.py)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint",
         "--rules", "scheme-dispatch"],
        cwd=REPO, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout


# ------------------------------------------------ suppression + baseline


_VIOLATION = """
import numpy as np

class Backend:
    def verify_batch_async(self, sigs):
        return np.asarray(self._run(sigs)){suffix}
"""


def test_line_suppression_silences_one_finding(tmp_path):
    assert lint(
        tmp_path,
        _VIOLATION.format(suffix="  # lint: disable=host-sync"),
        "host-sync",
    ) == 0


def test_file_suppression_silences_the_file(tmp_path):
    src = "# lint: disable-file=host-sync\n" + _VIOLATION.format(suffix="")
    assert lint(tmp_path, src, "host-sync") == 0


def test_suppression_is_rule_scoped(tmp_path):
    assert lint(
        tmp_path,
        _VIOLATION.format(suffix="  # lint: disable=lock-order"),
        "host-sync",
    ) == 1


def test_baseline_grandfathers_and_goes_stale(tmp_path, capsys):
    from tools.lint import core
    from tools.lint.__main__ import main

    fixture = tmp_path / "fixture.py"
    fixture.write_text(_VIOLATION.format(suffix=""))
    baseline = tmp_path / "baseline.txt"
    argv = ["fixture.py", "--rules", "host-sync",
            "--baseline", str(baseline), "--root", str(tmp_path)]

    assert main(argv) == 1                      # new finding fails
    assert main(argv + ["--write-baseline"]) == 0
    assert main(argv) == 0                      # grandfathered now
    reasons = core.load_baseline(core.Context(str(tmp_path)), str(baseline))
    assert len(reasons) == 1

    fixture.write_text("x = 1\n")               # finding fixed
    capsys.readouterr()
    assert main(argv) == 0
    assert "stale baseline entry" in capsys.readouterr().err


def test_unknown_rule_is_an_error(tmp_path):
    with pytest.raises(SystemExit):
        lint(tmp_path, "x = 1\n", "no-such-rule")


# ----------------------------------- sync-gossip validation regressions


CFG = None
P = None
NS = None


def _eth2():
    """Late imports so collecting this module stays cheap."""
    global CFG, P, NS
    if CFG is None:
        from grandine_tpu.types.config import Config
        from grandine_tpu.types.containers import spec_types

        CFG = Config.minimal()
        P = CFG.preset
        NS = spec_types(P).deneb
    return CFG, P, NS


@pytest.fixture()
def gossip_pair():
    """(publisher, receiver, pool): receiver verifies through the eager
    inline fallback, so accept/reject lands synchronously in stats."""
    from grandine_tpu.consensus.verifier import NullVerifier
    from grandine_tpu.p2p.network import InMemoryHub, Network
    from grandine_tpu.pools.sync_committee_pool import SyncCommitteeAggPool
    from grandine_tpu.runtime.controller import Controller
    from grandine_tpu.transition.genesis import interop_genesis_state

    cfg, _p, _ns = _eth2()
    genesis = interop_genesis_state(16, cfg)
    hub = InMemoryHub()
    pub = Network(
        hub.join("pub"),
        Controller(genesis, cfg, verifier_factory=NullVerifier), cfg,
    )
    pool = SyncCommitteeAggPool(cfg)
    rcv = Network(
        hub.join("rcv"),
        Controller(genesis, cfg, verifier_factory=NullVerifier), cfg,
        sync_pool=pool,
    )
    return genesis, pub, rcv, pool


def _signed_contribution(genesis, slot=1, forge_selection=False,
                         forge_outer=False, aggregator_index=None):
    from grandine_tpu.consensus import signing
    from grandine_tpu.validator.duties import _interop_keys

    cfg, p, ns = _eth2()
    head_root = bytes(32)
    sub_size = p.SYNC_COMMITTEE_SIZE // cfg.sync_committee_subnet_count
    members = [
        bytes(pk) for pk in genesis.current_sync_committee.pubkeys[:sub_size]
    ]
    val_pubkeys = [bytes(v.pubkey) for v in genesis.validators]
    agg_idx = (
        val_pubkeys.index(members[0])
        if aggregator_index is None else aggregator_index
    )
    mkey = _interop_keys(val_pubkeys.index(members[0]))
    from grandine_tpu.consensus import misc

    root = signing.sync_committee_message_signing_root(
        genesis, head_root, misc.compute_epoch_at_slot(slot, p), cfg
    )
    bits = [False] * sub_size
    bits[0] = True
    contribution = ns.SyncCommitteeContribution(
        slot=slot, beacon_block_root=head_root, subcommittee_index=0,
        aggregation_bits=bits, signature=mkey.sign(root).to_bytes(),
    )
    selection_root = signing.sync_selection_proof_signing_root(
        genesis,
        ns.SyncAggregatorSelectionData(slot=slot, subcommittee_index=0),
        cfg,
    )
    wrong_key = _interop_keys(15)
    proof = ns.ContributionAndProof(
        aggregator_index=agg_idx, contribution=contribution,
        selection_proof=(
            wrong_key if forge_selection else mkey
        ).sign(selection_root).to_bytes(),
    )
    outer_root = signing.contribution_and_proof_signing_root(
        genesis, proof, cfg
    )
    return ns.SignedContributionAndProof(
        message=proof,
        signature=(
            wrong_key if forge_outer else mkey
        ).sign(outer_root).to_bytes(),
    )


def test_valid_contribution_accepted(gossip_pair):
    genesis, pub, rcv, pool = gossip_pair
    pub.publish_sync_contribution(_signed_contribution(genesis))
    assert rcv.stats["sync_contributions_in"] == 1
    assert rcv.stats["sync_contributions_rejected"] == 0


def test_forged_selection_proof_rejected(gossip_pair):
    """A non-elected key signing the SyncAggregatorSelectionData must
    not aggregate — previously the proof was never checked."""
    genesis, pub, rcv, pool = gossip_pair
    pub.publish_sync_contribution(
        _signed_contribution(genesis, forge_selection=True)
    )
    assert rcv.stats["sync_contributions_rejected"] == 1


def test_forged_outer_signature_rejected(gossip_pair):
    """The SignedContributionAndProof envelope signature must verify
    against the declared aggregator — previously unchecked."""
    genesis, pub, rcv, pool = gossip_pair
    pub.publish_sync_contribution(
        _signed_contribution(genesis, forge_outer=True)
    )
    assert rcv.stats["sync_contributions_rejected"] == 1


def test_non_member_aggregator_rejected(gossip_pair):
    """An aggregator index whose pubkey is outside the declared
    subcommittee is rejected structurally."""
    genesis, pub, rcv, pool = gossip_pair
    cfg, p, _ns = _eth2()
    sub_size = p.SYNC_COMMITTEE_SIZE // cfg.sync_committee_subnet_count
    members = {
        bytes(pk) for pk in genesis.current_sync_committee.pubkeys[:sub_size]
    }
    outsider = next(
        i for i, v in enumerate(genesis.validators)
        if bytes(v.pubkey) not in members
    )
    pub.publish_sync_contribution(
        _signed_contribution(genesis, aggregator_index=outsider)
    )
    assert rcv.stats["sync_contributions_rejected"] == 1


def test_contribution_beyond_known_periods_rejected(gossip_pair):
    """A slot two sync-committee periods ahead resolves to no known
    committee: the state only holds current + next. Previously members
    were always read from current_sync_committee regardless of slot."""
    genesis, pub, rcv, pool = gossip_pair
    cfg, p, _ns = _eth2()
    ahead = 2 * p.SLOTS_PER_EPOCH * p.EPOCHS_PER_SYNC_COMMITTEE_PERIOD
    pub.publish_sync_contribution(
        _signed_contribution(genesis, slot=ahead)
    )
    assert rcv.stats["sync_contributions_rejected"] == 1
