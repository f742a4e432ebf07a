"""Schedule-fuzz harness tests: determinism (same seed, same trace),
all runtime scenarios clean across seeds, the harness actually CATCHES
races (torn counter) and deadlocks on seeded toys, and every
`# lint: atomic=` annotation in the runtime sources is backed by a
COVERAGE scenario. Kernel-free: pure host-thread interleaving.
"""

from __future__ import annotations

import ast
import importlib.util
import os

from grandine_tpu.testing import schedule_fuzz as sf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- determinism


def test_same_seed_reproduces_same_trace():
    # CPython 3.12 installs per-opcode instrumentation lazily: the FIRST
    # frame of a code object on which f_trace_opcodes is set receives no
    # opcode events for that call, so a process's first run of a scenario
    # is a few steps short of every later one. Determinism is a property
    # of the harness on instrumented code: prime once, then compare.
    sf.scenario_ticket_verdict(5)
    a = sf.scenario_ticket_verdict(5)
    b = sf.scenario_ticket_verdict(5)
    assert a["trace_sha256"] == b["trace_sha256"]
    assert a["steps"] == b["steps"]
    assert a["switches"] == b["switches"]
    assert a["preemption_points"] == b["preemption_points"]


def test_different_seeds_diverge():
    a = sf.scenario_ticket_verdict(5)
    b = sf.scenario_ticket_verdict(6)
    assert a["trace_sha256"] != b["trace_sha256"]


# --------------------------------------------------- runtime scenarios


def test_all_scenarios_clean_across_seeds():
    """The headline contract: every runtime scenario survives every
    interleaving the fuzzer throws at it — zero violations, and real
    preemption diversity (the schedules are not degenerate)."""
    report = sf.run_fuzz(seeds=(0, 1))
    assert report["violations"] == [], report["violations"]
    assert set(report["scenarios"]) == set(sf.SCENARIOS)
    assert report["preemption_points"] > 50
    assert report["switches"] > 100


# ------------------------------------------------- harness sensitivity


def _load_toy(tmp_path, name: str, source: str):
    toy = tmp_path / f"{name}.py"
    toy.write_text(source)
    spec = importlib.util.spec_from_file_location(name, toy)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return str(toy), mod


def test_torn_counter_is_caught(tmp_path):
    """An unlocked `self.n = self.n + 1` from two workers MUST lose an
    update under some seed — if the fuzzer can't tear this, its opcode
    preemption isn't real and every clean scenario result is vacuous."""
    path, mod = _load_toy(tmp_path, "toy_counter", (
        "class Counter:\n"
        "    def __init__(self):\n"
        "        self.n = 0\n"
        "    def bump(self):\n"
        "        self.n = self.n + 1\n"
    ))
    torn = None
    for seed in range(20):
        fz = sf.ScheduleFuzzer(seed, watched=[path], max_quantum=3)
        c = mod.Counter()

        def bumper():
            for _ in range(20):
                c.bump()

        fz.add_worker("a", bumper)
        fz.add_worker("b", bumper)
        res = fz.run()
        assert res["violations"] == []
        if c.n != 40:
            torn = seed
            break
    assert torn is not None, "no seed tore the unlocked counter"


def test_lock_prevents_the_tear(tmp_path):
    """Same toy with the increment under a FuzzLock: no seed may lose
    an update (the proxy lock really serializes the critical section)."""
    path, mod = _load_toy(tmp_path, "toy_locked", (
        "class Counter:\n"
        "    def __init__(self, lock):\n"
        "        self._lock = lock\n"
        "        self.n = 0\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.n = self.n + 1\n"
    ))
    for seed in range(5):
        fz = sf.ScheduleFuzzer(seed, watched=[path], max_quantum=3)
        c = mod.Counter(fz.lock("counter"))

        def bumper():
            for _ in range(10):
                c.bump()

        fz.add_worker("a", bumper)
        fz.add_worker("b", bumper)
        res = fz.run()
        assert res["violations"] == []
        assert c.n == 20


def test_unlocked_cached_pubkey_fill_is_caught(tmp_path):
    """The pre-fix CachedPublicKey.decompress (unlocked check-then-set,
    crypto/bls.py) must double-decompress under some seed — proving the
    cached_pubkey scenario's single-fill invariant has teeth."""
    path, mod = _load_toy(tmp_path, "toy_cached_key", (
        "class CachedKey:\n"
        "    def __init__(self, fill):\n"
        "        self._fill = fill\n"
        "        self._decompressed = None\n"
        "    def decompress(self):\n"
        "        if self._decompressed is None:\n"
        "            self._decompressed = self._fill()\n"
        "        return self._decompressed\n"
    ))
    raced = None
    for seed in range(20):
        fz = sf.ScheduleFuzzer(seed, watched=[path], max_quantum=3)
        calls = [0]

        def fill():
            calls[0] += 1
            return object()

        key = mod.CachedKey(fill)
        fz.add_worker("a", key.decompress)
        fz.add_worker("b", key.decompress)
        res = fz.run()
        assert res["violations"] == []
        if calls[0] != 1:
            raced = seed
            break
    assert raced is not None, "no seed raced the unlocked fill"


def test_cached_pubkey_scenario_clean():
    """The locked implementation survives every seed: exactly one fill,
    one shared object, across adversarial interleavings."""
    for seed in range(5):
        res = sf.scenario_cached_pubkey(seed)
        assert res["violations"] == [], res["violations"]


def test_deadlock_is_detected(tmp_path):
    """Opposite-order acquisition on two FuzzLocks must deadlock under
    some seed, and the harness must report it (not hang)."""
    path, mod = _load_toy(tmp_path, "toy_deadlock", (
        "def grab(first, second, spins):\n"
        "    for _ in range(spins):\n"
        "        with first:\n"
        "            with second:\n"
        "                pass\n"
    ))
    found = None
    for seed in range(20):
        fz = sf.ScheduleFuzzer(seed, watched=[path], max_quantum=2)
        la, lb = fz.lock("a"), fz.lock("b")
        fz.add_worker("fwd", lambda: mod.grab(la, lb, 10))
        fz.add_worker("rev", lambda: mod.grab(lb, la, 10))
        res = fz.run()
        kinds = {v["kind"] for v in res["violations"]}
        assert kinds <= {"deadlock"}, res["violations"]
        if "deadlock" in kinds:
            found = seed
            break
    assert found is not None, "no seed produced the AB/BA deadlock"


def test_invariant_breakage_is_reported(tmp_path):
    """A scenario-style invariant failure lands in the violations list
    as kind=invariant (the shape bench/tests key on)."""
    res = sf.scenario_ticket_verdict(0)
    assert res["violations"] == []
    res["violations"].append({"kind": "probe"})
    out = sf._invariant(res, "demo", ["it broke"])
    assert {"kind": "invariant", "detail": "demo: it broke"} \
        in out["violations"]


# ------------------------------------------------- annotation coverage


def test_every_atomic_annotation_has_a_fuzz_scenario():
    """The contract the PR exists for: parse every `# lint: atomic=`
    annotation from the thread-affinity rule's own path set and require
    a COVERAGE entry pointing at a real scenario — and no stale
    COVERAGE keys for annotations that no longer exist."""
    from tools.lint import thread_graph as tg
    from tools.lint.rules.thread_affinity import ThreadAffinityRule

    keys = set()
    for rel in ThreadAffinityRule.default_paths:
        path = os.path.join(REPO, rel)
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        anns = tg.class_annotations(ast.parse(src), src)
        mod = os.path.splitext(os.path.basename(rel))[0]
        for cls, attrs in anns.items():
            for attr in attrs:
                keys.add(f"{mod}.{cls}.{attr}")
    assert keys == set(sf.COVERAGE), (
        f"annotations {keys ^ set(sf.COVERAGE)} out of sync with "
        f"schedule_fuzz.COVERAGE"
    )
    for scenario in sf.COVERAGE.values():
        assert scenario in sf.SCENARIOS


def test_no_leaked_fuzz_threads():
    import threading
    import time

    sf.scenario_flight_ring(3)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = [
            t for t in threading.enumerate() if t.name.startswith("fuzz-")
        ]
        if not leaked:
            return
        time.sleep(0.05)
    raise AssertionError(f"leaked fuzz threads: {leaked}")
