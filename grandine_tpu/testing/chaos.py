"""Seeded fault injection over the async device seam.

`ChaosBackend` wraps any backend implementing the verify plane's async
seam (health.REQUIRED_SEAM_METHODS) and injects faults scheduled by a
deterministic `FaultPlan` — the same seed always produces the same
fault sequence, so a chaos soak is a reproducible test, not a flake
generator. Five fault kinds, matching the real failure modes the health
supervisor defends against:

  raise_dispatch — the seam call itself raises (XLA compile/transfer
      error at dispatch time)
  raise_settle   — dispatch succeeds, the returned settle raises
      (readback fault)
  hang           — the settle blocks until released (wedged device);
      pairs with the settle watchdog, released at teardown via
      `release_hangs()` so abandoned threads don't linger
  wrong_verdict  — dispatch and settle succeed but the verdict is
      INVERTED (silently corrupt accelerator) — the kind only canary
      probes and host bisection can catch
  slow_settle    — the settle sleeps before answering (degraded link);
      must NOT trip the breaker when within the watchdog deadline
  wrong_signature — `batch_sign` (the SIGN-side seam) returns a batch
      where one signature is valid-looking but wrong (signed over a
      different message) — the kind only the signing plane's release
      gate can catch before a caller publishes it

`KnownAnswerBackend` is the truth-table stub used underneath the chaos
wrapper by tests: verdicts come from a dict
keyed by message bytes, so the fault-free expectation is known exactly.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Optional, Sequence

import numpy as np

from grandine_tpu.runtime.health import REQUIRED_SEAM_METHODS

#: injectable fault kinds, in plan-draw order ("wrong_signature" is
#: appended so existing seeded rate plans keep their draw sequence)
FAULT_KINDS = (
    "raise_dispatch",
    "raise_settle",
    "hang",
    "wrong_verdict",
    "slow_settle",
    "wrong_signature",
)


class ChaosFault(RuntimeError):
    """The injected failure (distinguishable from real bugs in logs)."""


class FaultPlan:
    """Deterministic fault schedule over seam calls.

    Either scripted — `script[i]` is the fault kind (or None) for the
    i-th seam call, with calls past the end of the script fault-free —
    or rate-driven: per-call, one seeded uniform draw selects a fault
    kind by cumulative `rates` (mapping kind -> probability; the
    remainder is fault-free). `injected` counts draws per kind."""

    def __init__(self, seed: int = 0,
                 rates: "Optional[dict]" = None,
                 script: "Optional[Sequence[Optional[str]]]" = None) -> None:
        self.rng = random.Random(seed)
        self.rates = dict(rates or {})
        for kind in self.rates:
            if kind not in FAULT_KINDS:
                raise ValueError(f"unknown fault kind {kind!r}")
        self.script = list(script) if script is not None else None
        self.calls = 0
        self.injected = {k: 0 for k in FAULT_KINDS}
        self._lock = threading.Lock()

    def next_fault(self) -> "Optional[str]":
        with self._lock:
            i = self.calls
            self.calls += 1
            if self.script is not None:
                kind = self.script[i] if i < len(self.script) else None
            else:
                draw = self.rng.random()
                kind = None
                edge = 0.0
                for k in FAULT_KINDS:
                    edge += self.rates.get(k, 0.0)
                    if draw < edge:
                        kind = k
                        break
            if kind is not None:
                self.injected[kind] += 1
            return kind


class ChaosBackend:
    """Async-seam wrapper injecting `plan`-scheduled faults around an
    inner backend. Everything else delegates to the inner backend via
    `__getattr__`, so the wrapper is transparent to registry/tracer
    plumbing."""

    #: seams the wrapper can inject into; the inner backend only needs
    #: to implement the ones its scheme actually dispatches (the BLS
    #: pair from REQUIRED_SEAM_METHODS, ed25519's verify_batch_async,
    #: blob_kzg's verify_blobs_async, or the sign-side batch_sign)
    KNOWN_SEAMS = REQUIRED_SEAM_METHODS + (
        "verify_batch_async",
        "verify_blobs_async",
        "batch_sign",
    )

    def __init__(self, inner, plan: FaultPlan, slow_s: float = 0.05) -> None:
        assert any(hasattr(inner, m) for m in self.KNOWN_SEAMS)
        self.inner = inner
        self.plan = plan
        self.slow_s = float(slow_s)
        self.dispatches = 0  # seam calls that reached past the breaker
        self._lock = threading.Lock()
        self._hung: "list[threading.Event]" = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def release_hangs(self) -> None:
        """Unblock every injected hang (teardown: lets abandoned
        watchdog threads finish instead of sleeping forever)."""
        with self._lock:
            hung, self._hung = self._hung, []
        for ev in hung:
            ev.set()

    # ------------------------------------------------------ seam wrapping

    def _wrap(self, method: str, invert, args, kw=None):
        with self._lock:
            self.dispatches += 1
        kind = self.plan.next_fault()
        if kind == "raise_dispatch":
            raise ChaosFault(f"injected dispatch fault on {method}")
        inner_settle = getattr(self.inner, method)(*args, **(kw or {}))

        def settle():
            if kind == "raise_settle":
                raise ChaosFault(f"injected settle fault on {method}")
            if kind == "hang":
                ev = threading.Event()
                with self._lock:
                    self._hung.append(ev)
                ev.wait()
                raise ChaosFault(f"released injected hang on {method}")
            if kind == "slow_settle":
                time.sleep(self.slow_s)
            value = inner_settle()
            if kind == "wrong_verdict":
                return invert(value)
            return value

        return settle

    # `kw`: the `bucket_floor` a probe of the firehose's descent names

    def fast_aggregate_verify_batch_async(self, messages, signatures, keys,
                                          **kw):
        return self._wrap(
            "fast_aggregate_verify_batch_async",
            lambda v: not v,
            (messages, signatures, keys), kw,
        )

    def fast_aggregate_verify_batch_indexed_async(self, messages, signatures,
                                                  indices, registry, **kw):
        return self._wrap(
            "fast_aggregate_verify_batch_indexed_async",
            lambda v: not v,
            (messages, signatures, indices, registry), kw,
        )

    def g2_subgroup_check_batch_async(self, points):
        return self._wrap(
            "g2_subgroup_check_batch_async",
            lambda arr: ~np.asarray(arr),
            (points,),
        )

    def rlc_partition_verify_async(self, messages, signatures, member_keys,
                                   groups):
        return self._wrap(
            "rlc_partition_verify_async",
            lambda arr: ~np.asarray(arr),
            (messages, signatures, member_keys, groups),
        )

    # ------------------------------------------- non-BLS verify seams

    def verify_batch_async(self, prep):
        """ed25519 lane seam: scalar verdict, wrong_verdict inverts it
        — the silently-corrupt-accelerator mode the ed25519 lane's
        host-twin canary and quarantine path must catch."""
        return self._wrap("verify_batch_async", lambda v: not v, (prep,))

    def verify_blobs_async(self, prep):
        """blob_kzg lane seam: scalar verdict over the whole sidecar
        batch, wrong_verdict inverts it."""
        return self._wrap("verify_blobs_async", lambda v: not v, (prep,))

    # ---------------------------------------------------- sign-side seam

    def batch_sign(self, messages, secret_keys):
        """The signing plane's device seam (blocking, unlike the verify
        seams). `wrong_signature`/`wrong_verdict` corrupt the FIRST
        signature of the batch with a structurally valid signature over
        a different message — decodes cleanly, fails the release gate.
        Dispatch/hang/slow faults behave as on the verify seams."""
        with self._lock:
            self.dispatches += 1
        kind = self.plan.next_fault()
        if kind in ("raise_dispatch", "raise_settle"):
            raise ChaosFault("injected dispatch fault on batch_sign")
        if kind == "hang":
            ev = threading.Event()
            with self._lock:
                self._hung.append(ev)
            ev.wait()
            raise ChaosFault("released injected hang on batch_sign")
        if kind == "slow_settle":
            time.sleep(self.slow_s)
        sigs = self.inner.batch_sign(messages, secret_keys)
        if kind in ("wrong_signature", "wrong_verdict") and sigs:
            sigs = list(sigs)
            sigs[0] = secret_keys[0].sign(
                b"chaos: wrong message " + bytes(messages[0])
            )
        return sigs


class KnownAnswerBackend:
    """Truth-table async seam: the batch verdict is the AND of
    `truth[message_bytes]` over the batch (missing messages are
    invalid). Subgroup checks always pass — signature geometry is not
    under test here, verdict plumbing is."""

    def __init__(self, truth: "Optional[dict]" = None) -> None:
        self.truth = dict(truth or {})
        self.batches: "list[int]" = []
        #: (items, groups) per rlc_partition dispatch — lets tests
        #: assert the localization pass count and ladder shape
        self.partitions: "list[tuple]" = []

    def g2_subgroup_check_batch_async(self, points):
        n = len(points)
        return lambda: np.ones((n,), dtype=bool)

    # ------------------------------------- ed25519 / blob_kzg seams
    # (scheme dispatch calls prepare() first, then the async seam; the
    # "prep" here is just the message bytes so verdicts stay keyed by
    # the same truth table as the BLS seam)

    def prepare(self, items):
        return "ok", [bytes(it.message) for it in items]

    def verify_batch_async(self, prep):
        self.batches.append(len(prep))
        return lambda: all(self.truth.get(m, False) for m in prep)

    def verify_blobs_async(self, prep):
        self.batches.append(len(prep))
        return lambda: all(self.truth.get(m, False) for m in prep)

    def fast_aggregate_verify_batch_async(self, messages, signatures, keys,
                                          bucket_floor=None):
        self.batches.append(len(messages))
        msgs = [bytes(m) for m in messages]
        return lambda: all(self.truth.get(m, False) for m in msgs)

    def rlc_partition_verify_async(self, messages, signatures, member_keys,
                                   groups):
        """Per-group AND over the truth table with the device backend's
        padding geometry (pow-2 bucket lo=4, pad groups are clean)."""
        n = len(messages)
        self.partitions.append((n, int(groups)))
        b = 4
        while b < n:
            b <<= 1
        g = 4
        while g < groups:
            g <<= 1
        if g > b:
            g = b
        span = b // g
        flags = [self.truth.get(bytes(m), False) for m in messages]
        flags += [True] * (b - n)
        out = np.array(
            [all(flags[j * span:(j + 1) * span]) for j in range(g)],
            dtype=bool,
        )
        return lambda: out


__all__ = [
    "FAULT_KINDS",
    "ChaosBackend",
    "ChaosFault",
    "FaultPlan",
    "KnownAnswerBackend",
]
