"""Driver of the firehose under forged votes: the node of
`drivers/firehose.py` (nothing there is edited: this file loads it and
builds on its `Driver`), fed single votes of which exactly one in every
64 consecutive ones is forged, so that every batch fails its first check
and is isolated by the program's descent (`AttestationVerifier._isolate`:
bisection inside the batch's own padded bucket and executable).

What differs from the clean driver: the pacing (256 votes whenever fewer
than 256 lack a verdict), the forged labelling, a warm-up that also sends
a forged batch twice, a `correct` that EXPECTS rejections (exactly the
forged ones), a traced pass of one CLEAN batch, and `window_calls` read
from the program's kernel-call counter, so that the probes count as device
calls. On a program without the descent's counters it refuses to run at
once, before anything is warmed.
"""

from __future__ import annotations

import hashlib
import os
import random
import time
from dataclasses import replace

from benchmark import loader, observe
from benchmark.generators.attestations import AttestationTraffic, judge
from benchmark.generators.keys import MessageSigner
from benchmark.reference import bls as ref

_clean = loader.load_driver(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "firehose"
)
Refused = _clean.Refused

#: the program's counters of the descent; absent, the program isolates a
#: failed batch through other executables than the batch's own
PROBES = "attestation_isolation_probes_total"
ISOLATED = "attestation_isolated_batches_total"
KERNEL_CALLS = "device_kernel_calls_total"


def deliver_failed_batch() -> None:
    """The control: breaks the guarantee "every forged item is rejected
    and none is delivered". The batch verdict is forced to "valid" where
    the verifier resolves it, so a failed batch is delivered whole, the
    forged vote with it. Nothing is recompiled."""
    from grandine_tpu.runtime.attestation_verifier import AttestationVerifier

    resolve = AttestationVerifier._resolve_batch
    AttestationVerifier._resolve_batch = (
        lambda self, prepared, ok, fl=None: resolve(self, prepared, True, fl)
    )


CONTROLS = dict(_clean.CONTROLS, deliver_failed_batch=deliver_failed_batch)


class Driver(_clean.Driver):
    #: calls whose votes are forged before the window opens: 24 x 256
    #: votes, several times what a window takes at ~30 votes/s (a call
    #: beyond them is made, and forged, inside the window)
    PREPARED_CALLS = 24

    def __init__(self, cell: dict, seed: int, say) -> None:
        super().__init__(cell, seed, say)
        self.forged: "set[int]" = set()  # ids of submitted forged items
        self._signer = None

    # ----------------------------------------------------------- set-up

    def setup(self) -> None:
        from grandine_tpu.metrics import Metrics
        from grandine_tpu.tpu import compile_scope

        have = {name for name, _labels in
                observe.parse_exposition(Metrics().expose())}
        if not {PROBES, ISOLATED} <= have:
            raise Refused(
                f"the program has no {PROBES}: it isolates a failed batch "
                "through executables this cell does not warm (a compile "
                "of minutes on the settle path)"
            )
        super().setup()
        # one forged batch, twice, as the clean warm-up sends a clean one:
        # the descent's probes must find nothing left to compile
        batch = int(self.shapes["max_batch"])
        warm = self.slots[self.warm_slot][batch: 2 * batch]
        if len(warm) != batch:
            raise Refused("the warm-up slot is too small for a forged batch")
        rng = random.Random(f"warm-forged|{self.seed}")
        pos = rng.randrange(batch)
        warm = warm[:pos] + [self._forge(warm[pos])] + warm[pos + 1:]
        before = compile_scope.totals()
        for _ in range(2):
            first = len(self.items)
            self._submit(warm, time.perf_counter())
            self.forged.add(first + pos)
            if not self._wait_all(timeout=self.WARM_TIMEOUT_S):
                raise Refused("the forged warm-up was not answered: "
                              f"{dict(self.verifier.stats)}")
        after = compile_scope.totals()
        if after[1] != before[1]:
            raise Refused("the forged warm-up compiled: the descent reaches "
                          "another shape than the batch's own")
        self.say(phase="warm_forged", compiles=after[1] - before[1],
                 stats=dict(self.verifier.stats))

    def _forge(self, item):
        """`item` with its own validator's signature over another root
        (one seed-drawn root a run, so one signer): it decompresses, lies
        in G2, passes prevalidation; only the pairing refuses it."""
        if self._signer is None:
            other = hashlib.sha256(b"forged-root|%d" % self.seed).digest()
            self._signer = MessageSigner(self.keys, other)
        point = self._signer.single(item.members[0])
        return replace(item, signature=ref.g2_to_bytes(point))

    # ----------------------------------------------------------- window

    def run(self, seconds: float, trace_dir: "str | None") -> dict:
        from grandine_tpu.tpu import compile_scope

        self._compiles0 = compile_scope.totals()[1]
        out = super().run(seconds, trace_dir)
        # first passes AND probes: what the idle estimate multiplies
        self.window_calls = int(observe.series_delta(
            self.counters_before, self.counters_after, KERNEL_CALLS,
            kernel=self.cell["kernel"],
        ))
        return out

    def _stream(self):
        """The window's votes in slot order, `(slot, item)`; should they
        run out they come again from the first (`resubmitted`)."""
        slots = sorted(s for s in self.slots if s >= self.first_slot)
        self.resubmitted = 0
        again = False
        while True:
            for slot in slots:
                for item in self.slots[slot]:
                    self.resubmitted += again
                    yield slot, item
            again = True

    def _run_forged_backlog(self, seconds: float) -> dict:
        """Closed against the queue: whenever fewer than `backlog_items`
        items lack a verdict the next `submit_items` (whole batches) are
        submitted in one call; of every `forged_one_in` consecutive votes
        exactly one is forged, at a seed-drawn position."""
        batch = int(self.shapes["max_batch"])
        floor = int(self.traffic["backlog_items"])
        chunk = int(self.traffic["submit_items"])
        one_in = int(self.traffic["forged_one_in"])
        if chunk % batch or one_in != batch:
            raise Refused("submissions are whole batches, one forged each")
        stream = self._stream()
        rng = random.Random(f"forged|{self.seed}")
        # forged before the window opens, more than any window takes
        ahead = [self._next_call(stream, rng, chunk, one_in)
                 for _ in range(self.PREPARED_CALLS)]
        t0 = time.perf_counter()
        submitted, ticked = 0, None
        while (now := time.perf_counter()) < t0 + seconds:
            if self._outstanding() - self._rejected() < floor:
                slot, items, bad = (ahead.pop(0) if ahead else
                                    self._next_call(stream, rng, chunk,
                                                    one_in))
                if slot != ticked:
                    self._tick(slot)
                    ticked = slot
                first = len(self.items)
                with observe.annotate("bench/submit"):
                    self._submit(items, now)
                self.forged.update(first + k for k in bad)
                submitted += len(items)
            with observe.annotate("bench/generator_sleep"):
                time.sleep(0.005)
        return dict(self._close_window(t0, seconds), attempted=submitted,
                    gen={})

    def _next_call(self, stream, rng, chunk: int, one_in: int):
        """(latest slot, items, positions of the forged) of one call."""
        taken = [next(stream) for _ in range(chunk)]
        items = [it for _slot, it in taken]
        bad = [base + rng.randrange(one_in)
               for base in range(0, chunk, one_in)]
        for k in bad:
            items[k] = self._forge(items[k])
        return max(slot for slot, _it in taken), items, bad

    def _honest(self, lo: int, hi: int) -> "list[int]":
        return [i for i in range(lo, hi) if i not in self.forged]

    def traced_batch(self, asked: bool) -> None:
        """ONE full batch of the window's HONEST votes, traced as the
        clean driver traces its own: one device call. A descent is
        thirteen: over a gigabyte of trace and ten minutes of
        `stop_trace`; it is seen through the window's spans and counters."""
        if not asked:
            return
        batch = int(self.shapes["max_batch"])
        ids = self._honest(self._window_first_id, self._window_end_id)
        items = [self.items[i] for i in ids[:batch]]
        if self.trace.wanted:
            self.trace.start()
            self.trace.mark_begin()
        with observe.annotate("bench/submit"):
            self._submit(items, time.perf_counter())
        with observe.annotate("bench/wait_verdicts"):
            self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        if self.trace.wanted:
            self.trace.stop()

    # ------------------------------------------------------ correctness

    def settle(self) -> None:
        """After the window: wait for every answer that is due, read the
        program's counters and flight rows over the window AND its drain
        (every submitted batch has then been isolated), hand the
        reference its sample of 8 honest and 8 forged window items, then
        the clean driver's negative cases on 64 honest items."""
        from grandine_tpu.tpu import compile_scope

        answered = self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        self._window_end_id = len(self.items)
        self.health_window = self._health()
        self.counters_drained = observe.parse_exposition(
            self.metrics.expose())
        first_seq = min((r["seq"] for r in self.flight_rows), default=None)
        self.drained_rows = [
            r.as_dict() for r in self.node.flight.snapshot(lane="attestation")
            if first_seq is not None and r.seq >= first_seq
        ]
        batch = int(self.shapes["max_batch"])
        lo, hi = self._window_first_id, self._window_end_id
        honest = self._honest(lo, hi)
        forged = sorted(i for i in self.forged if lo <= i < hi)

        rng = random.Random(f"probe|{self.seed}")
        valid = [self.items[i] for i in honest[:batch]]
        pair, i, j = AttestationTraffic.forged_pair(valid, rng)
        k = rng.randrange(len(valid))
        torsion = list(valid)
        torsion[k] = AttestationTraffic.off_subgroup(valid[k])
        bad = [AttestationTraffic.malformed(it) for it in valid]
        negatives = [pair[i], pair[j], torsion[k], bad[k]]

        # -- the reference, in the workers, while this process goes on
        half = int(self.cell["reference_sample"]) // 2
        srng = random.Random(f"sample|{self.seed}")
        self.sample = (srng.sample(honest, min(half, len(honest)))
                       + srng.sample(forged, min(half, len(forged))))
        jobs = [[self.items[i]] for i in self.sample] + [negatives]
        self._judged = [
            self.pool.submit(judge, self.keys.n, self.seed, job)
            for job in jobs
        ]

        # -- the executable the window drove, called as the node calls it
        self.probe = {name: self._executable_accepts(items) for name, items
                      in (("valid", valid), ("forged_pair", pair),
                          ("off_subgroup", torsion))}

        # -- the served entry: a batch nobody can decompress
        before = self._health()
        first_bad = len(self.items)
        self._submit(bad, time.perf_counter())
        self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        after = self._health()
        with self._lock:
            self.malformed_delivered = sum(
                1 for ident in range(first_bad, first_bad + len(bad))
                if ident in self.delivered
            )
            for it in bad:
                waiting = self._pending[(it.slot, tuple(it.members))]
                waiting[:] = [i for i in waiting if i < first_bad]
        self.malformed_rejected = (after["stats"]["rejected"]
                                   - before["stats"]["rejected"])
        self._malformed = (first_bad, len(bad), before, after)
        self.counters_settled = observe.parse_exposition(
            self.metrics.expose())
        # everything after the window's own count: the drain's descents,
        # the direct calls, the malformed batch's descent
        self.probe_compiles = (compile_scope.totals()[1] - self._compiles0
                               - self.window_compiles)
        self.say(phase="settled", answered=answered, probe=self.probe,
                 malformed_delivered=self.malformed_delivered,
                 malformed_rejected=self.malformed_rejected,
                 forged=len(forged), stats=after["stats"])

    def checks(self) -> "list[tuple[str, float, float]]":
        """Every number compared, beside its limit. All are counts and all
        are exact: the limit is 0 (a difference is given as its absolute
        value), and `sampled` has to reach its own."""
        judged = [f.result(timeout=self.ANSWER_TIMEOUT_S * 5)
                  for f in self._judged]
        mismatch = sum(
            int(want[0] != (ident in self.delivered))
            for ident, want in zip(self.sample, judged)
        )
        sampled_forged = sum(1 for i in self.sample if i in self.forged)
        first_bad, n_bad, before, after = self._malformed
        # the program's counts over everything but the malformed batch
        spans = ((self.health_start, before), (after, self.health_end))
        d = {k: sum(b["stats"].get(k, 0) - a["stats"].get(k, 0)
                    for a, b in spans)
             for k in ("rejected", "breaker_skips", "retries",
                       "settle_errors")}
        lo, hi = self._window_first_id, self._window_end_id
        batch = int(self.shapes["max_batch"])
        with self._lock:
            # over ALL items, the warm-up's and the traced batch's too
            forged_delivered = sum(
                1 for i in self.forged if i in self.delivered)
            undelivered = [
                i for i in range(lo, len(self.items))
                if i not in self.delivered
                and not first_bad <= i < first_bad + n_bad
            ]
        forged_in = sum(1 for i in self.forged if lo <= i)
        forged_out = sum(1 for i in undelivered if i in self.forged)
        honest_out = len(undelivered) - forged_out
        # rejections beyond the forged items left undelivered hit honest
        # ones; an item neither delivered nor rejected has no verdict
        honest_rejected = min(honest_out, max(0, d["rejected"] - forged_out))
        missing = max(0, len(undelivered) - d["rejected"])
        isolated = observe.series_delta(
            self.counters_before, self.counters_drained, ISOLATED)
        rows = [r for r in self.drained_rows if r["kind"] == "batch"]
        open_breaker = sum(int(h["breaker"] != "closed") for h in
                           (self.health_window, after, self.health_end))
        kernels = observe.kernels_called(
            self.counters_before, self.counters_settled
        )
        other = sum(n for k, n in kernels.items()
                    if k != self.cell["kernel"])
        return [
            ("sampled", len(self.sample), int(self.cell["reference_sample"])),
            ("sampled_not_half_forged",
             abs(sampled_forged - len(self.sample) // 2), 0),
            ("verdict_mismatch", mismatch, 0),
            ("forged_delivered", forged_delivered, 0),
            ("honest_rejected", honest_rejected, 0),
            ("missing_verdicts", missing, 0),
            ("rejected_not_forged_submitted",
             abs(d["rejected"] - forged_in), 0),
            ("isolated_not_batches_submitted",
             abs(int(isolated) - (hi - lo) // batch), 0),
            ("unmatched_verdicts", self.unmatched, 0),
            ("host_path_batches", d["breaker_skips"] + d["retries"]
             + d["settle_errors"]
             + sum(1 for r in rows if r["host_s"] > 0), 0),
            ("faulted_batches", sum(1 for r in rows if r["fault"]), 0),
            ("breaker_not_closed", open_breaker, 0),
            ("window_compiles", self.window_compiles + self.probe_compiles, 0),
            ("other_kernel_calls", other, 0),
            ("other_batch_buckets",
             sum(1 for r in rows if r["bucket"] != batch), 0),
            ("cell_kernel_calls_missing",
             int(kernels.get(self.cell["kernel"], 0) == 0), 0),
            ("valid_batch_refused", int(not self.probe["valid"]), 0),
            ("forged_pair_accepted", int(self.probe["forged_pair"]), 0),
            ("off_subgroup_accepted", int(self.probe["off_subgroup"]), 0),
            ("malformed_delivered", self.malformed_delivered, 0),
            ("malformed_not_rejected", n_bad - self.malformed_rejected, 0),
            ("negatives_reference_accepts", sum(map(int, judged[-1])), 0),
        ]

    def calls(self) -> "list[dict]":
        """Shapes of the window's device calls, for the work counts: a
        row a call. A batch's first pass at its items; its descent's
        probes at THEIR real items (the halves of a bisection towards one
        bad item, which is this cell's traffic: 32, 32, 16, 16, ... of
        64), each in the bucket's shape all the same."""
        rows = []
        width = max(len(it.members) for it in
                    self.items[self._window_first_id:] or self.items)
        per_slot = len({it.message for it in self.slots[self.first_slot]})

        def row(n):
            return {"n": n, "w": width, "m": min(n, per_slot)}

        for r in self.flight_rows:
            if r["kind"] != "batch" or not r["items"]:
                continue
            rows.append(row(r["items"]))
            sizes, n = [], r["items"]
            while n > 1:
                sizes += [n // 2, n - n // 2]
                n -= n // 2
            rows.extend(row(n) for n in sizes[: r.get("probes", 0)])
        return rows
