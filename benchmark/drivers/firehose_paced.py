"""Driver of the firehose on live gossip: the node of `drivers/firehose.py`
(nothing there is edited: this file loads it and builds on its `Driver`),
fed the slot's single votes ONE BY ONE, each at a due time of its own
inside the slot's attestation phase, so that the collector closes most
batches by its deadline, short of the batch bound, and the verifier
dispatches them padded into its one batch bucket
(`AttestationVerifier.batch_bucket`): one executable, no compile in the
window, every vote still exactly one verdict.

What differs from the clean driver: the pacing (`_run_slot_phase`: per-vote
due times, a vote's latency runs from ITS due time), a warm-up that also
sends a partial batch and must find nothing left to compile, a `correct`
that also holds the padding to its contract (partial batches really
formed, every batch in the one bucket, two direct padded calls, extra
sampled votes from batches the deadline closed), and the delivery groups
the node's verdicts came in. On a program whose verifier has no
`batch_bucket` it refuses to run at once, before anything is built or
warmed: there a partial batch is another executable, i.e. a compile of
minutes inside the window.
"""

from __future__ import annotations

import os
import random
import time
from dataclasses import replace

from benchmark import loader, observe
from benchmark.generators.attestations import judge
from benchmark.reference import bls as ref

_clean = loader.load_driver(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "firehose"
)
Refused = _clean.Refused


def drop_partial_batches() -> None:
    """The control: breaks the guarantee "a batch ... is dispatched
    whatever its size". From the window's start a batch short of the
    batch bound is dropped where the pool thread takes it, so its votes
    get no verdict. Nothing is recompiled."""
    Driver.drop_partial = True


CONTROLS = dict(_clean.CONTROLS, drop_partial_batches=drop_partial_batches)


class Driver(_clean.Driver):
    #: votes of the warm-up's partial batch, and of the two direct padded
    #: calls after the window (fewer where the batch bound is no larger)
    PARTIAL_ITEMS = 5
    #: set by the control `drop_partial_batches`
    drop_partial = False

    def __init__(self, cell: dict, seed: int, say) -> None:
        super().__init__(cell, seed, say)
        #: ids of the items of each delivery (`on_valid_attestation_batch`
        #: is called once a batch), in delivery order
        self.groups: "list[list[int]]" = []
        #: how late each hand-over of the window was (sent - the due time
        #: of its oldest vote), seconds
        self.late: "list[float]" = []

    # ----------------------------------------------------------- set-up

    def setup(self) -> None:
        from grandine_tpu.runtime.attestation_verifier import (
            AttestationVerifier,
        )
        from grandine_tpu.tpu import compile_scope

        if not hasattr(AttestationVerifier, "batch_bucket"):
            why = ("the program's AttestationVerifier has no batch_bucket: "
                   "it dispatches a partial batch in a bucket of its own, "
                   "an executable this cell does not warm (a compile of "
                   "minutes inside the window)")
            self.say(phase="refused", why=why)
            raise Refused(why)
        super().setup()
        v, batch = self.verifier, int(self.shapes["max_batch"])
        if v.batch_bucket != batch:
            raise Refused(f"AttestationVerifier.batch_bucket is "
                          f"{v.batch_bucket}, the configuration's batches "
                          f"fill {batch} slots")
        # a partial batch, closed by the deadline: it has to run in the
        # executable the full batch has just warmed
        self.partial = min(self.PARTIAL_ITEMS, batch - 1)
        warm = self.slots[self.warm_slot][batch: batch + self.partial]
        if len(warm) != self.partial or self.partial < 2:
            raise Refused("the warm-up slot or the batch bound is too "
                          "small for a partial batch")
        before = compile_scope.totals()[1]
        self._submit(warm, time.perf_counter())
        if not self._wait_all(timeout=self.WARM_TIMEOUT_S):
            raise Refused("the partial warm-up was not answered: "
                          f"{dict(v.stats)}")
        if compile_scope.totals()[1] != before:
            raise Refused("the partial warm-up compiled: a batch short of "
                          "the bound reaches another shape")
        # every window vote's due time within its slot and its wire form,
        # made now so that the generator's loop only hands them over
        start = float(self.traffic["phase_start_s"])
        length = float(self.traffic["phase_seconds"])
        self.schedule = {}
        for slot in sorted(s for s in self.slots if s >= self.first_slot):
            rng = random.Random(f"paced|{self.seed}|{slot}")
            votes = [(start + length * rng.random(), it, self._wire(it))
                     for it in self.slots[slot]]
            self.schedule[slot] = sorted(votes, key=lambda vote: vote[0])
        self.say(phase="warm_partial", items=len(warm),
                 bucket=v.batch_bucket, stats=dict(v.stats))

    # ----------------------------------------------------------- window

    def run(self, seconds: float, trace_dir: "str | None") -> dict:
        if self.drop_partial:
            from grandine_tpu.runtime.attestation_verifier import (
                AttestationVerifier,
            )

            traced = AttestationVerifier._verify_batch_traced

            def drop(verifier, batch, life, t_start):
                if len(batch) < verifier.max_batch:
                    life.root.finish()
                    return None
                return traced(verifier, batch, life, t_start)

            AttestationVerifier._verify_batch_traced = drop
        return super().run(seconds, trace_dir)

    def _delivered(self, valids, now: float) -> None:
        group = []
        with self._lock:
            for valid in valids:
                key = (int(valid.earliest_slot) - 1,
                       tuple(int(i) for i in valid.indices))
                waiting = self._pending.get(key)
                if waiting:
                    ident = waiting.pop(0)
                    self.delivered[ident] = now
                    group.append(ident)
                else:
                    self.unmatched += 1
            self.groups.append(group)

    def _submit_due(self, votes, mark: float) -> float:
        """One `submit_many` call of votes that came due, each stamped
        with its own due time; returns the time it was sent."""
        with self._lock:
            for offset, it, _wire in votes:
                ident = len(self.items)
                self.items.append(it)
                self.due.append(mark + offset)
                self._pending.setdefault(
                    (it.slot, tuple(it.members)), []
                ).append(ident)
        sent = time.perf_counter()
        self.verifier.submit_many([wire for _offset, _it, wire in votes])
        return sent

    def _run_slot_phase(self, seconds: float) -> dict:
        """Open loop on the slot clock, a vote at a time: at each slot's
        mark the node's clock ticks; each of the slot's votes is due at
        mark + its own offset inside the attestation phase and is handed
        over at the generator's first tick at or after that (a tick at
        the next due time, `tick_max_s` at the most), whatever came due
        together in one call. No vote is held back to round a batch, none
        is sent twice. A vote's time runs from its due time to its
        verdict's delivery."""
        slot_s = float(self.traffic["slot_seconds"])
        lead = float(self.traffic["tick_lead_s"])
        tick = float(self.traffic["tick_max_s"])
        cycle = self._slot_cycle()
        t0 = time.perf_counter() + lead
        submitted, k = 0, 0
        while k * slot_s < seconds:
            mark = t0 + k * slot_s
            slot = next(cycle)
            with observe.annotate("bench/generator_sleep"):
                time.sleep(max(0.0, mark - lead - time.perf_counter()))
            self._tick(slot)
            votes, i = self.schedule[slot], 0
            while i < len(votes):
                now, j = time.perf_counter(), i
                while j < len(votes) and mark + votes[j][0] <= now:
                    j += 1
                if j == i:
                    time.sleep(min(tick, mark + votes[i][0] - now))
                    continue
                sent = self._submit_due(votes[i:j], mark)
                self.late.append(sent - (mark + votes[i][0]))
                submitted += j - i
                i = j
            k += 1
        out = self._close_window(t0, seconds)
        # an answer that comes late is late, not wrong: wait for it, and
        # for the last batch's flight row
        self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        try:
            self.verifier.flush(timeout=self.ANSWER_TIMEOUT_S)
        except TimeoutError:
            pass
        lat = self._latencies_ms()
        return dict(out, attempted=submitted,
                    verdict_p95_ms=observe.percentile(lat, 95),
                    gen={"late_ms_max": max(self.late) * 1000.0})

    # ------------------------------------------------------ correctness

    def settle(self) -> None:
        """The clean driver's settle (sample of the window against the
        reference, three direct calls, the malformed batch), then what
        the padding adds: further sampled votes from batches that the
        deadline closed short, and two direct calls of the window's
        executable padded as the node pads a first pass: five honest
        votes, and the same five with one forged."""
        from grandine_tpu.tpu import compile_scope

        super().settle()
        batch = int(self.shapes["max_batch"])
        lo, hi = self._window_first_id, self._window_end_id
        short = sorted(i for group in self.groups if len(group) < batch
                       for i in group if lo <= i < hi)
        srng = random.Random(f"sample-short|{self.seed}")
        want = int(self.cell["reference_sample_deadline_closed"])
        self.sample_short = srng.sample(short, min(want, len(short)))
        self._judged_short = [
            self.pool.submit(judge, self.keys.n, self.seed, [self.items[i]])
            for i in self.sample_short
        ]
        rng = random.Random(f"padded|{self.seed}")
        honest = list(self.items[lo:hi][: self.partial])
        a, b = rng.sample(range(len(honest)), 2)
        forged = list(honest)
        # another vote's signature: it decompresses, lies in G2, and only
        # the pairing refuses it
        forged[a] = replace(honest[a], signature=honest[b].signature)
        c0 = compile_scope.totals()[1]
        self.padded = {"valid": self._padded_accepts(honest),
                       "forged": self._padded_accepts(forged)}
        self.probe_compiles += compile_scope.totals()[1] - c0
        self.say(phase="settled_padded", padded=self.padded,
                 sampled_short=len(self.sample_short))

    def _padded_accepts(self, items) -> bool:
        """One call of the cell's executable over the resident registry
        with fewer items than slots, padded as `_device_dispatch` pads a
        first pass."""
        from grandine_tpu.crypto import bls as A

        v = self.verifier
        sigs = [A.Signature(ref.g2_from_bytes(it.signature,
                                              subgroup_check=False))
                for it in items]
        return bool(v.backend.fast_aggregate_verify_batch_indexed_async(
            [it.message for it in items], sigs,
            [it.members for it in items], v.registry,
            bucket_floor=(v.batch_bucket, 0),
        )())

    def checks(self) -> "list[tuple[str, float, float]]":
        """The clean driver's counts, and the padding's: all exact, limit
        0."""
        judged = [f.result(timeout=self.ANSWER_TIMEOUT_S * 5)
                  for f in self._judged_short]
        mismatch = sum(
            int(want[0] != (ident in self.delivered))
            for ident, want in zip(self.sample_short, judged)
        )
        batch = int(self.shapes["max_batch"])
        rows = [r for r in self.flight_rows if r["kind"] == "batch"]
        need = int(self.cell["reference_sample_deadline_closed"])
        return super().checks() + [
            ("deadline_closed_sample_missing",
             need - len(self.sample_short), 0),
            ("deadline_closed_verdict_mismatch", mismatch, 0),
            ("padded_batches_missing",
             int(not any(r["items"] < batch for r in rows)), 0),
            ("other_bucket_batches",
             sum(1 for r in rows if r["bucket"] != batch), 0),
            ("closed_by_disagrees", sum(
                1 for r in rows if (r["items"] < batch)
                != (r.get("closed_by") == "deadline")), 0),
            ("padded_valid_refused", int(not self.padded["valid"]), 0),
            ("padded_forged_accepted", int(self.padded["forged"]), 0),
        ]
