"""Driver of an all-subnets node's real queue: the node of
`drivers/firehose.py`, paced by `drivers/firehose_paced.py` (nothing in
either is edited: this file loads the paced driver and builds on its
`Driver`), fed BOTH kinds of item a slot into the one
`AttestationVerifier` queue: the slot's single votes one by one through
the attestation phase, and the same slot's aggregates all together where
the phase ends. Votes have one member, aggregates a whole committee; the
verifier keeps a width floor (`AttestationVerifier.width_floor`: the
widest committee it has dispatched), so once the warm-up's first batch of
aggregates has gone out every later call, a batch of 1-64 votes included,
runs the aggregates' executable: one shape, no compile and no second load
in the window, a vote and an aggregate in one batch each their own
verdict.

What differs from the paced driver: two kinds made by the one generator
(the traffic file's own parameters make the aggregates, its `votes` group
the votes); a warm-up that after the aggregates sends a full batch of
votes, a partial one and a mixed one and must find nothing left to
compile; a schedule of votes at their own offsets plus the aggregates at
`aggregates_due_s`; direct calls that name the verifier's floor as the
node does; a `correct` that also holds every batch of the window to the
one width bucket, wants a batch of both kinds formed, two direct mixed
calls right and further sampled aggregates and items of mixed batches
judged by the plain reference; the tail split by kind. On a program whose
verifier has no `width_floor` it refuses to run at once, before anything
is built or warmed: there votes run in the narrow executable and
aggregates in the wide one, and the second is a load of ~80 s (cold: a
compile of minutes) wherever it first meets one.
"""

from __future__ import annotations

import json
import os
import random
import time
from dataclasses import replace

from benchmark import loader, observe
from benchmark.generators.attestations import ChainIdentity, judge, make_slot
from benchmark.reference import bls as ref

_paced = loader.load_driver(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "firehose_paced",
)
Refused = _paced.Refused


def narrow_votes() -> None:
    """The control: breaks the guarantee "every device call of the window
    ... runs the cell's ONE executable". From the window's start the
    verifier forgets its width floor before every call, so a batch of
    votes alone is dispatched in the votes' own narrow bucket: another
    shape, and a compile (or load) inside the window."""
    Driver.forget_floor = True


CONTROLS = dict(_paced.CONTROLS, narrow_votes=narrow_votes)


def is_vote(item) -> bool:
    """A single vote names one member; an aggregate a committee, less one
    member at the most."""
    return len(item.members) == 1


class Driver(_paced.Driver):
    #: set by the control `narrow_votes`
    forget_floor = False

    # ----------------------------------------------------------- set-up

    def setup(self) -> None:
        from grandine_tpu.runtime.attestation_verifier import (
            AttestationVerifier,
        )
        from grandine_tpu.tpu import compile_scope

        if not hasattr(AttestationVerifier, "width_floor"):
            why = ("the program's AttestationVerifier has no width_floor: "
                   "it dispatches a batch of votes in the votes' own width "
                   "bucket and one of aggregates in theirs, two executables "
                   "where this cell warms one (a load of ~80 s, cold a "
                   "compile of minutes, inside the window)")
            self.say(phase="refused", why=why)
            raise Refused(why)
        self._votes_made = None
        # the clean and the paced set-up, on the traffic file's own
        # generator parameters, the aggregates: the first batch the node
        # ever sees is a full batch of aggregates, so the one executable
        # loaded is theirs; then a partial batch of them. The votes are
        # made by the same workers meanwhile (`_tick`)
        super().setup()
        v, batch = self.verifier, int(self.shapes["max_batch"])
        want = int(self.cell["width_bucket"])
        if v.width_bucket != want:
            raise Refused(f"after the aggregates' warm-up the verifier's "
                          f"width bucket is {v.width_bucket}, the cell's "
                          f"one executable is {batch} x {want}")
        self.votes = {slot: made.result()
                      for slot, made in self._votes_made.items()}
        # what the window will send beside full batches of aggregates: a
        # full batch of votes, a partial one, one of both kinds. Each has
        # to run in the executable that is loaded
        votes = self.votes[self.warm_slot]
        held_back = self.slots[self.warm_slot][batch + self.partial:]
        n_votes = (self.partial + 1) // 2
        mixed = (votes[batch + self.partial:][:n_votes]
                 + held_back[: self.partial - n_votes])
        if len(mixed) != self.partial or is_vote(mixed[-1]):
            raise Refused("the warm-up slot is too small for a mixed batch")
        before = compile_scope.totals()[1]
        flight0 = self._flight_seq()
        for items in (votes[:batch], votes[batch: batch + self.partial],
                      mixed):
            self._submit(items, time.perf_counter())
            if not self._wait_all(timeout=self.WARM_TIMEOUT_S):
                raise Refused("the votes' warm-up was not answered: "
                              f"{dict(v.stats)}")
        v.flush(timeout=self.ANSWER_TIMEOUT_S)
        if compile_scope.totals()[1] != before:
            raise Refused("the votes' warm-up compiled: votes, or votes "
                          "beside aggregates, reach another shape")
        rows = [r.as_dict()
                for r in self.node.flight.snapshot(lane="attestation")
                if r.seq >= flight0 and r.kind == "batch"]
        if [r for r in rows if r["width_bucket"] != want]:
            raise Refused("a warm-up batch of votes was dispatched in "
                          f"another width bucket than {want}: {rows}")
        # every window item's due time within its slot and its wire form,
        # made now so that the generator's loop only hands them over: the
        # votes at offsets of their own inside the phase, the aggregates
        # together where it ends
        start = float(self.traffic["phase_start_s"])
        length = float(self.traffic["phase_seconds"])
        due = float(self.traffic["aggregates_due_s"])
        self.schedule = {}
        for slot in sorted(s for s in self.slots if s >= self.first_slot):
            rng = random.Random(f"mixed|{self.seed}|{slot}")
            timed = [(start + length * rng.random(), it, self._wire(it))
                     for it in self.votes[slot]]
            timed += [(due, it, self._wire(it)) for it in self.slots[slot]]
            self.schedule[slot] = sorted(timed, key=lambda one: one[0])
        self.say(phase="warm_mixed", width_bucket=v.width_bucket,
                 width_floor=v.width_floor, batches=len(rows),
                 votes_per_slot=len(votes),
                 aggregates_per_slot=len(self.slots[self.warm_slot]),
                 stats=dict(v.stats))

    def _tick(self, slot: int) -> None:
        """The clean set-up ticks the node's clock once, right before its
        warm-up begins and with the workers' pool already made: that is
        where the votes of every slot are asked for, behind the
        aggregates, so that they are made while the executable loads."""
        if self._votes_made is None:
            head = self.node.controller.snapshot()
            state = head.head_state
            chain = ChainIdentity(
                genesis_validators_root=bytes(state.genesis_validators_root),
                fork_version=bytes(state.fork.current_version),
                anchor_root=bytes(head.head_root),
                randao_mix=_paced._clean.RANDAO_MIX,
            )
            self._votes_made = {
                s: self.pool.submit(
                    make_slot, json.dumps(self.traffic["votes"]),
                    json.dumps(self.shapes), self.keys.n, self.seed, chain, s,
                )
                for s in range(self.warm_slot, self.warm_slot
                               + int(self.traffic["slots"]) + 1)
            }
        super()._tick(slot)

    # ----------------------------------------------------------- window

    def run(self, seconds: float, trace_dir: "str | None") -> dict:
        if self.forget_floor:
            from grandine_tpu.runtime.attestation_verifier import (
                AttestationVerifier,
            )

            raise_floor = AttestationVerifier._raise_width_floor

            def forget(verifier, widest):
                with verifier._width_lock:
                    verifier._width_floor = 0
                return raise_floor(verifier, widest)

            AttestationVerifier._raise_width_floor = forget
        out = super().run(seconds, trace_dir)
        lo = self._window_first_id
        lat = self._latencies_ms()
        votes = [x for i, x in enumerate(lat) if is_vote(self.items[lo + i])]
        aggregates = [x for i, x in enumerate(lat)
                      if not is_vote(self.items[lo + i])]
        groups = self._window_groups(lo, len(self.items))
        mixed = [g for g in groups if self._is_mixed(g)]
        by_slot = {}
        for g in mixed:
            slot = self.items[g[0]].slot
            by_slot[slot] = by_slot.get(slot, 0) + 1
        return dict(
            out, votes_p95_ms=observe.percentile(votes, 95),
            aggregates_p95_ms=observe.percentile(aggregates, 95),
            votes=len(votes), aggregates=len(aggregates),
            mixed_batch_pct=100.0 * len(mixed) / max(1, len(groups)),
            mixed_batches_by_slot=sorted(by_slot.items()),
        )

    def _window_groups(self, lo: int, hi: int) -> "list[list[int]]":
        """The deliveries (one a batch) made of window items alone."""
        with self._lock:
            return [list(g) for g in self.groups
                    if g and all(lo <= i < hi for i in g)]

    def _is_mixed(self, group) -> bool:
        kinds = {is_vote(self.items[i]) for i in group}
        return len(kinds) == 2

    # ------------------------------------------------------ correctness

    def settle(self) -> None:
        """The paced driver's settle (its direct calls name the
        verifier's floor here, as the node's do), then what the two kinds
        add: further sampled aggregates and items of batches that held
        both kinds for the plain reference, and two direct calls of the
        window's executable over votes beside aggregates: all honest, and
        with a vote's and an aggregate's signatures exchanged."""
        from grandine_tpu.tpu import compile_scope

        super().settle()
        lo, hi = self._window_first_id, self._window_end_id
        self._mixed_groups = [g for g in self._window_groups(lo, hi)
                              if self._is_mixed(g)]
        srng = random.Random(f"sample-mixed|{self.seed}")
        pools = (
            ("aggregates", sorted(i for i in range(lo, hi)
                                  if not is_vote(self.items[i]))),
            ("mixed", sorted(i for g in self._mixed_groups for i in g)),
        )
        self.sample_kinds, self._judged_kinds = {}, {}
        for name, ids in pools:
            want = int(self.cell["reference_sample_" + name])
            self.sample_kinds[name] = srng.sample(ids, min(want, len(ids)))
            self._judged_kinds[name] = [
                self.pool.submit(judge, self.keys.n, self.seed,
                                 [self.items[i]])
                for i in self.sample_kinds[name]
            ]
        window = self.items[lo:hi]
        n_votes = (self.partial + 1) // 2
        honest = ([it for it in window if is_vote(it)][:n_votes]
                  + [it for it in window
                     if not is_vote(it)][: self.partial - n_votes])
        # each keeps its message and members and carries the other's
        # signature: both decompress, both lie in G2, only the pairing
        # refuses them
        forged = list(honest)
        forged[0] = replace(honest[0], signature=honest[-1].signature)
        forged[-1] = replace(honest[-1], signature=honest[0].signature)
        c0 = compile_scope.totals()[1]
        self.mixed_probe = {"valid": self._executable_accepts(honest),
                            "forged": self._executable_accepts(forged)}
        self.probe_compiles += compile_scope.totals()[1] - c0
        self.say(phase="settled_mixed", mixed=self.mixed_probe,
                 mixed_batches=len(self._mixed_groups),
                 sampled={k: len(v) for k, v in self.sample_kinds.items()})

    def _executable_accepts(self, items) -> bool:
        """One call of the cell's executable over the resident registry,
        as `_device_dispatch` makes a first pass here: in the node's one
        batch bucket and at its width floor, whatever the items' own
        widths."""
        from grandine_tpu.crypto import bls as A

        v = self.verifier
        sigs = [A.Signature(ref.g2_from_bytes(it.signature,
                                              subgroup_check=False))
                for it in items]
        return bool(v.backend.fast_aggregate_verify_batch_indexed_async(
            [it.message for it in items], sigs,
            [it.members for it in items], v.registry,
            bucket_floor=(v.batch_bucket, v.width_floor),
        )())

    _padded_accepts = _executable_accepts

    def checks(self) -> "list[tuple[str, float, float]]":
        """The paced driver's counts, and the two kinds': all exact, limit
        0."""
        missing = mismatch = 0
        for name, sample in self.sample_kinds.items():
            judged = [f.result(timeout=self.ANSWER_TIMEOUT_S * 5)
                      for f in self._judged_kinds[name]]
            missing += int(self.cell["reference_sample_" + name]) - len(sample)
            mismatch += sum(
                int(says[0] != (ident in self.delivered))
                for ident, says in zip(sample, judged)
            )
        bucket = int(self.cell["width_bucket"])
        rows = [r for r in self.flight_rows if r["kind"] == "batch"]
        return super().checks() + [
            ("other_width_bucket_batches",
             sum(1 for r in rows if r.get("width_bucket") != bucket), 0),
            ("mixed_batches_missing", int(not self._mixed_groups), 0),
            ("mixed_valid_refused", int(not self.mixed_probe["valid"]), 0),
            ("mixed_forged_accepted", int(self.mixed_probe["forged"]), 0),
            ("mixed_sample_missing", missing, 0),
            ("mixed_verdict_mismatch", mismatch, 0),
        ]

    def calls(self) -> "list[dict]":
        """Shapes of the verify calls of the window, for the work counts:
        what each was ASKED to do, not what it was padded to: its real
        items n, its widest item's members w (what the flight row calls
        `width`) and its distinct messages m, read off the driver's own
        items of each delivery."""
        rows = []
        for g in self._window_groups(self._window_first_id,
                                     self._window_end_id):
            items = [self.items[i] for i in g]
            rows.append({"n": len(items),
                         "w": max(len(it.members) for it in items),
                         "m": len({it.message for it in items})})
        return rows
