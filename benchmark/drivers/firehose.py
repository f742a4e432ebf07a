"""Driver of the gossip-attestation firehose: the node as `cli run
--use-device` builds it (`cli._node_once`: Database, Storage, Metrics,
Tracer, Slasher, OperationPool, InProcessNode(use_device_firehose=True)),
fed through `node.attestation_verifier.submit_many`, verdicts read where
the node delivers them (`controller.on_valid_attestation_batch`).

From the program it takes the system under test, its counters
(`Metrics.expose()`, the verifier's `stats`, `compile_scope.totals()`),
its flight records and its profiler capture session. Traffic, timing,
reduction and the reference are the benchmark's own.
"""

from __future__ import annotations

import dataclasses
import json
import multiprocessing
import os
import random
import shutil
import tempfile
import threading
import time
from concurrent.futures import ProcessPoolExecutor

from benchmark import observe
from benchmark.generators.attestations import (
    AttestationTraffic,
    ChainIdentity,
    judge,
    make_slot,
)
from benchmark.generators.keys import ProgressionKeys
from benchmark.reference import bls as ref

RANDAO_MIX = b"\x42" * 32  # interop_genesis_state's eth1_block_hash


class Refused(Exception):
    """The cell cannot be measured as stated (a second shape, a compile
    inside the window, a host path taken)."""


def equal_randomizers() -> None:
    """The control: breaks the stated guarantee "64-bit random-linear-
    combination randomizers per item". Every item of a batch gets the same
    randomizer, so forgeries that cancel in a plain sum pass the batch
    check. (Equal scalars fill the MSM's buckets unevenly, which changes a
    compile-time shape of the plan: a control run compiles an executable
    of its own in its warm-up, seven minutes on the chip.)"""
    from grandine_tpu.tpu import bls as B

    B.TpuBlsBackend._rlc_pair = staticmethod(
        lambda rng: (0x9E3779B9, 0x7F4A7C15)
    )


#: `--control <name>`: the program with one stated guarantee broken; such
#: a run has to come out with `correct` false
CONTROLS = {"equal_randomizers": equal_randomizers}


class Driver:
    #: how long the warm-up may take (it compiles on a cold cache), and how
    #: long an answer that is due is waited for once the window has closed
    WARM_TIMEOUT_S = 1500.0
    ANSWER_TIMEOUT_S = 60.0
    #: processes that make the traffic while the warm-up runs and the
    #: reference's verdicts while the trace is written: plain Python, no
    #: JAX, idle during the window
    WORKERS = 3

    def __init__(self, cell: dict, seed: int, say) -> None:
        self.cell, self.seed, self.say = cell, seed, say
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.shapes = self.config["shapes"]
        self.node = None
        self._tmp = None
        self.pool = None
        self._lock = threading.Lock()
        #: (slot, members) -> ids of submitted items still without verdict
        self._pending: "dict[tuple, list[int]]" = {}
        self.items: "list" = []          # every submitted item, by id
        self.due: "list[float]" = []     # its due time (perf_counter)
        self.delivered: "dict[int, float]" = {}
        self.unmatched = 0

    # ----------------------------------------------------------- set-up

    def setup(self) -> None:
        from grandine_tpu.metrics import Metrics
        from grandine_tpu.pools import OperationPool
        from grandine_tpu.runtime import InProcessNode
        from grandine_tpu.runtime.warmup import enable_persistent_cache
        from grandine_tpu.slasher import Slasher
        from grandine_tpu.storage import Database, Storage
        from grandine_tpu.tracing import Tracer
        from grandine_tpu.transition.genesis import interop_genesis_state
        from grandine_tpu.types.config import Config

        self.say(phase="cache", dir=enable_persistent_cache())
        sh = self.shapes
        cfg = Config.mainnet() if sh["preset"] == "mainnet" else Config.minimal()
        cfg = dataclasses.replace(
            cfg, altair_fork_epoch=0, bellatrix_fork_epoch=0,
            capella_fork_epoch=0, deneb_fork_epoch=0,
        )
        for name in ("SLOTS_PER_EPOCH", "TARGET_COMMITTEE_SIZE",
                     "MAX_COMMITTEES_PER_SLOT", "SHUFFLE_ROUND_COUNT"):
            if getattr(cfg.preset, name) != sh[name]:
                raise Refused(f"preset {name} is not the configuration's")
        n = int(sh["validators"])
        self.keys = ProgressionKeys(n, self.seed)
        self.say(phase="keys", validators=n)
        genesis = interop_genesis_state(
            n, cfg, eth1_block_hash=RANDAO_MIX,
            pubkeys=self.keys.pubkey_bytes(),
        )
        self.say(phase="genesis")

        # -- the node, argument for argument as cli._node_once builds it
        self._tmp = tempfile.mkdtemp(prefix="bench-node-")
        db = Database.persistent(os.path.join(self._tmp, "chain.sqlite"))
        storage = Storage(db, cfg)
        self.metrics = metrics = Metrics()
        stored, _unfinalized = storage.load(anchor_state=genesis)
        node = self.node = InProcessNode(
            stored, cfg, use_device_firehose=True, execution_engine=None,
            slasher=Slasher(db, metrics=metrics),
            operation_pool=OperationPool(cfg), metrics=metrics,
            tracer=Tracer(), mesh=None, use_isolation=True,
            use_brownout=True, database=db,
        )
        node.controller.storage = storage
        node.controller.store.pre_prune_hook = (
            node.controller._persist_finalized
        )
        node.controller.metrics = metrics
        self.verifier = v = node.attestation_verifier
        for name in ("max_batch", "deadline_s", "pipeline_depth"):
            if getattr(v, name) != sh[name]:
                raise Refused(
                    f"AttestationVerifier.{name} is {getattr(v, name)}, "
                    f"the configuration states {sh[name]}"
                )
        self.say(phase="node")

        # -- where the node delivers its verdicts
        inner = node.controller.on_valid_attestation_batch

        def deliver(valids):
            self._delivered(valids, time.perf_counter())
            return inner(valids)

        node.controller.on_valid_attestation_batch = deliver

        # -- traffic
        head = node.controller.snapshot()
        state = head.head_state
        chain = ChainIdentity(
            genesis_validators_root=bytes(state.genesis_validators_root),
            fork_version=bytes(state.fork.current_version),
            anchor_root=bytes(head.head_root),
            randao_mix=RANDAO_MIX,
        )
        from grandine_tpu.transition.fork_upgrade import state_phase
        from grandine_tpu.types.containers import spec_types

        self._ns = getattr(spec_types(cfg.preset), state_phase(state, cfg).key)
        # made by the workers while this process warms the executable; the
        # first slot is the warm-up's, the window's follow it
        first = int(self.traffic["first_slot"])
        self.pool = ProcessPoolExecutor(
            max_workers=self.WORKERS,
            mp_context=multiprocessing.get_context("spawn"),
        )
        made = {
            slot: self.pool.submit(
                make_slot, json.dumps(self.traffic), json.dumps(sh), n,
                self.seed, chain, slot,
            )
            for slot in range(first, first + int(self.traffic["slots"]) + 1)
        }
        self.warm_slot, self.first_slot = first, first + 1

        # -- warm the cell's one shape through the node itself
        from grandine_tpu.consensus import accessors
        from grandine_tpu.tpu import compile_scope

        v.registry.ensure(accessors.registry_columns(state).pubkeys)
        self.say(phase="registry", rows=int(v.registry.capacity))
        self.slots = {first: made[first].result()}
        self.say(phase="traffic_begun", slots=len(made),
                 items_per_slot=len(self.slots[first]))
        # one full batch, twice: the first pass compiles (or loads) the
        # cell's executable, the second must find nothing left to compile
        warm = self.slots[self.warm_slot][: int(sh["max_batch"])]
        self._tick(self.warm_slot)
        self._submit(warm, time.perf_counter())
        answered = self._wait_all(timeout=self.WARM_TIMEOUT_S)
        after_first = compile_scope.totals()
        self._submit(warm, time.perf_counter())
        if not (answered and self._wait_all(timeout=self.WARM_TIMEOUT_S)):
            raise Refused(f"the warm-up was not answered: {dict(v.stats)}")
        if compile_scope.totals()[1] != after_first[1]:
            raise Refused("the warm-up's second pass compiled: the cell's "
                          "traffic reaches more than one shape")
        self.say(phase="warm", compile_s=round(after_first[0], 1),
                 compiles=after_first[1], stats=dict(v.stats))
        for slot, future in made.items():
            self.slots[slot] = future.result()
        self.say(phase="traffic", slots=len(self.slots))

    # ------------------------------------------------------- submission

    def _tick(self, slot: int) -> None:
        """The node's clock reaches `slot`. Not `controller.wait()`: that
        waits for the verify batches in flight too, and under a backlog
        there always are some."""
        from grandine_tpu.fork_choice.store import Tick, TickKind

        self.node.controller.on_tick(Tick(slot, TickKind.ATTEST))
        end = time.monotonic() + 10.0
        while self.node.controller.store.slot < slot:
            if time.monotonic() > end:
                raise Refused(f"the node's clock did not reach slot {slot}")
            time.sleep(0.001)

    def _wire(self, item):
        import numpy as np

        ns = self._ns
        return ns.Attestation(
            aggregation_bits=np.asarray(item.bits, dtype=bool),
            data=ns.AttestationData(
                slot=item.slot, index=item.index,
                beacon_block_root=item.beacon_block_root,
                source=ns.Checkpoint(epoch=item.source[0],
                                     root=item.source[1]),
                target=ns.Checkpoint(epoch=item.target[0],
                                     root=item.target[1]),
            ),
            signature=item.signature,
        )

    def _submit(self, items, due: float) -> float:
        """One `submit_many` call; returns the time it was sent."""
        wire = [self._wire(it) for it in items]
        with self._lock:
            for it in items:
                ident = len(self.items)
                self.items.append(it)
                self.due.append(due)
                self._pending.setdefault(
                    (it.slot, tuple(it.members)), []
                ).append(ident)
        sent = time.perf_counter()
        self.verifier.submit_many(wire)
        return sent

    def _delivered(self, valids, now: float) -> None:
        with self._lock:
            for valid in valids:
                key = (int(valid.earliest_slot) - 1,
                       tuple(int(i) for i in valid.indices))
                waiting = self._pending.get(key)
                if waiting:
                    self.delivered[waiting.pop(0)] = now
                else:
                    self.unmatched += 1

    def _outstanding(self) -> int:
        with self._lock:
            return len(self.items) - len(self.delivered)

    def _wait_all(self, timeout: float) -> bool:
        """Until every submitted item has a verdict (delivered, or counted
        as rejected by the verifier) or `timeout` passes."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if self._outstanding() - self._rejected() <= 0:
                return True
            time.sleep(0.02)
        return False

    def _rejected(self) -> int:
        return int(self.verifier.stats["rejected"])

    # ----------------------------------------------------------- window

    def run(self, seconds: float, trace_dir: "str | None") -> dict:
        from grandine_tpu.tpu import compile_scope

        pace = getattr(self, "_run_" + self.traffic["pacing"])
        self._window_first_id = len(self.items)
        self.counters_before = observe.parse_exposition(self.metrics.expose())
        self.health_start = self._health()
        compiles0 = compile_scope.totals()[1]
        flight0 = self._flight_seq()
        self.trace = observe.TraceSession(self.node.profiler, trace_dir)
        out = pace(seconds)
        self.window_compiles = compile_scope.totals()[1] - compiles0
        self.counters_after = observe.parse_exposition(self.metrics.expose())
        self.flight_rows = [
            r.as_dict() for r in self.node.flight.snapshot(lane="attestation")
            if r.seq >= flight0
        ]
        self.window_calls = len(
            [r for r in self.flight_rows if r["kind"] == "batch"]
        )
        return out

    def traced_batch(self, asked: bool) -> None:
        """The device trace of a `--trace 1` run: ONE full batch of the
        window's own items put through the node once the window has closed
        and drained, traced from its submission to its verdict's delivery.
        One, because the chip records every executed operation and a
        verify call runs ~1.25 million of them (35-60 s of `stop_trace`
        and ~100 MB a call); after the window, so that the profiler slows
        nothing that is measured. The reference runs in the worker
        processes meanwhile: in this process's own threads it made
        `stop_trace` three times as long (my chip runs, PR 23). Off a TPU
        the batch goes through all the same, with no profiler."""
        if not asked:
            return
        batch = int(self.shapes["max_batch"])
        items = self.items[self._window_first_id:][:batch]
        if self.trace.wanted:
            self.trace.start()
            self.trace.mark_begin()
        with observe.annotate("bench/submit"):
            self._submit(items, time.perf_counter())
        with observe.annotate("bench/wait_verdicts"):
            self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        if self.trace.wanted:
            self.trace.stop()

    def _flight_seq(self) -> int:
        rows = self.node.flight.snapshot()
        return rows[-1].seq + 1 if rows else 0

    def _slot_cycle(self):
        """The window's slots in order. The traffic file holds more of
        them than a window takes; should they run out all the same, they
        come again from the first and `resubmitted` says so in the
        `closed` line (the node then sees votes it has seen)."""
        slots = sorted(s for s in self.slots if s >= self.first_slot)
        self.resubmitted = 0
        yield from slots
        while True:
            for slot in slots:
                self.resubmitted += len(self.slots[slot])
                yield slot

    def _run_backlog(self, seconds: float) -> dict:
        """Closed against the queue: whenever fewer than `backlog_items`
        items are without a verdict, the next slot's set is submitted."""
        batch = int(self.shapes["max_batch"])
        floor = int(self.traffic["backlog_items"])
        cycle = self._slot_cycle()
        t0 = time.perf_counter()
        submitted, carry = 0, []
        while (now := time.perf_counter()) < t0 + seconds:
            if self._outstanding() - self._rejected() < floor:
                slot = next(cycle)
                self._tick(slot)
                # whole batches only: what is left of a slot's set goes
                # first in the next call, so that a queue that drains
                # never forms a partial batch (another executable, and a
                # compile inside the window)
                due = carry + self.slots[slot]
                whole = len(due) - len(due) % batch
                carry = due[whole:]
                with observe.annotate("bench/submit"):
                    self._submit(due[:whole], now)
                submitted += whole
            with observe.annotate("bench/generator_sleep"):
                time.sleep(0.005)
        return dict(self._close_window(t0, seconds), attempted=submitted,
                    gen={})

    def _close_window(self, t0: float, seconds: float) -> dict:
        """Verdicts come a batch at a time, so a window cut at a fixed
        instant would count a batch or not by a hair. The window closes at
        the first verdict delivered at or after `seconds` (at `seconds`
        where nothing is left to deliver): the rate is every set given a
        verdict over ALL the time to that delivery, so a stall still
        counts and no part of a batch is cut."""
        end = t0 + seconds
        with observe.annotate("bench/generator_sleep"):
            time.sleep(max(0.0, end - time.perf_counter()))
        limit = time.monotonic() + self.ANSWER_TIMEOUT_S
        t1 = end
        while time.monotonic() < limit:
            with self._lock:
                late = [t for i, t in self.delivered.items()
                        if i >= self._window_first_id and t >= end]
                left = len(self.items) - len(self.delivered)
            if late:
                t1 = min(late)
                break
            if left - self._rejected() <= 0:
                break
            time.sleep(0.002)
        with self._lock:
            done = sum(1 for i, t in self.delivered.items()
                       if i >= self._window_first_id and t0 <= t <= t1)
        return {"sigsets_per_s": done / (t1 - t0), "in_window": done,
                "window_s": t1 - t0, "resubmitted": self.resubmitted}

    def _run_slot_clock(self, seconds: float) -> dict:
        """Open loop on the slot clock: at each slot's mark the slot's
        items are due together and submitted in one call. An item's time
        runs from its due time to its verdict's delivery."""
        slot_s = float(self.traffic["slot_seconds"])
        lead = float(self.traffic["tick_lead_s"])
        cycle = self._slot_cycle()
        t0 = time.perf_counter() + lead
        late, submitted, k = [], 0, 0
        while k * slot_s < seconds:
            due = t0 + k * slot_s
            slot = next(cycle)
            with observe.annotate("bench/generator_sleep"):
                time.sleep(max(0.0, due - lead - time.perf_counter()))
            self._tick(slot)
            with observe.annotate("bench/generator_sleep"):
                time.sleep(max(0.0, due - time.perf_counter()))
            with observe.annotate("bench/submit"):
                sent = self._submit(self.slots[slot], due)
            late.append(sent - due)
            submitted += len(self.slots[slot])
            k += 1
        out = self._close_window(t0, seconds)
        # an answer that comes late is late, not wrong: wait for it
        self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        lat = self._latencies_ms()
        return dict(out, attempted=submitted,
                    verdict_p95_ms=observe.percentile(lat, 95),
                    gen={"late_ms_max": max(late) * 1000.0})

    def _latencies_ms(self) -> "list[float]":
        with self._lock:
            return [
                (self.delivered[i] - self.due[i]) * 1000.0
                if i in self.delivered else float("inf")
                for i in range(self._window_first_id, len(self.items))
            ]

    # ------------------------------------------------------ correctness

    def settle(self) -> None:
        """After the window: wait for every answer that is due (a minute
        at the most); hand the reference its sample; then the negative
        cases. None of them reaches a second executable: three calls of
        the executable the window drove (a valid batch, a forged pair, a
        signature outside G2), and one whole batch of signatures that
        cannot be decompressed through the served entry, which the node
        has to reject item by item without a verdict."""
        from grandine_tpu.tpu import compile_scope

        answered = self._wait_all(timeout=self.ANSWER_TIMEOUT_S)
        self._window_end_id = len(self.items)
        self.health_window = self._health()
        v, batch = self.verifier, int(self.shapes["max_batch"])
        rng = random.Random(f"probe|{self.seed}")
        valid = list(self.items[self._window_first_id:][:batch])
        forged, i, j = AttestationTraffic.forged_pair(valid, rng)
        k = rng.randrange(len(valid))
        torsion = list(valid)
        torsion[k] = AttestationTraffic.off_subgroup(valid[k])
        bad = [AttestationTraffic.malformed(it) for it in valid]
        negatives = [forged[i], forged[j], torsion[k], bad[k]]

        # -- the reference, in the workers, while this process goes on
        ids = range(self._window_first_id, self._window_end_id)
        srng = random.Random(f"sample|{self.seed}")
        self.sample = srng.sample(
            ids, min(int(self.cell["reference_sample"]), len(ids)))
        jobs = [[self.items[i]] for i in self.sample] + [negatives]
        self._judged = [
            self.pool.submit(judge, self.keys.n, self.seed, job)
            for job in jobs
        ]

        # -- the executable the window drove, called as the node calls it
        c0 = compile_scope.totals()[1]
        self.probe = {name: self._executable_accepts(items) for name, items
                      in (("valid", valid), ("forged_pair", forged),
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
            # refused for good: they wait for no verdict any more (one
            # that came now would be counted as unmatched)
            for it in bad:
                waiting = self._pending[(it.slot, tuple(it.members))]
                waiting[:] = [i for i in waiting if i < first_bad]
        self.malformed_rejected = (after["stats"]["rejected"]
                                   - before["stats"]["rejected"])
        self._malformed = (first_bad, len(bad), before, after)
        self.probe_compiles = compile_scope.totals()[1] - c0
        self.say(phase="settled", answered=answered, probe=self.probe,
                 malformed_delivered=self.malformed_delivered,
                 malformed_rejected=self.malformed_rejected,
                 stats=after["stats"])

    def _executable_accepts(self, items) -> bool:
        """One call of the cell's executable over the resident registry,
        as `_device_dispatch` makes it. The signatures are decompressed by
        the benchmark's own reference; the program's signature type only
        carries the point."""
        from grandine_tpu.crypto import bls as A

        v = self.verifier
        sigs = [A.Signature(ref.g2_from_bytes(it.signature,
                                              subgroup_check=False))
                for it in items]
        return bool(v.backend.fast_aggregate_verify_batch_indexed_async(
            [it.message for it in items], sigs,
            [it.members for it in items], v.registry,
        )())

    def _health(self) -> dict:
        return {"stats": dict(self.verifier.stats),
                "breaker": self.node.health.state}

    def stop(self) -> None:
        if self.node is not None:
            self.health_end = self._health()
            self.node.stop()
            self.node = None
        if self._tmp is not None:
            shutil.rmtree(self._tmp, ignore_errors=True)
            self._tmp = None

    def close(self) -> None:
        """Ends the worker processes and waits for them."""
        if self.pool is not None:
            self.pool.shutdown(wait=True, cancel_futures=True)
            self.pool = None

    def checks(self) -> "list[tuple[str, float, float]]":
        """Every number compared, beside its limit. All are counts and all
        are exact: the limit is 0, and `sampled` has to reach its own."""
        judged = [f.result(timeout=self.ANSWER_TIMEOUT_S * 5)
                  for f in self._judged]
        mismatch = sum(
            int(want[0] != (ident in self.delivered))
            for ident, want in zip(self.sample, judged)
        )
        first_bad, n_bad, before, after = self._malformed
        # the program's counts over everything but the malformed batch
        spans = ((self.health_start, before), (after, self.health_end))
        d = {k: sum(b["stats"].get(k, 0) - a["stats"].get(k, 0)
                    for a, b in spans)
             for k in ("rejected", "fallbacks", "breaker_skips", "retries",
                       "settle_errors")}
        with self._lock:
            missing = sum(
                1 for i in range(self._window_first_id, len(self.items))
                if i not in self.delivered
                and not first_bad <= i < first_bad + n_bad
            )
        open_breaker = sum(int(h["breaker"] != "closed") for h in
                           (self.health_window, after, self.health_end))
        kernels = observe.kernels_called(
            self.counters_before, self.counters_after
        )
        other = sum(n for k, n in kernels.items()
                    if k != self.cell["kernel"])
        return [
            ("sampled", len(self.sample), int(self.cell["reference_sample"])),
            ("verdict_mismatch", mismatch, 0),
            ("rejected_valid", d["rejected"], 0),
            ("missing_verdicts", missing - d["rejected"], 0),
            ("unmatched_verdicts", self.unmatched, 0),
            ("host_path_batches", d["fallbacks"] + d["breaker_skips"]
             + d["retries"] + d["settle_errors"], 0),
            ("breaker_not_closed", open_breaker, 0),
            ("window_compiles", self.window_compiles + self.probe_compiles, 0),
            ("other_kernel_calls", other, 0),
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
        """Shapes of the verify calls of the window, for the work counts:
        items n, member width w (widest of the batch), distinct messages."""
        rows = []
        width = max(len(it.members) for it in
                    self.items[self._window_first_id:] or self.items)
        per_slot = len({it.message for it in self.slots[self.first_slot]})
        for r in self.flight_rows:
            if r["kind"] == "batch" and r["items"]:
                rows.append({"n": r["items"], "w": width,
                             "m": min(r["items"], per_slot)})
        return rows
