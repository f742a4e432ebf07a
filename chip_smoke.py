"""Prove that the served verify path runs on one TPU v5e chip.

    python chip_smoke.py                 # one chip: device, plane, node
    python chip_smoke.py --chips 4       # four chips: the sharded path only
    python chip_smoke.py --rehearse      # CPU, tiny sizes, 4 virtual devices

One process, one chip, no child that needs JAX. Phases, in order; each
prints one JSON line when it ends and any failure exits non-zero at once:

  device  jax.devices() must be a TPU; host and device memory on the page.
  plane   a TpuBlsBackend behind TpuVerifier.finish_async at the widest
          bucket (16,384 signatures x 16,384 keys over 256 messages),
          valid / one forged / sampled against the host anchor / three
          fresh batches with no recompile; then the firehose's shape: a
          50,000-key DevicePubkeyRegistry and a 64-aggregate batch through
          the indexed kernel, valid and one forged.
  node    `grandine_tpu.cli run --use-device` on the minimal preset, judged
          from its own /metrics and flight endpoint: head advanced every
          slot, device batches > 0, no fallback, breaker never left closed.

It compiles three verify executables (grouped MSM verify at 256x64,
indexed aggregate verify at 64x256 over 65,536 registry rows and at 64x4
over 64 rows: the node's batch of two, padded into its one batch bucket)
and the registry's g1_decompress at two capacities — one at a time, host
memory trimmed after each (tpu/compile_scope.py). Before each compile it
prints its resident memory and refuses to start one that the host cannot
hold. CHANGES.md (PR 22) has the budget these were sized by.

The last line of a chip run is `{"ok": true, "device": {...}}`. A
rehearsal never prints it, and without `--rehearse` a missing chip is a
failure, not a reason to fall back to the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import resource
import shutil
import socket
import sys
import tempfile
import time
import urllib.request

#: phase `plane`, the width: the backend's widest bucket over 256 distinct
#: messages (BASELINE.md config 2's shape)
PLANE_N = 1 << 14
PLANE_MSGS = 256
#: phase `plane`, the firehose's shape: the reference's operator size
#: (BASELINE.json) and one full gossip batch (attestation_verifier
#: MAX_BATCH) of mainnet-preset committees at that validator count:
#: 50,000 / 32 slots / 12 committees = 130 members
REGISTRY_KEYS = 50_000
AGG_ITEMS = 64
AGG_WIDTH = 130
#: phase `node`: minimal preset (8 slots/epoch, target committee size 4) —
#: 64 validators give 2 committees of 4 every slot, so every slot's
#: attestations form one device batch, padded like any batch into the
#: verifier's one batch bucket (AGG_ITEMS) at committee width 4
NODE_VALIDATORS = 64
NODE_SLOTS = 8
NODE_COMMITTEES_PER_SLOT = 2
NODE_COMMITTEE_SIZE = 4
#: --chips 4: one sharded grouped batch at this bucket
MESH_N = 1 << 12
MESH_MSGS = 64

#: a compile of a pairing kernel peaks ~4 GB above what the process holds
COMPILE_HEADROOM = 4 << 30


class PhaseFailed(Exception):
    pass


def registry_capacity(count: int) -> int:
    from grandine_tpu.tpu.registry import _next_pow2

    return _next_pow2(count)


# ------------------------------------------------------------------ host


def _rss() -> int:
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def _mem_total() -> int:
    with open("/proc/meminfo") as fh:
        for line in fh:
            if line.startswith("MemTotal:"):
                return int(line.split()[1]) * 1024
    return 0


_T0 = time.perf_counter()


def _say(**row) -> None:
    """One JSON line, stamped with the seconds since the script started:
    a run that is cut still shows where its time went."""
    print(json.dumps({**row, "at_s": round(time.perf_counter() - _T0, 1)}),
          flush=True)


def before_compile(what: str) -> None:
    """Print resident memory ahead of a compile; refuse one the host
    cannot hold rather than be killed without a sentence."""
    rss, total = _rss(), _mem_total()
    _say(before_compile=what, rss=rss, mem_total=total)
    if rss + COMPILE_HEADROOM > 0.8 * total:
        raise PhaseFailed(
            f"resident memory {rss} plus {COMPILE_HEADROOM} for the compile "
            f"of {what} exceeds 80% of MemTotal {total}"
        )


class Phase:
    """Times one phase and prints its line: seconds, compile seconds (the
    process-wide compile clock), device and host memory, counters."""

    def __init__(self, name: str, device) -> None:
        self.name, self.device = name, device
        self.counters: dict = {}

    def __enter__(self) -> "Phase":
        from grandine_tpu.tpu import compile_scope

        self.t0 = time.perf_counter()
        self.c0 = compile_scope.totals()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        from grandine_tpu.tpu import bls as B
        from grandine_tpu.tpu import compile_scope

        c1 = compile_scope.totals()
        stats = (self.device.memory_stats() or {}) if self.device else {}
        _say(
            phase=self.name,
            passed=exc_type is None,
            seconds=round(time.perf_counter() - self.t0, 3),
            compile_seconds=round(c1[0] - self.c0[0], 3),
            compiles=c1[1] - self.c0[1],
            device_peak_bytes_in_use=stats.get("peak_bytes_in_use"),
            device_bytes_in_use=stats.get("bytes_in_use"),
            device_bytes_limit=stats.get("bytes_limit"),
            ru_maxrss=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            * 1024,
            rss=_rss(),
            post_warmup_recompiles=B.post_warmup_recompiles(),
            **self.counters,
            **({"error": f"{exc_type.__name__}: {exc}"} if exc_type else {}),
        )
        return False


def release_executables(what: str) -> None:
    """Drop every compiled executable this process holds and give the
    memory back. A kept pairing executable holds ~2.3 GB of HOST memory
    (CHANGES.md PR 22), and no section of this script needs another
    section's kernels, so it never holds more than one of them. A node
    keeps its executables; what that costs is on the phase lines."""
    import gc

    import jax

    from grandine_tpu.tpu.compile_scope import trim_host_memory

    before = _rss()
    jax.clear_caches()
    gc.unfreeze()  # compile_scope.settle_heap froze them with the rest
    gc.collect()
    trim_host_memory()
    _say(released=what, rss_before=before, rss=_rss())


def check(cond, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ------------------------------------------------------------------ data
#
# Keys in arithmetic progression (as benchmark/generators/keys.py makes
# them): sk_i = a + b*i, so N valid (pk, sig) pairs cost N point ADDS on the host
# and no device work. (a, b) come from --seed.


def _progression(seed: int, salt: int) -> "tuple[int, int]":
    import hashlib

    from grandine_tpu.crypto.constants import R

    def draw(tag: bytes) -> int:
        h = hashlib.sha256(b"chip_smoke|%d|%d|" % (seed, salt) + tag)
        return int.from_bytes(h.digest(), "big") % (R - 1) + 1

    return draw(b"a"), draw(b"b")


def make_keys(n: int, a: int, b: int):
    """[(a + b*i) * G1 for i < n] as curve points."""
    from grandine_tpu.crypto.curves import G1

    out, acc, step = [], G1.mul(a), G1.mul(b)
    for _ in range(n):
        out.append(acc)
        acc = acc + step
    return out


def make_sigs(n: int, a: int, b: int, hs):
    """sig_i = (a + b*i) * H[i mod M]: per message, walk i = j, j+M, …"""
    from grandine_tpu.crypto.constants import R

    m = len(hs)
    sigs = [None] * n
    for j in range(m):
        acc = hs[j].mul((a + b * j) % R)
        step = hs[j].mul((b * m) % R)
        for i in range(j, n, m):
            sigs[i] = acc
            acc = acc + step
    return sigs


def build_later_sections(seed: int, n: int, hs, n_keys: int,
                         agg_items: int, agg_width: int, fresh: int) -> dict:
    """Host-only data of the plane phase's later sections: the fresh wide
    batches, the registry's key bytes, the aggregates. Pure Python and
    the native library — no JAX call — so it runs on a helper thread
    while the main thread sits in the wide kernel's compile (XLA releases
    the GIL): ~70 s off a script that has 1,200 s, compiles included."""
    import random

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.constants import R
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2

    rng = random.Random(seed + 1)
    out = {"fresh": []}
    for r in range(fresh):
        a, b = _progression(seed, 1 + r)
        out["fresh"].append((
            [A.PublicKey(p) for p in make_keys(n, a, b)],
            make_sigs(n, a, b, hs),
        ))
    ka, kb = _progression(seed, 100)
    out["key_bytes"] = tuple(
        A.g1_to_bytes(p) for p in make_keys(n_keys, ka, kb)
    )
    members = [sorted(rng.sample(range(n_keys), agg_width))
               for _ in range(agg_items)]
    msgs = [b"chip-smoke-agg-%d-%d" % (seed, j) for j in range(agg_items)]
    agg_hs = [hash_to_g2(m) for m in msgs]
    sigs = [
        A.Signature(h.mul(sum(ka + kb * i for i in ix) % R))
        for h, ix in zip(agg_hs, members)
    ]
    bad = rng.randrange(agg_items)
    forged = list(sigs)
    forged[bad] = A.Signature(sigs[bad].point + agg_hs[bad])
    out.update(members=members, agg_msgs=msgs, agg_sigs=sigs,
               forged_aggs=forged, bad=bad)
    return out


# ----------------------------------------------------------------- device


def _runtime_claims(jax) -> dict:
    """Comments in the pre-chip scripts once justified host fetches and
    fresh arguments by a runtime whose block_until_ready "did not wait"
    and which "deduped" identical executions. Read both on THIS runtime:
    a result fetched after block_until_ready should cost ~nothing more,
    and the same call on the same arguments should take as long again."""
    import numpy as np

    fn = jax.jit(lambda x: (x @ x).sum())
    x = jax.device_put(np.ones((4096, 4096), np.float32))
    fn(x).block_until_ready()  # compile (a plain matmul: ~1 s)
    t0 = time.perf_counter()
    y = fn(x)
    dispatched = time.perf_counter() - t0
    y.block_until_ready()
    blocked = time.perf_counter() - t0
    float(y)
    fetched = time.perf_counter() - t0
    t0 = time.perf_counter()
    fn(x).block_until_ready()
    again = time.perf_counter() - t0
    return {"dispatch_s": round(dispatched, 6), "blocked_s": round(blocked, 6),
            "fetched_s": round(fetched, 6), "same_args_again_s": round(again, 6)}


def phase_device(rehearse: bool, chips: int):
    import jax

    import grandine_tpu.native as native
    from grandine_tpu.runtime.warmup import enable_persistent_cache

    with Phase("device", None) as ph:
        devices = jax.devices()
        dev = ph.device = devices[0]
        ph.counters.update(
            platform=dev.platform, kind=dev.device_kind, count=len(devices),
            jax=jax.__version__, mem_total=_mem_total(),
        )
        want = "cpu" if rehearse else "tpu"
        check(
            dev.platform == want,
            f"jax.devices() is {dev.platform!r}, not {want!r}: "
            + ("a rehearsal runs on the CPU" if rehearse
               else "this script proves the chip and has no host path"),
        )
        check(len(devices) >= chips,
              f"{len(devices)} devices visible, {chips} asked")
        ph.counters["runtime"] = _runtime_claims(jax)
        ph.counters.update(
            cache_dir=enable_persistent_cache(),
            cache_dir_from_env=bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR")
            ),
            native_built=native.available(),
        )
    return dev


# ------------------------------------------------------------------ plane


def phase_plane(dev, seed: int, n: int, n_msgs: int, n_keys: int,
                agg_items: int, agg_width: int, fresh: int) -> None:
    import random
    from concurrent.futures import ThreadPoolExecutor

    from grandine_tpu.consensus.verifier import SignatureInvalid, TpuVerifier
    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu.registry import DevicePubkeyRegistry

    rng = random.Random(seed)
    metrics = Metrics()
    with Phase("plane", dev) as ph:
        backend = B.TpuBlsBackend(metrics=metrics)
        ph.counters["donate_buffers"] = backend.donate_buffers
        check(
            backend.donate_buffers == (dev.platform != "cpu"),
            "donation is not what the backend defaults it to on this device",
        )

        # -- the width: n signatures by n distinct keys over n_msgs messages
        t0 = time.perf_counter()
        a, b = _progression(seed, 0)
        msgs = [b"chip-smoke-%d-%d" % (seed, j) for j in range(n_msgs)]
        hs = [hash_to_g2(m) for m in msgs]
        pks = [A.PublicKey(p) for p in make_keys(n, a, b)]
        sigs = make_sigs(n, a, b, hs)
        messages = [msgs[i % n_msgs] for i in range(n)]
        ph.counters["build_s"] = round(time.perf_counter() - t0, 3)
        later = ThreadPoolExecutor(1, "smoke-build").submit(
            build_later_sections, seed, n, hs, n_keys, agg_items, agg_width,
            fresh,
        )

        def through_verifier(sig_points) -> bool:
            """The entry block import and replay use: wire bytes in,
            TpuVerifier.finish_async -> multi_verify_async, settle."""
            verifier = TpuVerifier(backend)
            for msg, s, pk in zip(messages, sig_points, pks):
                verifier.verify_singular(msg, A.g2_to_bytes(s), pk)
            settle = verifier.finish_async()
            try:
                settle()
            except SignatureInvalid:
                return False
            return True

        def through_backend(sig_points) -> bool:
            return bool(backend.multi_verify_async(
                messages, [A.Signature(s) for s in sig_points], pks
            )())

        before_compile(f"grouped_multi_verify_msm {n}x{n_msgs}")
        t0 = time.perf_counter()
        check(through_verifier(sigs) is True,
              f"valid batch of {n} over {n_msgs} messages was rejected")
        ph.counters["wide_first_s"] = round(time.perf_counter() - t0, 3)
        _say(done="wide batch through TpuVerifier, first call",
             seconds=ph.counters["wide_first_s"])
        ph.counters["rss_after_wide_compile"] = _rss()
        calls = metrics.device_kernel_calls.labels("grouped_multi_verify_msm")
        check(calls.value == 1,
              "the wide batch did not reach the grouped MSM kernel")

        forged_at = rng.randrange(n)
        forged = list(sigs)
        forged[forged_at] = sigs[forged_at] + hs[0]
        t0 = time.perf_counter()
        check(through_backend(forged) is False,
              f"batch with signature {forged_at} forged was accepted")
        ph.counters["wide_forged_s"] = round(time.perf_counter() - t0, 3)

        for i in rng.sample(range(n), 8):
            for s, want in ((sigs[i], True), (forged[i], i != forged_at)):
                got = A.Signature(s).verify(messages[i], pks[i])
                check(got is want, f"host anchor disagrees on item {i}")
        check(A.Signature(forged[forged_at]).verify(
            messages[forged_at], pks[forged_at]) is False,
            "host anchor accepted the forged item")

        # -- fresh signatures and randomizers, same shape: no recompile
        t0 = time.perf_counter()
        later = later.result()
        ph.counters["waited_for_builder_s"] = round(
            time.perf_counter() - t0, 3)
        compiles0 = _compiles()
        times = []
        for r, (pks, fresh_sigs) in enumerate(later["fresh"]):
            t0 = time.perf_counter()
            check(through_backend(fresh_sigs) is True,
                  f"fresh batch {r} was rejected")
            times.append(round(time.perf_counter() - t0, 3))
        check(_compiles() == compiles0, "a fresh batch recompiled")
        ph.counters["wide_fresh_s"] = times
        release_executables("grouped_multi_verify_msm")

        # -- the firehose's shape: resident registry + indexed aggregates
        t0 = time.perf_counter()
        registry = DevicePubkeyRegistry(metrics=metrics)
        before_compile(f"g1_decompress {registry_capacity(n_keys)}")
        check(registry.ensure(later["key_bytes"]),
              "registry refused the key set")
        rx, _ry, count = registry.arrays()
        check(count == n_keys and rx.shape[0] == registry_capacity(n_keys),
              "registry does not hold the key set at pow-2 capacity")
        ph.counters["registry_s"] = round(time.perf_counter() - t0, 3)
        ph.counters["registry_rows"] = int(rx.shape[0])

        members, agg_msgs = later["members"], later["agg_msgs"]
        agg_sigs, forged_aggs = later["agg_sigs"], later["forged_aggs"]
        bad = later["bad"]
        before_compile(f"agg_fast_verify_msm_idx {agg_items}x{agg_width}")
        t0 = time.perf_counter()
        check(backend.fast_aggregate_verify_batch_indexed_async(
            agg_msgs, agg_sigs, members, registry)() is True,
            "valid indexed aggregate batch was rejected")
        ph.counters["agg_first_s"] = round(time.perf_counter() - t0, 3)
        _say(done="indexed aggregate batch, first call",
             seconds=ph.counters["agg_first_s"])
        ph.counters["rss_after_agg_compile"] = _rss()
        t0 = time.perf_counter()
        check(backend.fast_aggregate_verify_batch_indexed_async(
            agg_msgs, forged_aggs, members, registry)() is False,
            f"indexed batch with aggregate {bad} forged was accepted")
        ph.counters["agg_forged_s"] = round(time.perf_counter() - t0, 3)
        check(agg_sigs[bad].fast_aggregate_verify(
            agg_msgs[bad], registry.public_keys(members[bad])) is True
            and forged_aggs[bad].fast_aggregate_verify(
            agg_msgs[bad], registry.public_keys(members[bad])) is False,
            "host anchor disagrees on the aggregate")
        ph.counters["kernel_calls"] = {
            k: int(metrics.device_kernel_calls.labels(k).value)
            for k in ("grouped_multi_verify_msm", "agg_fast_verify_msm_idx")
        }
        del registry, rx, _ry
        release_executables("g1_decompress, agg_fast_verify_msm_idx")


def _compiles() -> int:
    from grandine_tpu.tpu import compile_scope

    return compile_scope.totals()[1]


# ------------------------------------------------------------------- node


class _SlotTee:
    """stdout wrapper for the node's run: passes everything through and,
    on each `slot N:` line the CLI prints after a slot has fully settled,
    scrapes the node's own /metrics (the server runs on its own thread of
    this process, and shuts down right after the last slot)."""

    def __init__(self, out, port: int) -> None:
        self.out, self.port = out, port
        self.scrapes: "list[dict]" = []
        self.flight = None
        self.warm_lines: "list[str]" = []
        self._buf = ""

    def write(self, text: str) -> int:
        self.out.write(text)
        self._buf += text
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if line.startswith("slot "):
                self.scrapes.append(_scrape(self.port))
                self.flight = json.loads(_get(
                    self.port, "/eth/v1/debug/grandine/flight?n=64"
                ))
            elif line.startswith("[warmup]"):
                self.warm_lines.append(line)
        return len(text)

    def flush(self) -> None:
        self.out.flush()


def _get(port: int, path: str) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as resp:
        return resp.read().decode()


def _scrape(port: int) -> dict:
    """{series (name with its labels): value} from the node's /metrics."""
    out = {}
    for line in _get(port, "/metrics").splitlines():
        if line and not line.startswith("#"):
            name, _, value = line.rpartition(" ")
            try:
                out[name] = float(value)
            except ValueError:
                pass
    return out


def _series(scrape: dict, family: str) -> dict:
    return {k: v for k, v in scrape.items()
            if k == family or k.startswith(family + "{")}


def phase_node(dev, validators: int, slots: int, data_dir: str,
               port: int) -> None:
    from grandine_tpu.cli import main

    with Phase("node", dev) as ph:
        before_compile(
            f"g1_decompress {registry_capacity(validators)} + "
            "agg_fast_verify_msm_idx "
            f"{AGG_ITEMS}x{NODE_COMMITTEE_SIZE}"
        )
        tee = _SlotTee(sys.stdout, port)
        with contextlib.redirect_stdout(tee):
            rc = main([
                "--network", "minimal", "--data-dir", data_dir,
                "--use-device", "run", "--validators", str(validators),
                "--slots", str(slots), "--http-port", str(port),
                "--no-restart",
            ])
        check(rc == 0, f"cli run returned {rc}")
        check(tee.warm_lines and " 1 entries" in tee.warm_lines[0],
              f"cli warmed more than this node's one shape: "
              f"{tee.warm_lines[:1]}")
        check(not any("FAILED" in w or "skipped" in w
                      for w in tee.warm_lines),
              f"a warm entry failed: {tee.warm_lines}")
        check(len(tee.scrapes) == slots, "not every slot was scraped")
        heads = [int(s.get("head_slot", -1)) for s in tee.scrapes]
        last = tee.scrapes[-1]
        batches = last.get("attestation_verifier_batches_total", 0)
        fallbacks = last.get("attestation_verifier_fallbacks_total", 0)
        device_s = _series(last, "verify_device_seconds_total")
        records = (tee.flight or {}).get("data", {}).get("records", [])
        ph.counters.update(
            heads=heads, att_batches=batches, att_fallbacks=fallbacks,
            device_batch_signatures=last.get(
                "device_batch_signatures_total", 0),
            verify_device_seconds=device_s,
            breaker_transitions=_series(
                last, "verify_breaker_transitions_total"),
            breaker_faults=_series(last, "verify_breaker_faults_total"),
            watchdog_fired=_series(last, "verify_watchdog_fired_total"),
            recompiles=last.get("verify_recompiles_total", 0),
            flight_records=len(records),
            flight_faults=[r.get("fault") for r in records
                           if r.get("fault")],
            warm=tee.warm_lines,
        )
        check(heads == list(range(1, slots + 1)),
              f"head did not advance by every slot: {heads}")
        check(batches >= slots and fallbacks == 0,
              f"firehose batches {batches}, fallbacks {fallbacks}")
        check(last.get("device_batch_signatures_total", 0)
              >= slots * NODE_COMMITTEES_PER_SLOT,
              "not every slot's aggregates were shipped to the device")
        check(not ph.counters["breaker_transitions"]
              and not ph.counters["breaker_faults"]
              and not ph.counters["watchdog_fired"],
              "the breaker or the watchdog fired")
        check(sum(device_s.values()) > 0, "no device seconds were recorded")
        check(records and not ph.counters["flight_faults"]
              and all(r.get("breaker_state") in ("", "closed")
                      for r in records),
              "the flight recorder saw a fault or a breaker not closed")
        check(last.get("verify_recompiles_total", 0) == 0,
              "a slot recompiled after warm-up")


# ------------------------------------------------------------------- mesh


def phase_mesh(dev, seed: int, chips: int, n: int, n_msgs: int,
               n_keys: int) -> None:
    """The sharded path and what it is compared with, nothing else: one
    sharded_multi_verify_msm batch over a `chips`-device mesh against the
    one-chip verdict of the same batch and the host anchor, and a look at
    where the registry's and the batch's rows really sit."""
    import random

    import jax

    from grandine_tpu.crypto import bls as A
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.metrics import Metrics
    from grandine_tpu.tpu import bls as B
    from grandine_tpu.tpu.mesh import VerifyMesh
    from grandine_tpu.tpu.registry import DevicePubkeyRegistry

    rng = random.Random(seed)
    with Phase("mesh", dev) as ph:
        mesh = VerifyMesh.build(chips)
        ph.counters["mesh_shape"] = mesh.describe()
        a, b = _progression(seed, 0)
        msgs = [b"chip-smoke-%d-%d" % (seed, j) for j in range(n_msgs)]
        hs = [hash_to_g2(m) for m in msgs]
        points = make_keys(max(n, n_keys), a, b)
        pks = [A.PublicKey(p) for p in points[:n]]
        sigs = make_sigs(n, a, b, hs)
        messages = [msgs[i % n_msgs] for i in range(n)]
        forged_at = rng.randrange(n)
        forged = list(sigs)
        forged[forged_at] = sigs[forged_at] + hs[0]

        # row-sharded registry: every device holds capacity/chips rows
        registry = DevicePubkeyRegistry(mesh=mesh)
        before_compile(f"g1_decompress {registry_capacity(n_keys)}")
        check(registry.ensure(
            tuple(A.g1_to_bytes(p) for p in points[:n_keys])),
            "registry refused the key set")
        rx, _ry, _count = registry.arrays()
        reg_shards = sorted(
            (s.device.id, tuple(s.data.shape)) for s in rx.addressable_shards
        )
        ph.counters["registry_shards"] = reg_shards
        check(len({d for d, _ in reg_shards}) == chips
              and all(shape[0] == rx.shape[0] // chips
                      for _, shape in reg_shards),
              f"registry rows are not spread over {chips} devices: "
              f"{reg_shards}")

        # where a batch's member rows land (the kernel's own placement)
        (probe,) = mesh.put(
            (jax.numpy.zeros((n_msgs, n // n_msgs), jax.numpy.int32),),
            mesh.member_sharding(),
        )
        batch_shards = sorted(
            (s.device.id, tuple(s.data.shape))
            for s in probe.addressable_shards
        )
        ph.counters["batch_shards"] = batch_shards
        check(len({d for d, _ in batch_shards}) == chips,
              f"batch rows are not spread over {chips} devices")

        results = {}
        for label, backend in (
            ("mesh", B.TpuBlsBackend(metrics=Metrics(), mesh=mesh)),
            ("one_chip", B.TpuBlsBackend(metrics=Metrics())),
        ):
            kernel = ("sharded_multi_verify_msm" if label == "mesh"
                      else "grouped_multi_verify_msm")
            before_compile(f"{kernel} {n}x{n_msgs}")
            t0 = time.perf_counter()
            good = bool(backend.multi_verify_async(
                messages, [A.Signature(s) for s in sigs], pks)())
            t1 = time.perf_counter()
            bad = bool(backend.multi_verify_async(
                messages, [A.Signature(s) for s in forged], pks)())
            results[label] = (good, bad)
            ph.counters[label] = {
                "valid": good, "forged": bad,
                "first_s": round(t1 - t0, 3),
                "second_s": round(time.perf_counter() - t1, 3),
                "calls": int(backend.metrics.device_kernel_calls.labels(
                    kernel).value),
            }
            check(ph.counters[label]["calls"] == 2,
                  f"the {label} batches did not reach {kernel}")
            ph.counters[label]["rss_after_compile"] = _rss()
            release_executables(kernel)
        host = (
            A.Signature(sigs[forged_at]).verify(
                messages[forged_at], pks[forged_at]),
            A.Signature(forged[forged_at]).verify(
                messages[forged_at], pks[forged_at]),
        )
        ph.counters["host_anchor"] = host
        check(results["mesh"] == results["one_chip"] == host == (True, False),
              f"verdicts differ: {results}, host {host}")


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded path and what it is "
                         "compared with (the driver runs 1)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, four virtual devices; never "
                         "prints the final line")
    ap.add_argument("--port", type=int, default=0,
                    help="the node phase's Beacon API port (default: any "
                         "free one)")
    args = ap.parse_args(argv)

    if args.rehearse:
        # before JAX is imported: the rehearsal is the one CPU path
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
    if not args.port:
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            args.port = sock.getsockname()[1]
    # a fresh chain every run: a node that finds one resumes it
    data_dir = tempfile.mkdtemp(prefix="chip_smoke_node_")
    try:
        dev = phase_device(args.rehearse, args.chips)
        if args.chips == 4:
            if args.rehearse:
                phase_mesh(dev, args.seed, 4, 32, 8, 16)
            else:
                phase_mesh(dev, args.seed, 4, MESH_N, MESH_MSGS, MESH_N)
        elif args.rehearse:
            phase_plane(dev, args.seed, 32, 8, 48, 4, 3, fresh=1)
            phase_node(dev, NODE_VALIDATORS, 2, data_dir, args.port)
        else:
            phase_plane(dev, args.seed, PLANE_N, PLANE_MSGS, REGISTRY_KEYS,
                        AGG_ITEMS, AGG_WIDTH, fresh=3)
            phase_node(dev, NODE_VALIDATORS, NODE_SLOTS, data_dir, args.port)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    from grandine_tpu.tpu import bls as B

    if B.post_warmup_recompiles() != 0:
        print("chip_smoke: FAILED: post-warm-up recompiles", file=sys.stderr)
        return 1
    if args.rehearse:
        print("rehearsal passed (no chip was touched; this is not a result)")
        return 0
    import jax

    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
