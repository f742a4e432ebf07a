"""Device-resident validator pubkey registry.

Committee-based consensus re-verifies the SAME validator keys every slot,
yet the verify plane used to re-upload each batch's pubkey rows (26 limbs
× 2 coords × 4 B = 208 B/key) on the per-batch clock — ~4× the device
execute time at the 50k-validator operating point (BENCH r5). This module
keeps the whole validator set's decompressed G1 pubkeys resident on the
accelerator as flat rest-format limb arrays; the indexed verify kernels
(`tpu/bls.py` *_idx_kernel) `gather` rows on-device from an int32 index
vector, so per-batch host→device traffic shrinks to signatures + message
points + indices.

Freshness model (the registry is an append-mostly mirror of
`state.validators`):
  - `ensure(pubkeys)` is called with the head state's compressed-pubkey
    tuple (`accessors.registry_columns(state).pubkeys`). States sharing an
    unmodified registry share ONE tuple object, so the hot check is a
    single identity comparison.
  - Validator-set GROWTH (deposits) extends the registry without touching
    existing rows: a prefix match appends only the new rows (an O(new)
    device scatter into spare capacity; capacity grows in powers of two so
    the gather kernels recompile only on capacity doubling).
  - `mark_stale()` (wired to the controller's `on_validator_set_change`
    hook: validator-count or finalized-epoch change) demotes the next
    `ensure` from the identity fast path to the full prefix check;
    `invalidate()` drops everything and forces a cold rebuild.

Ingest is the compressed-ingest path (PR 17): deposit-batch churn uploads
the RAW 48-byte compressed rows (48 B/row instead of 208 B/row of affine
limbs — ~4.3× less per-row traffic) and decompresses them on device with
the batched `g1_decompress` kernel (tpu/curve.py sqrt ladders), so the
per-key pure-Python `Fq2`-style host sqrt disappears from registry builds
too. The host mirror holds the same raw bytes, so capacity growth
re-uploads without re-decompressing anything anywhere.

Rows are guaranteed non-identity: `_raw_rows` rejects the infinity
encoding (and any wire-malformed blob) before it can enter the mirror, so
indexed kernels need no per-row infinity handling beyond the batch
padding mask the caller supplies. A payload that is wire-well-formed but
off-curve/non-canonical (possible only for corrupted input — registry
bytes passed KeyValidate at deposit time) is zeroed by the device
decompressor's validity mask: fail-closed, any verification naming that
row fails, and the host mirror stays authoritative for naming it.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from grandine_tpu.consensus import keys
from grandine_tpu.crypto import bls as A
from grandine_tpu.tpu import curve as C
from grandine_tpu.tpu import limbs as L

#: smallest device capacity — below this, padding waste is noise and a
#: stable floor avoids recompiling the gather kernels for tiny devnets
MIN_CAPACITY = 16

#: the mainnet operating point: ≥1M active validators. A manifest bound
#: and warmup-ladder row (tools/shapes), so the 2^20 gather-kernel
#: capacity pre-warms like any other contract instead of compiling the
#: first time a mainnet-sized state walks in.
MAINNET_CAPACITY = 1 << 20


def _next_pow2(n: int, lo: int = MIN_CAPACITY) -> int:
    b = lo
    while b < n:
        b <<= 1
    return b


class DevicePubkeyRegistry:
    """The validator set's G1 pubkeys, device-resident and index-addressable.

    Thread-safe: `ensure` may be called from any verify-pool thread; the
    controller's mutator thread calls `mark_stale`/`invalidate` through the
    validator-set-change hook.
    """

    def __init__(self, metrics=None, mesh=None) -> None:
        from grandine_tpu.tpu.mesh import mesh_or_none

        self.metrics = metrics
        #: injected VerifyMesh (tpu/mesh.py): with a multi-device mesh the
        #: device arrays are row-sharded over it (`P("batch")` on axis 0),
        #: so the table's residency scales with the fleet — capacity is
        #: always a power of two ≥ MIN_CAPACITY, so any power-of-two mesh
        #: divides it evenly. None (or a 1-device mesh) keeps the plain
        #: single-chip placement byte-for-byte.
        self.mesh = mesh_or_none(mesh)
        self._lock = threading.RLock()
        #: host mirror: the exact compressed-bytes tuple the device arrays
        #: were built from (identity-compared against head-state columns)
        self._pubkeys: "Optional[tuple]" = None
        self._stale = False
        #: host raw-bytes rows ((capacity, 48) uint8, `_hcount` occupied)
        #: — the compressed wire encoding itself, kept so capacity growth
        #: re-uploads without re-decompressing (the device kernel redoes
        #: the sqrt, the host never does). Growth is geometric: at 2^20
        #: rows a per-append `np.concatenate` would copy the whole mirror
        #: per deposit batch; in-place writes make churn O(new) with
        #: O(log n) reallocations over the set's lifetime.
        self._hraw: "Optional[np.ndarray]" = None
        self._hcount = 0
        #: device arrays, (capacity, NLIMBS) int32 Montgomery limbs
        self._x = None
        self._y = None
        self.stats = {
            "hits": 0, "misses": 0, "appends": 0, "refreshes": 0,
            "uploaded_bytes": 0, "host_grows": 0,
        }

    # --------------------------------------------------------------- state

    @property
    def count(self) -> int:
        with self._lock:  # RLock: fine from already-locked callers
            return 0 if self._pubkeys is None else len(self._pubkeys)

    @property
    def capacity(self) -> int:
        with self._lock:
            return 0 if self._x is None else int(self._x.shape[0])

    def arrays(self):
        """(device_x, device_y, count) — rows past `count` are zero
        padding and must be masked by the caller's batch padding mask."""
        with self._lock:
            return self._x, self._y, self.count

    def public_keys(self, indices: "Sequence[int]"):
        """Decompressed PublicKeys for `indices` from the host mirror —
        the upload-path fallback for batches the indexed kernels cannot
        take (out-of-range index, committee wider than a bucket)."""
        with self._lock:
            pks = self._pubkeys or ()
        return keys.decompress_pubkeys(
            (pks[int(i)] for i in indices), trusted=True
        )

    # ------------------------------------------------------------- metrics

    def _event(self, event: str) -> None:
        if self.metrics is not None:
            self.metrics.pubkey_registry_events.labels(event).inc()

    def _sync_gauges(self) -> None:
        if self.metrics is None:
            return
        self.metrics.pubkey_registry_size.set(self.count)
        cap = self.capacity
        self.metrics.pubkey_registry_capacity.set(cap)
        host = 0 if self._hraw is None else int(self._hraw.nbytes)
        self.metrics.pubkey_registry_host_bytes.set(host)
        dev = cap * L.NLIMBS * 4 * 2
        self.metrics.pubkey_registry_device_bytes.set(dev)
        shards = 1 if self.mesh is None else max(1, self.mesh.device_count)
        self.metrics.pubkey_registry_shard_bytes.set(dev // shards)

    def _count_upload(self, nbytes: int) -> None:
        self.stats["uploaded_bytes"] += nbytes
        if self.metrics is not None:
            # labeled apart from the per-batch verify kernels: registry
            # uploads are amortized over the set's lifetime, not charged
            # to any batch (the lint rule no-per-batch-upload relies on
            # this separation)
            self.metrics.device_upload_bytes.labels("pubkey_registry").inc(
                nbytes
            )

    # ------------------------------------------------------------ lifecycle

    def mark_stale(self) -> None:
        """Demote the next ensure() from the identity fast path to the
        full prefix check (controller validator-set-change hook)."""
        with self._lock:
            self._stale = True

    def invalidate(self) -> None:
        """Drop device arrays and the host mirror; the next ensure() does
        a cold rebuild."""
        with self._lock:
            self._pubkeys = None
            self._hraw = None
            self._hcount = 0
            self._x = self._y = None
            self._stale = False
            self._event("invalidate")
            self._sync_gauges()

    # --------------------------------------------------------------- ensure

    def ensure(self, pubkeys: "Sequence[bytes]") -> bool:
        """Make the registry cover `pubkeys` (the head state's compressed
        pubkey tuple). Identity match → free hit; prefix growth → O(new)
        append; anything else → full refresh. Returns True when the
        device arrays are usable (always, barring an empty set)."""
        if not isinstance(pubkeys, tuple):
            pubkeys = tuple(bytes(b) for b in pubkeys)
        if len(pubkeys) == 0:
            return False
        with self._lock:
            old = self._pubkeys
            if old is pubkeys and not self._stale:
                self.stats["hits"] += 1
                self._event("hit")
                return True
            self.stats["misses"] += 1
            self._event("miss")
            if (
                old is not None
                and len(pubkeys) >= len(old)
                and pubkeys[: len(old)] == old
            ):
                if len(pubkeys) > len(old):
                    self._append(pubkeys, start=len(old))
                # equal prefix, equal length: same set under a new tuple
                # object (or a stale-flag re-check) — adopt the new tuple
                # so the next ensure() hits on identity
                self._pubkeys = pubkeys
            else:
                self._refresh(pubkeys)
            self._stale = False
            self._sync_gauges()
            return True

    # ------------------------------------------------------------ internals

    def _raw_rows(self, pubkey_bytes: "Sequence[bytes]") -> "np.ndarray":
        """Compressed bytes → (n, 48) uint8 raw rows for device-side
        decompression. Raises BlsError on what the WIRE alone can
        answer: wrong length, missing compressed flag, or the identity
        encoding (identity keys never enter the registry — the indexed
        kernels rely on it). Off-curve/non-canonical payloads pass
        through and are zeroed per-row by the device decompressor's
        validity mask (fail-closed; see module docstring)."""
        try:
            rows = C.compressed_rows(pubkey_bytes, 48)
        except ValueError as e:
            raise A.BlsError(str(e)) from None
        if rows.shape[0]:
            flags = rows[:, 0]
            if ((flags & C.COMPRESSED_FLAG) == 0).any():
                raise A.BlsError("uncompressed pubkey in registry input")
            if ((flags & C.INFINITY_FLAG) != 0).any():
                raise A.BlsError("identity pubkey can not enter the registry")
        return rows

    def _decompress_dev(self, raw: "np.ndarray"):
        """Upload (b, 48) uint8 raw rows and run the batched
        g1_decompress kernel: returns device ((b, NLIMBS) x, (b, NLIMBS)
        y) Montgomery rows. Rows the decompressor rejects (and zero
        padding rows) come back zeroed — never batch-fatal."""
        from grandine_tpu.tpu import bls as B

        x, y, _inf, _ok, _be, _bc, _bi = B.g1_decompress_rows(
            raw, self.metrics
        )
        return x, y

    def _host_reserve(self, rows: int) -> None:
        """Grow the host mirror to hold `rows`, geometrically — appends
        within capacity are pure in-place writes."""
        cur = 0 if self._hraw is None else int(self._hraw.shape[0])
        if rows <= cur:
            return
        cap = _next_pow2(rows)
        nraw = np.zeros((cap, 48), np.uint8)
        if self._hraw is not None and self._hcount:
            nraw[: self._hcount] = self._hraw[: self._hcount]
        self._hraw = nraw
        self.stats["host_grows"] += 1

    def _append(self, pubkeys: tuple, start: int) -> None:
        import jax

        raw = self._raw_rows(pubkeys[start:])
        end = len(pubkeys)
        n_new = end - start
        self._host_reserve(end)
        self._hraw[start:end] = raw
        self._hcount = end
        if end <= self.capacity:
            # in-place device scatter of O(new) rows: upload the RAW
            # 48-byte rows (bucketed so the decompress kernel's dispatch
            # shapes stay on the warm ladder) and decompress on device —
            # 48 B/row of traffic instead of 208 B/row of affine limbs
            b = _next_pow2(n_new)
            pad = np.zeros((b, 48), np.uint8)
            pad[:n_new] = raw
            dx, dy = self._decompress_dev(pad)
            self._x = self._x.at[start:end].set(dx[:n_new])
            self._y = self._y.at[start:end].set(dy[:n_new])
            if self.mesh is not None:
                # re-pin the row sharding: the eager scatter's output
                # layout is XLA's choice, and the shard-per-device
                # invariant is what the indexed kernels compile against
                sharding = self.mesh.batch_sharding()
                self._x = jax.device_put(self._x, sharding)
                self._y = jax.device_put(self._y, sharding)
            self._count_upload(int(pad.nbytes))
        else:
            self._upload_full(end)
        self._pubkeys = pubkeys
        self.stats["appends"] += 1
        self._event("append")

    def _refresh(self, pubkeys: tuple) -> None:
        raw = self._raw_rows(pubkeys)
        self._hraw = None
        self._hcount = 0
        self._host_reserve(len(pubkeys))
        self._hraw[: len(pubkeys)] = raw
        self._hcount = len(pubkeys)
        self._pubkeys = pubkeys
        self._upload_full(len(pubkeys))
        self.stats["refreshes"] += 1
        self._event("refresh")

    def _upload_full(self, count: int) -> None:
        """(Re)build the device arrays at power-of-two capacity from the
        host mirror: ONE raw-bytes upload + ONE batched decompress at
        capacity shape (the same bucket the gather kernels compile
        against, so warmup's capacity row covers it). Zero rows pad
        count..capacity — the decompressor zeroes them under an invalid
        mask, which is exactly the padding the gather kernels expect."""
        import jax

        cap = _next_pow2(count)
        if self.mesh is not None:
            # a power-of-two mesh must divide the power-of-two capacity;
            # MIN_CAPACITY floors the row count above any sane mesh width
            cap = max(cap, _next_pow2(self.mesh.device_count))
        praw = np.zeros((cap, 48), np.uint8)
        praw[:count] = self._hraw[:count]
        dx, dy = self._decompress_dev(praw)
        if self.mesh is not None:
            # row-sharded residency: the indexed kernels gather rows
            # on-device and XLA routes cross-shard lookups over the mesh
            sharding = self.mesh.batch_sharding()
            self._x = jax.device_put(dx, sharding)
            self._y = jax.device_put(dy, sharding)
        else:
            self._x = dx
            self._y = dy
        self._count_upload(int(praw.nbytes))


__all__ = ["DevicePubkeyRegistry", "MIN_CAPACITY", "MAINNET_CAPACITY"]
