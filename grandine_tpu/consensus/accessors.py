"""Spec accessors with registry-columnar caching — reference:
helper_functions/src/accessors.rs (committees, proposer index, cached
shuffled indices, total balances) and types/src/cache.rs (intra-state
caches).

TPU-first design: the validator registry is viewed as numpy *columns*
(effective balance, activation/exit epochs, slashed) so every registry-wide
computation — active sets, churn, epoch deltas — is a vectorized array op,
not a per-validator loop. The expensive artifacts (whole-list shuffles,
committee partitions) are memoized in bounded module-level caches keyed
*structurally* (shuffle seed + digest of the active set), so they are shared
across the many states of one epoch — the same economy the reference gets
from types/src/cache.rs, without tying cache lifetime to one state object.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Sequence

import numpy as np

from grandine_tpu.consensus import misc
from grandine_tpu.consensus.misc import (
    committee_count_per_slot,
    compute_epoch_at_slot,
    compute_start_slot_at_epoch,
)
from grandine_tpu.types.preset import Preset
from grandine_tpu.types.primitives import (
    DOMAIN_BEACON_ATTESTER,
    DOMAIN_SYNC_COMMITTEE,
    GENESIS_EPOCH,
    PARTICIPATION_FLAG_WEIGHTS,
    TIMELY_HEAD_FLAG_INDEX,
    TIMELY_SOURCE_FLAG_INDEX,
    TIMELY_TARGET_FLAG_INDEX,
)


# One coarse lock for all accessor caches: they are hit concurrently from
# the controller's parallel validation tasks; get+move_to_end / put+evict
# are not atomic on their own.
_CACHE_LOCK = threading.Lock()


def _lru_put(cache: OrderedDict, key, value, cap: int) -> None:
    cache[key] = value
    cache.move_to_end(key)
    while len(cache) > cap:
        cache.popitem(last=False)


# --------------------------------------------------------- registry columns


class RegistryColumns:
    """Columnar numpy view of `state.validators` (one array per field)."""

    __slots__ = (
        "pubkeys",
        "withdrawal_credentials",
        "effective_balance",
        "slashed",
        "activation_eligibility_epoch",
        "activation_epoch",
        "exit_epoch",
        "withdrawable_epoch",
        "_active",
    )

    def __init__(self, validators) -> None:
        vs = list(validators)
        n = len(vs)
        self.pubkeys = tuple(bytes(v.pubkey) for v in vs)
        self.withdrawal_credentials = tuple(
            bytes(v.withdrawal_credentials) for v in vs
        )
        self.effective_balance = np.fromiter(
            (int(v.effective_balance) for v in vs), np.uint64, n
        )
        self.slashed = np.fromiter((bool(v.slashed) for v in vs), bool, n)
        self.activation_eligibility_epoch = np.fromiter(
            (int(v.activation_eligibility_epoch) for v in vs), np.uint64, n
        )
        self.activation_epoch = np.fromiter(
            (int(v.activation_epoch) for v in vs), np.uint64, n
        )
        self.exit_epoch = np.fromiter(
            (int(v.exit_epoch) for v in vs), np.uint64, n
        )
        self.withdrawable_epoch = np.fromiter(
            (int(v.withdrawable_epoch) for v in vs), np.uint64, n
        )
        # the active sets below are a function of these two: frozen, so
        # a memoised set cannot outlive a change to them
        self.activation_epoch.setflags(write=False)
        self.exit_epoch.setflags(write=False)
        #: epoch -> (active indices, their digest), the last few epochs
        self._active: "dict[int, tuple[np.ndarray, bytes]]" = {}

    def __len__(self) -> int:
        return len(self.pubkeys)

    def active_set(self, epoch: int) -> "tuple[np.ndarray, bytes]":
        """(active indices at `epoch`, read-only; their digest, the
        shuffle caches' key), computed once per epoch of this registry
        view: every vote of an epoch looks its committee up through them."""
        hit = self._active.get(epoch)
        if hit is not None:
            with _CACHE_LOCK:
                _ACTIVE_SET_COUNTS[1] += 1
            return hit
        e = np.uint64(epoch)
        active = np.nonzero(
            (self.activation_epoch <= e) & (e < self.exit_epoch)
        )[0].astype(np.int64)
        active.setflags(write=False)
        hit = (active, _active_digest(active))
        with _CACHE_LOCK:
            self._active[epoch] = hit
            while len(self._active) > _ACTIVE_EPOCHS:
                del self._active[next(iter(self._active))]
        return hit

    def active_indices(self, epoch: int) -> np.ndarray:
        return self.active_set(epoch)[0]


_COLUMNS_CACHE: OrderedDict = OrderedDict()  # id(items) -> (items, columns)


def registry_columns(state) -> RegistryColumns:
    """Columns for `state.validators`, cached by registry identity (states
    sharing an unmodified registry — the common case within an epoch —
    share one columnar view)."""
    items = state.validators.items
    key = id(items)
    with _CACHE_LOCK:
        hit = _COLUMNS_CACHE.get(key)
        if hit is not None and hit[0] is items:
            _COLUMNS_CACHE.move_to_end(key)
            return hit[1]
    cols = RegistryColumns(state.validators)
    with _CACHE_LOCK:
        _lru_put(_COLUMNS_CACHE, key, (items, cols), cap=8)
    return cols


#: epochs of active sets a registry view keeps (the previous, current and
#: next epoch, and one more)
_ACTIVE_EPOCHS = 4

#: [active-set digests computed, active sets found memoised], process-wide
_ACTIVE_SET_COUNTS = [0, 0]


def _active_digest(active: np.ndarray) -> bytes:
    with _CACHE_LOCK:
        _ACTIVE_SET_COUNTS[0] += 1
    return hashlib.blake2b(active.tobytes(), digest_size=16).digest()


def active_set_totals() -> "tuple[int, int]":
    """(active-set digests computed, active sets found memoised)
    process-wide: about one digest per (registry view, epoch), not one per
    committee lookup."""
    with _CACHE_LOCK:
        return _ACTIVE_SET_COUNTS[0], _ACTIVE_SET_COUNTS[1]


# ------------------------------------------------------------ shuffle caches

# (seed, active-digest, the preset's constants that shape the result) ->
# shuffled active indices / committee partition. Structurally keyed:
# reusable across every state that shares the seed and active set (all
# states of an epoch, across forks with a common mix); the preset is in
# the key because one process can hold states of two presets with the
# same mix and active set (the tests do), and a minimal-preset partition
# handed to a mainnet-preset state has too few committees.
_SHUFFLE_CACHE: OrderedDict = OrderedDict()
_PARTITION_CACHE: OrderedDict = OrderedDict()


def shuffled_active_indices(
    seed: bytes, active: np.ndarray, p: Preset,
    digest: "bytes | None" = None,
) -> np.ndarray:
    """`digest`: `active`'s, where the caller holds it
    (`RegistryColumns.active_set`)."""
    if digest is None:
        digest = _active_digest(active)
    key = (seed, digest, p.SHUFFLE_ROUND_COUNT)
    with _CACHE_LOCK:
        hit = _SHUFFLE_CACHE.get(key)
        if hit is not None:
            _SHUFFLE_CACHE.move_to_end(key)
            return hit
    from grandine_tpu.core.shuffling import shuffled_indices

    sigma = shuffled_indices(seed, len(active), p.SHUFFLE_ROUND_COUNT)
    hit = np.asarray(active)[sigma]
    with _CACHE_LOCK:
        _lru_put(_SHUFFLE_CACHE, key, hit, cap=16)
    return hit


def committee_partition(
    seed: bytes, active: np.ndarray, p: Preset,
    digest: "bytes | None" = None,
) -> "list[np.ndarray]":
    """All committees of the epoch with shuffle seed `seed`, flat-indexed
    k = (slot % SLOTS_PER_EPOCH) * committees_per_slot + committee_index.
    `digest` as for `shuffled_active_indices`."""
    if digest is None:
        digest = _active_digest(active)
    key = (seed, digest, p.SHUFFLE_ROUND_COUNT,
           p.SLOTS_PER_EPOCH, p.MAX_COMMITTEES_PER_SLOT,
           p.TARGET_COMMITTEE_SIZE)
    with _CACHE_LOCK:
        hit = _PARTITION_CACHE.get(key)
        if hit is not None:
            _PARTITION_CACHE.move_to_end(key)
            return hit
    shuffled = shuffled_active_indices(seed, active, p, digest)
    n = len(shuffled)
    count = committee_count_per_slot(n, p) * p.SLOTS_PER_EPOCH
    hit = [
        shuffled[n * k // count : n * (k + 1) // count] for k in range(count)
    ]
    with _CACHE_LOCK:
        _lru_put(_PARTITION_CACHE, key, hit, cap=16)
    return hit


# ------------------------------------------------------------ time & roots


def get_current_epoch(state, p: Preset) -> int:
    return compute_epoch_at_slot(int(state.slot), p)


def get_previous_epoch(state, p: Preset) -> int:
    cur = get_current_epoch(state, p)
    return GENESIS_EPOCH if cur == GENESIS_EPOCH else cur - 1


def get_block_root_at_slot(state, slot: int, p: Preset) -> bytes:
    if not slot < int(state.slot) <= slot + p.SLOTS_PER_HISTORICAL_ROOT:
        raise ValueError(f"slot {slot} outside historical root window")
    return bytes(state.block_roots[slot % p.SLOTS_PER_HISTORICAL_ROOT])


def get_block_root(state, epoch: int, p: Preset) -> bytes:
    return get_block_root_at_slot(state, compute_start_slot_at_epoch(epoch, p), p)


# ------------------------------------------------------------- active sets


def get_active_validator_indices(state, epoch: int) -> np.ndarray:
    return registry_columns(state).active_indices(epoch)


def get_total_balance(state, indices, p: Preset) -> int:
    cols = registry_columns(state)
    idx = np.asarray(list(indices), dtype=np.int64)
    total = int(cols.effective_balance[idx].sum()) if len(idx) else 0
    return max(p.EFFECTIVE_BALANCE_INCREMENT, total)


def get_total_active_balance(state, p: Preset) -> int:
    cols = registry_columns(state)
    active = cols.active_indices(get_current_epoch(state, p))
    total = int(cols.effective_balance[active].sum()) if len(active) else 0
    return max(p.EFFECTIVE_BALANCE_INCREMENT, total)


# -------------------------------------------------------------- committees


def get_committee_count_per_slot(state, epoch: int, p: Preset) -> int:
    return committee_count_per_slot(
        len(get_active_validator_indices(state, epoch)), p
    )


def _attester_partition(state, epoch: int, p: Preset) -> "list[np.ndarray]":
    seed = misc.get_seed(state, epoch, DOMAIN_BEACON_ATTESTER, p)
    active, digest = registry_columns(state).active_set(epoch)
    if len(active) == 0:
        raise ValueError(f"no active validators at epoch {epoch}")
    return committee_partition(seed, active, p, digest)


def get_beacon_committee(state, slot: int, index: int, p: Preset) -> np.ndarray:
    epoch = compute_epoch_at_slot(slot, p)
    partition = _attester_partition(state, epoch, p)
    per_slot = len(partition) // p.SLOTS_PER_EPOCH
    if index >= per_slot:
        raise ValueError(f"committee index {index} >= {per_slot}")
    return partition[(slot % p.SLOTS_PER_EPOCH) * per_slot + index]


def get_beacon_proposer_index(state, p: Preset) -> int:
    slot = int(state.slot)
    epoch = compute_epoch_at_slot(slot, p)
    seed = misc.proposer_seed(state, slot, p)
    cols = registry_columns(state)
    active = cols.active_indices(epoch)
    return misc.compute_proposer_index(cols.effective_balance, active, seed, p)


# ------------------------------------------------------------ attestations


def get_attesting_indices(state, data, aggregation_bits, p: Preset) -> np.ndarray:
    committee = get_beacon_committee(state, int(data.slot), int(data.index), p)
    bits = np.asarray(aggregation_bits.array, dtype=bool)
    if len(bits) != len(committee):
        raise ValueError(
            f"aggregation bits {len(bits)} != committee size {len(committee)}"
        )
    return committee[bits]


def get_indexed_attestation(state, attestation, types_ns, p: Preset):
    """Spec `get_indexed_attestation` → an IndexedAttestation container from
    `types_ns` (the fork namespace of `spec_types`)."""
    indices = get_attesting_indices(
        state, attestation.data, attestation.aggregation_bits, p
    )
    return types_ns.IndexedAttestation(
        attesting_indices=sorted(int(i) for i in indices),
        data=attestation.data,
        signature=bytes(attestation.signature),
    )


# ----------------------------------------------------------- altair rewards


def get_base_reward_per_increment(state, p: Preset) -> int:
    return (
        p.EFFECTIVE_BALANCE_INCREMENT
        * p.BASE_REWARD_FACTOR
        // misc.integer_squareroot(get_total_active_balance(state, p))
    )


def get_base_reward(state, index: int, p: Preset) -> int:
    """Altair per-validator base reward (increments × per-increment)."""
    cols = registry_columns(state)
    increments = int(cols.effective_balance[index]) // p.EFFECTIVE_BALANCE_INCREMENT
    return increments * get_base_reward_per_increment(state, p)


def has_flag(flags: int, flag_index: int) -> bool:
    return bool((int(flags) >> flag_index) & 1)


def add_flag(flags: int, flag_index: int) -> int:
    return int(flags) | (1 << flag_index)


def get_unslashed_participating_mask(
    state, flag_index: int, epoch: int, p: Preset
) -> np.ndarray:
    """Boolean registry mask of unslashed validators active at `epoch` with
    `flag_index` set in that epoch's participation column (vectorized twin
    of spec `get_unslashed_participating_indices`)."""
    cur = get_current_epoch(state, p)
    if epoch not in (cur, get_previous_epoch(state, p)):
        raise ValueError("participation is only tracked for current/previous")
    col = (
        state.current_epoch_participation
        if epoch == cur
        else state.previous_epoch_participation
    )
    flags = np.asarray(col.array, dtype=np.uint8)
    cols = registry_columns(state)
    active = np.zeros(len(cols), dtype=bool)
    active[cols.active_indices(epoch)] = True
    flag_bit = (flags >> flag_index) & 1
    return active & (flag_bit == 1) & ~cols.slashed


def get_attestation_participation_flag_indices(
    state, data, inclusion_delay: int, cfg, phase
) -> "list[int]":
    """Altair+ `get_attestation_participation_flag_indices`. Raises on a
    non-matching source (structural invalidity)."""
    from grandine_tpu.types.primitives import Phase

    p = cfg.preset
    cur = get_current_epoch(state, p)
    if int(data.target.epoch) == cur:
        justified = state.current_justified_checkpoint
    else:
        justified = state.previous_justified_checkpoint
    matching_source = data.source == justified
    if not matching_source:
        raise ValueError("attestation source does not match justified checkpoint")
    matching_target = (
        bytes(data.target.root) == get_block_root(state, int(data.target.epoch), p)
    )
    matching_head = matching_target and (
        bytes(data.beacon_block_root)
        == get_block_root_at_slot(state, int(data.slot), p)
    )
    flags = []
    if inclusion_delay <= misc.integer_squareroot(p.SLOTS_PER_EPOCH):
        flags.append(TIMELY_SOURCE_FLAG_INDEX)
    if matching_target and (
        phase >= Phase.DENEB or inclusion_delay <= p.SLOTS_PER_EPOCH
    ):
        # EIP-7045 (deneb) drops the target inclusion-delay cap
        flags.append(TIMELY_TARGET_FLAG_INDEX)
    if matching_head and inclusion_delay == p.MIN_ATTESTATION_INCLUSION_DELAY:
        flags.append(TIMELY_HEAD_FLAG_INDEX)
    return flags


# ----------------------------------------------------------- sync committee


def get_next_sync_committee_indices(state, cfg) -> "list[int]":
    """Altair `get_next_sync_committee_indices`: effective-balance-weighted
    rejection sampling, SYNC_COMMITTEE_SIZE picks (with replacement)."""
    p = cfg.preset
    epoch = get_current_epoch(state, p) + 1
    cols = registry_columns(state)
    active, digest = cols.active_set(epoch)
    n = len(active)
    if n == 0:
        raise ValueError("no active validators for sync committee")
    seed = misc.get_seed(state, epoch, DOMAIN_SYNC_COMMITTEE, p)
    shuffled = shuffled_active_indices(seed, active, p, digest)
    max_eb = p.MAX_EFFECTIVE_BALANCE
    out: "list[int]" = []
    i = 0
    hash_cache: dict = {}
    while len(out) < p.SYNC_COMMITTEE_SIZE:
        candidate = int(shuffled[i % n])
        block = i // 32
        rand = hash_cache.get(block)
        if rand is None:
            rand = misc.sha256(seed + misc.uint_to_bytes(block))
            hash_cache[block] = rand
        if int(cols.effective_balance[candidate]) * 0xFF >= max_eb * rand[i % 32]:
            out.append(candidate)
        i += 1
    return out


def get_next_sync_committee(state, types_ns, cfg):
    """Build the altair `SyncCommittee` container (pubkeys + aggregate)."""
    from grandine_tpu.consensus.keys import aggregate_pubkey_bytes

    indices = get_next_sync_committee_indices(state, cfg)
    cols = registry_columns(state)
    pubkeys = [cols.pubkeys[i] for i in indices]
    return types_ns.SyncCommittee(
        pubkeys=pubkeys,
        aggregate_pubkey=aggregate_pubkey_bytes(pubkeys),
    )


__all__ = [
    "RegistryColumns",
    "registry_columns",
    "active_set_totals",
    "shuffled_active_indices",
    "committee_partition",
    "get_current_epoch",
    "get_previous_epoch",
    "get_block_root_at_slot",
    "get_block_root",
    "get_active_validator_indices",
    "get_total_balance",
    "get_total_active_balance",
    "get_committee_count_per_slot",
    "get_beacon_committee",
    "get_beacon_proposer_index",
    "get_attesting_indices",
    "get_indexed_attestation",
    "get_base_reward_per_increment",
    "get_base_reward",
    "has_flag",
    "add_flag",
    "get_unslashed_participating_mask",
    "get_attestation_participation_flag_indices",
    "get_next_sync_committee_indices",
    "get_next_sync_committee",
]
