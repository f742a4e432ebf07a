"""The consensus-spec pieces the traffic generators and the plain reference
need, written from the specification (phase0 beacon-chain.md) with hashlib
and numpy alone: committee assignment (swap-or-not shuffle over the whole
active set), the attestation signing root (SSZ merkleization of the fixed
containers AttestationData, Checkpoint, ForkData, SigningData).

Nothing here imports the program. A fault in the program's own shuffle or
signing root therefore shows as rejected valid items, not as traffic that
agrees with the fault.

What identifies the chain is taken from the node as a gossip peer would
learn it (Status / fork digest): genesis_validators_root, the current fork
version and the anchor block root. Everything else is computed here.
"""

from __future__ import annotations

import hashlib

import numpy as np

DOMAIN_BEACON_ATTESTER = bytes.fromhex("01000000")
ZERO32 = b"\x00" * 32


def sha256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def u64(value: int) -> bytes:
    return int(value).to_bytes(8, "little")


def chunk_u64(value: int) -> bytes:
    return u64(value) + b"\x00" * 24


def merkleize(chunks: "list[bytes]") -> bytes:
    """Root of `chunks` padded with zero chunks to the next power of two."""
    width = 1
    while width < len(chunks):
        width *= 2
    layer = list(chunks) + [ZERO32] * (width - len(chunks))
    while len(layer) > 1:
        layer = [sha256(layer[i] + layer[i + 1])
                 for i in range(0, len(layer), 2)]
    return layer[0]


def checkpoint_root(epoch: int, root: bytes) -> bytes:
    return merkleize([chunk_u64(epoch), bytes(root)])


def attestation_data_root(slot: int, index: int, beacon_block_root: bytes,
                          source: "tuple[int, bytes]",
                          target: "tuple[int, bytes]") -> bytes:
    return merkleize([
        chunk_u64(slot), chunk_u64(index), bytes(beacon_block_root),
        checkpoint_root(*source), checkpoint_root(*target),
    ])


def compute_domain(domain_type: bytes, fork_version: bytes,
                   genesis_validators_root: bytes) -> bytes:
    fork_data_root = merkleize([
        bytes(fork_version) + b"\x00" * 28, bytes(genesis_validators_root),
    ])
    return domain_type + fork_data_root[:28]


def signing_root(object_root: bytes, domain: bytes) -> bytes:
    return merkleize([object_root, domain])


def shuffled_positions(seed: bytes, count: int, rounds: int) -> np.ndarray:
    """compute_shuffled_index(i, count, seed) for every i < count at once:
    each round is the spec's formula over the whole index vector."""
    index = np.arange(count, dtype=np.int64)
    blocks = (count + 255) // 256
    for rnd in range(rounds):
        rb = bytes([rnd])
        pivot = int.from_bytes(sha256(seed + rb)[:8], "little") % count
        flip = (pivot + count - index) % count
        position = np.maximum(index, flip)
        source = np.frombuffer(b"".join(
            sha256(seed + rb + int(b).to_bytes(4, "little"))
            for b in range(blocks)
        ), dtype=np.uint8)
        byte = source[(position // 256) * 32 + (position % 256) // 8]
        bit = (byte >> (position % 8).astype(np.uint8)) & 1
        index = np.where(bit == 1, flip, index)
    return index


class Committees:
    """Every beacon committee of one epoch of a validator set that is all
    active (a genesis set), from the epoch's seed."""

    def __init__(self, n_validators: int, epoch: int, randao_mix: bytes,
                 shapes: dict) -> None:
        self.slots_per_epoch = int(shapes["SLOTS_PER_EPOCH"])
        self.per_slot = max(1, min(
            int(shapes["MAX_COMMITTEES_PER_SLOT"]),
            n_validators // self.slots_per_epoch
            // int(shapes["TARGET_COMMITTEE_SIZE"]),
        ))
        seed = sha256(DOMAIN_BEACON_ATTESTER + u64(epoch) + bytes(randao_mix))
        # committee k holds active[shuffled(i)] for i in its slice; the
        # active set of a genesis registry is 0..n-1
        self._sigma = shuffled_positions(
            seed, n_validators, int(shapes["SHUFFLE_ROUND_COUNT"])
        )
        self._n = n_validators

    def committee(self, slot: int, index: int) -> "list[int]":
        total = self.per_slot * self.slots_per_epoch
        k = (slot % self.slots_per_epoch) * self.per_slot + index
        lo, hi = self._n * k // total, self._n * (k + 1) // total
        return [int(v) for v in self._sigma[lo:hi]]
