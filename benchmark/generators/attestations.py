"""The one general generator of attestation traffic. A traffic mix is a
JSON file of parameters under benchmark/traffic/; this module reads it and
makes, from the seed, the items of successive slots:

  members "single"     every member of every committee of the slot votes
                       alone (one aggregation bit): the subnet stream
  members "aggregate"  `aggregators_per_committee` aggregates per
                       committee, each missing 0..`missing_members_max` of
                       its members (seed-drawn): the aggregate-and-proof
                       stream

Every seed gives the same sizes (items per slot, members per item up to
which member is missing) in another order. Items are plain data: the
driver turns them into the program's wire types. The committee, the
signing root and the signature of each item are computed here from the
specification (generators/spec.py, generators/keys.py), so the plain
reference can judge an item without asking the program anything.
"""

from __future__ import annotations

import functools
import random
from dataclasses import dataclass, replace

from benchmark.generators import spec
from benchmark.generators.keys import MessageSigner, ProgressionKeys
from benchmark.reference import bls as ref
from benchmark.reference.constants import DST_SIGNATURE, R
from benchmark.reference.hash_to_curve import (
    hash_to_field_fq2,
    hash_to_g2,
    map_to_curve_g2,
)


@dataclass(frozen=True)
class ChainIdentity:
    """What a gossip peer knows of the chain it votes on."""

    genesis_validators_root: bytes
    fork_version: bytes
    anchor_root: bytes
    randao_mix: bytes


@dataclass
class Item:
    slot: int
    index: int
    bits: "list[bool]"
    members: "list[int]"
    message: bytes
    signature: bytes
    source: "tuple[int, bytes]"
    target: "tuple[int, bytes]"
    beacon_block_root: bytes


class AttestationTraffic:
    def __init__(self, params: dict, shapes: dict, keys: ProgressionKeys,
                 chain: ChainIdentity, seed: int) -> None:
        self.params, self.shapes = params, shapes
        self.keys, self.chain, self.seed = keys, chain, seed
        self.committees = spec.Committees(
            keys.n, 0, chain.randao_mix, shapes
        )
        self.domain = spec.compute_domain(
            spec.DOMAIN_BEACON_ATTESTER, chain.fork_version,
            chain.genesis_validators_root,
        )

    def slot_items(self, slot: int) -> "list[Item]":
        """All items of `slot` (epoch 0, voting for the anchor block)."""
        p = self.params
        rng = random.Random(f"traffic|{self.seed}|{slot}")
        source = (0, spec.ZERO32)
        target = (0, self.chain.anchor_root)
        out = []
        for index in range(self.committees.per_slot):
            committee = self.committees.committee(slot, index)
            data_root = spec.attestation_data_root(
                slot, index, self.chain.anchor_root, source, target
            )
            message = spec.signing_root(data_root, self.domain)
            signer = MessageSigner(self.keys, message)

            def item(bits, point):
                members = [v for v, b in zip(committee, bits) if b]
                return Item(slot, index, bits, members, message,
                            ref.g2_to_bytes(point), source, target,
                            self.chain.anchor_root)

            if p["members"] == "single":
                for pos, v in enumerate(committee):
                    bits = [False] * len(committee)
                    bits[pos] = True
                    out.append(item(bits, signer.single(v)))
            elif p["members"] == "aggregate":
                full = signer.aggregate(committee)
                for _ in range(int(p["aggregators_per_committee"])):
                    missing = rng.sample(
                        range(len(committee)),
                        rng.randint(0, int(p["missing_members_max"])),
                    )
                    bits = [True] * len(committee)
                    point = full
                    for pos in missing:
                        bits[pos] = False
                        point = point - signer.single(committee[pos])
                    out.append(item(bits, point))
            else:
                raise ValueError(f"unknown members kind {p['members']!r}")
        rng.shuffle(out)  # arrival order within the slot
        return out

    # -- items no sound verifier accepts (the reference rejects each) -----

    @staticmethod
    def forged_pair(items: "list[Item]", rng) -> "tuple[list, int, int]":
        """Copy of `items` in which two seed-drawn items are forged so that
        the forgeries cancel in an unweighted sum: sig_i + D, sig_j - D.
        Each is invalid alone; a batch check whose randomizers are all
        equal accepts the pair."""
        i, j = rng.sample(range(len(items)), 2)
        delta = hash_to_g2(b"forged-delta")
        out = list(items)
        for pos, sign in ((i, 1), (j, -1)):
            point = ref.g2_from_bytes(items[pos].signature)
            point = point + delta if sign > 0 else point - delta
            out[pos] = replace(items[pos], signature=ref.g2_to_bytes(point))
        return out, i, j

    @staticmethod
    def off_subgroup(item: Item) -> Item:
        """`item` with T added to its signature, T on the curve and of an
        order that divides G2's cofactor: a point of the curve that is not
        in G2. (The pairing is not bilinear outside G2, so the equation
        fails for it as well: the probe shows that such a signature is
        refused, not which of the two refuses it.)"""
        u = hash_to_field_fq2(b"off-subgroup", DST_SIGNATURE, 1)[0]
        torsion = map_to_curve_g2(u).mul(R)
        assert not torsion.is_infinity()
        point = ref.g2_from_bytes(item.signature) + torsion
        return replace(item, signature=ref.g2_to_bytes(point))

    @staticmethod
    def malformed(item: Item) -> Item:
        """`item` with its signature's x moved to the next value that is
        no point of the curve: a well-formed encoding that no one can
        decompress."""
        head, tail = item.signature[:88], int.from_bytes(
            item.signature[88:], "big")
        while True:
            tail = (tail + 1) % 2**64
            data = head + tail.to_bytes(8, "big")
            try:
                ref.g2_from_bytes(data, subgroup_check=False)
            except ref.BlsError:
                return replace(item, signature=data)


def reference_verdict(keys: ProgressionKeys, item: Item) -> bool:
    """The plain reference's answer for one item: decompress and
    subgroup-check the signature, add up the members' public keys, two
    pairings (benchmark/reference: pure Python, no program code)."""
    try:
        sig = ref.Signature.from_bytes(item.signature)
    except ref.BlsError:
        return False
    if not item.members:
        return False
    members = [ref.PublicKey(keys.points[v]) for v in item.members]
    return bool(sig.fast_aggregate_verify(item.message, members))


# -- what the driver's worker processes run (plain Python, no JAX) --------

@functools.lru_cache(maxsize=2)
def _traffic(params: str, shapes: str, n: int, seed: int,
             chain: ChainIdentity) -> AttestationTraffic:
    import json

    return AttestationTraffic(json.loads(params), json.loads(shapes),
                              ProgressionKeys(n, seed), chain, seed)


def make_slot(params: str, shapes: str, n: int, seed: int,
              chain: ChainIdentity, slot: int) -> "list[Item]":
    """`slot_items(slot)` of the traffic these arguments describe (the
    two dictionaries as JSON text, so that the generator is kept from one
    call of a worker to the next)."""
    return _traffic(params, shapes, n, seed, chain).slot_items(slot)


@functools.lru_cache(maxsize=2)
def _keys(n: int, seed: int) -> ProgressionKeys:
    return ProgressionKeys(n, seed)


def judge(n: int, seed: int, items: "list[Item]") -> "list[bool]":
    """The plain reference's verdict on each item."""
    keys = _keys(n, seed)
    return [reference_verdict(keys, it) for it in items]
