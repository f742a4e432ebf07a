"""Mainnet-shaped chain synthesis (BASELINE configs 3 & 4): the generators.

What a replay cell needs to make its chain (PERF.md Open question 1), at
the reference harness's operating point (ad_hoc_bench/src/main.rs:27-148:
50k validators, mainnet preset, full committees, one aggregate per
committee, full sync aggregate), SYNTHESIZED because no cached real-chain
data ships offline. Nothing here writes BENCH_CONFIG3.json /
BENCH_CONFIG4.json: those records of this operating point stay as they are.

Synthesis trick: validator i's secret key is the arithmetic progression
sk_i = (A + B·i) mod r, so
  - the 50k pubkeys cost one host G1 ADD each (pk_{i+1} = pk_i + [B]G);
  - a full-committee aggregate signature is [Σ_{i∈C} sk_i]·H(m) — the
    scalar is a closed-form integer sum, ONE G2 scalar-mul per aggregate
    (device batch_sign when available, host anchor otherwise).
The verified workload is identical to real traffic: every aggregate is a
distinct valid signature set over real committee pubkeys.
"""

import time

import numpy as np

# ------------------------------------------------------------ AP key plane


class ApKeys:
    """Arithmetic-progression validator keys with closed-form aggregate
    scalars."""

    A0 = 0x1357_0000_DEAD_BEEF_1234_5678_9ABC_DEF0
    B0 = 0x2468_ACE0_2468_ACE0_2468_ACE1

    def __init__(self, n: int) -> None:
        from grandine_tpu.crypto.constants import R

        self.n = n
        self.R = R

    def sk_int(self, i: int) -> int:
        return (self.A0 + self.B0 * int(i)) % self.R

    def secret_key(self, i: int):
        from grandine_tpu.crypto import bls as A

        return A.SecretKey(self.sk_int(i))

    def sum_scalar(self, indices) -> int:
        """Σ sk_i over a committee, mod r — closed form."""
        idx = np.asarray(indices, dtype=object)
        return int(
            (self.A0 * len(idx) + self.B0 * int(sum(int(v) for v in idx)))
            % self.R
        )

    def pubkeys(self) -> "list[bytes]":
        """All n compressed pubkeys via one host G1 add per key."""
        from grandine_tpu.crypto.bls import PublicKey
        from grandine_tpu.crypto.curves import G1

        out = []
        acc = G1.mul(self.A0)
        step = G1.mul(self.B0)
        for _ in range(self.n):
            out.append(PublicKey(acc).to_bytes())
            acc = acc + step
        return out


class FastSigner:
    """Signs (message, scalar) pairs: one device batch (batch_sign) when a
    TPU backend is usable, else host anchor scalar-muls."""

    def __init__(self, use_device: bool) -> None:
        self.backend = None
        if use_device:
            from grandine_tpu.tpu.bls import TpuBlsBackend

            self.backend = TpuBlsBackend()

    def sign_batch(self, messages, scalars) -> "list[bytes]":
        from grandine_tpu.crypto import bls as A

        sks = [A.SecretKey(s) for s in scalars]
        if self.backend is not None and len(messages) > 1:
            sigs = self.backend.batch_sign(list(messages), sks)
            return [s.to_bytes() for s in sigs]
        return [
            sk.sign(bytes(m)).to_bytes() for m, sk in zip(messages, sks)
        ]


# --------------------------------------------------------- chain synthesis


def build_config(n: int):
    import dataclasses

    from grandine_tpu.types.config import Config

    cfg = Config()  # mainnet preset
    return dataclasses.replace(
        cfg, altair_fork_epoch=0, bellatrix_fork_epoch=0,
        capella_fork_epoch=0, deneb_fork_epoch=0,
    )


def build_genesis(n: int, cfg, ap: ApKeys):
    from grandine_tpu.transition.genesis import interop_genesis_state

    t0 = time.time()
    pubkeys = ap.pubkeys()
    state = interop_genesis_state(n, cfg, pubkeys=pubkeys)
    print(f"genesis ({n} AP validators): {time.time()-t0:.1f}s", flush=True)
    return state


def fast_attestations(state, cfg, ap: ApKeys, signer: FastSigner, slot: int):
    """One full-committee aggregate per committee of `slot` — signatures
    via closed-form scalars, one batch_sign call for the whole slot."""
    from grandine_tpu.consensus import accessors, misc, signing
    from grandine_tpu.transition.fork_upgrade import state_phase
    from grandine_tpu.types.containers import spec_types

    p = cfg.preset
    epoch = misc.compute_epoch_at_slot(slot, p)
    ns = getattr(spec_types(p), state_phase(state, cfg).key)
    if slot == int(state.slot):
        header = state.latest_block_header
        if bytes(header.state_root) == b"\x00" * 32:
            header = header.replace(state_root=state.hash_tree_root())
        head_root = header.hash_tree_root()
    else:
        head_root = accessors.get_block_root_at_slot(state, slot, p)
    target_slot = misc.compute_start_slot_at_epoch(epoch, p)
    target_root = (
        head_root
        if target_slot == slot
        else accessors.get_block_root_at_slot(state, target_slot, p)
    )
    cur = accessors.get_current_epoch(state, p)
    source = (
        state.current_justified_checkpoint
        if epoch == cur
        else state.previous_justified_checkpoint
    )
    count = accessors.get_committee_count_per_slot(state, epoch, p)
    datas, roots, scalars, committees = [], [], [], []
    for index in range(count):
        committee = accessors.get_beacon_committee(state, slot, index, p)
        data = ns.AttestationData(
            slot=slot, index=index, beacon_block_root=head_root,
            source=source,
            target=ns.Checkpoint(epoch=epoch, root=target_root),
        )
        datas.append(data)
        roots.append(signing.attestation_signing_root(state, data, cfg))
        scalars.append(ap.sum_scalar([int(v) for v in committee]))
        committees.append(committee)
    sigs = signer.sign_batch(roots, scalars)
    out = []
    for data, committee, sig in zip(datas, committees, sigs):
        out.append(
            ns.Attestation(
                aggregation_bits=np.ones(len(committee), dtype=bool),
                data=data,
                signature=sig,
            )
        )
    return out


def fast_sync_aggregate(state, cfg, ap: ApKeys, signer: FastSigner):
    """Full-participation sync aggregate, one scalar-mul."""
    from grandine_tpu.consensus import accessors, signing
    from grandine_tpu.transition.fork_upgrade import state_phase
    from grandine_tpu.types.containers import spec_types

    p = cfg.preset
    ns = getattr(spec_types(p), state_phase(state, cfg).key)
    cols = accessors.registry_columns(state)
    by_pk = {bytes(cols.pubkeys[i]): i for i in range(len(cols))}
    indices = [by_pk[bytes(pk)] for pk in state.current_sync_committee.pubkeys]
    root = signing.sync_aggregate_signing_root(state, cfg)
    (sig,) = (
        signer.sign_batch([root], [ap.sum_scalar(indices)])
    )
    return ns.SyncAggregate(
        sync_committee_bits=np.ones(p.SYNC_COMMITTEE_SIZE, dtype=bool),
        sync_committee_signature=sig,
    )


def synthesize_chain(state, cfg, ap, signer, n_slots: int):
    """`n_slots` full-committee blocks on top of genesis. Returns
    (blocks, signature_sets_per_block)."""
    from grandine_tpu.validator.duties import produce_block

    blocks, set_counts = [], []
    prev_atts = []
    from grandine_tpu.transition.slots import process_slots

    for slot in range(1, n_slots + 1):
        t0 = time.time()
        if int(state.slot) < slot:
            state = process_slots(state, slot, cfg)
        # the sync aggregate signs against the slot-advanced state (the
        # same state produce_block builds the body on)
        sync_agg = fast_sync_aggregate(state, cfg, ap, signer)
        blk, post = produce_block(
            state,
            slot,
            cfg,
            keys=ap.secret_key,
            attestations=prev_atts,
            sync_aggregate=sync_agg,
            full_sync_participation=False,
        )
        # sets the verifier will check: proposer + randao + sync aggregate
        # + one aggregate per packed attestation
        set_counts.append(3 + len(prev_atts))
        blocks.append(blk)
        prev_atts = fast_attestations(post, cfg, ap, signer, slot)
        state = post
        print(
            f"  synth slot {slot}: {len(blocks[-1].message.body.attestations)}"
            f" atts in block, {time.time()-t0:.1f}s",
            flush=True,
        )
    return blocks, set_counts
