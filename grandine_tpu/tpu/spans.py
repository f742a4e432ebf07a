"""Device span-update kernel for the slasher's bulk-replay feed.

The slasher's chunked min/max target spans (slasher.py) take one range
update per attesting validator. The gossip path batches an aggregate's
updates with numpy; the bulk-replay feed is wider — thousands of
attesting indices per window, each with its own (source, target) — and
that merge is a pure elementwise min/max over a (validators × epochs)
grid, exactly the shape the accelerator wants.

`SpanPlane.update` merges one EPOCH-GRID window: for each row v with
attestation (s_v, t_v),

  new_min[v][e] = min(old_min[v][e], t_v if e < s_v else UNSET)
  new_max[v][e] = max(old_max[v][e], t_v if s_v < e <= t_v else 0)

over the fixed grid [base, base + SPAN_GRID_EPOCHS). The grid is the
chunk-aligned window covering the batch's source/target range; epochs
below the grid (the long min-span tail toward `history_epochs`) stay on
the host where per-chunk early exit prunes almost all of the work
(slasher._walk_min_below). Rows are padded to a pow-2 bucket and epochs
are fixed at SPAN_GRID_EPOCHS, so the kernel holds exactly one compiled
shape per row bucket — registered through `_jitted_global` under the
shape-contract machinery (tools/shapes) and pre-warmed from the
manifest's `span_update` rows like any other contract.

Epochs ride as int32 on device (jax x64 is off): the min-side UNSET
sentinel maps uint64 0xFFFF..FF ↔ INT32_UNSET at the host boundary, and
the caller falls back to the host merge for targets ≥ 2^31 (no real
chain gets there).
"""

from __future__ import annotations

import numpy as np

#: epochs per device grid — four span chunks (slasher.CHUNK_EPOCHS × 4),
#: wide enough for any gossip-fresh window (sources and targets within a
#: few epochs of head); wider historical mixes fall back to the host walk
SPAN_GRID_EPOCHS = 64

#: int32 stand-in for the slasher's uint64 UNSET min sentinel
INT32_UNSET = np.int32(0x7FFF_FFFF)


def _span_grid_compute(min_block, max_block, src, tgt, valid, base):
    """The jitted body: elementwise grid merge (shapes fixed by bucket)."""
    import jax.numpy as jnp

    e = base[0] + jnp.arange(SPAN_GRID_EPOCHS, dtype=jnp.int32)[None, :]
    src_c = src[:, None]
    tgt_c = tgt[:, None]
    v = valid[:, None]
    new_min = jnp.minimum(
        min_block, jnp.where(v & (e < src_c), tgt_c, INT32_UNSET)
    )
    new_max = jnp.maximum(
        max_block,
        jnp.where(v & (e > src_c) & (e <= tgt_c), tgt_c, jnp.int32(0)),
    )
    return new_min, new_max


class SpanPlane:
    """Host façade for the span-update grid kernel.

    One instance per slasher; stateless apart from observability seams,
    so a single verify-pool thread owns each call (the slasher serializes
    its mutating calls behind the firehose's _slasher_lock)."""

    def __init__(self, metrics=None) -> None:
        self.metrics = metrics

    def _count_kernel(self, kernel: str) -> None:
        if self.metrics is not None:
            self.metrics.device_kernel_calls.labels(kernel).inc()

    def _run_kernel(self, kernel: str, fn, args: tuple):
        """Dispatch with shape-ledger accounting (tpu/bls.py): a novel
        signature after warmup seal counts as a steady-state recompile,
        the same zero-recompile contract the verify kernels live under."""
        from grandine_tpu.tpu import bls as B

        self._count_kernel(kernel)
        with B.dispatch_scope(kernel, args, self.metrics), \
                B._node_profiler().annotate(kernel, len(args[0])):
            out = fn(*args)
        for leaf in out:
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
        return out

    def update(self, min_block, max_block, src, tgt, base_epoch: int):
        """Merge one grid window on the device.

        `min_block`/`max_block`: (n, SPAN_GRID_EPOCHS) int32 current
        values (min side already sentinel-mapped to INT32_UNSET);
        `src`/`tgt`: (n,) int32 per-row attestation epochs; `base_epoch`:
        the grid's first epoch. Returns (new_min, new_max) as (n, E)
        int32 numpy arrays."""
        from grandine_tpu.tpu import bls as B

        n = int(min_block.shape[0])
        vb = B._bucket(n, lo=256)
        mn = np.full((vb, SPAN_GRID_EPOCHS), INT32_UNSET, np.int32)
        mx = np.zeros((vb, SPAN_GRID_EPOCHS), np.int32)
        sr = np.zeros((vb,), np.int32)
        tg = np.zeros((vb,), np.int32)
        va = np.zeros((vb,), bool)
        base = np.full((1,), int(base_epoch), np.int32)
        mn[:n] = min_block
        mx[:n] = max_block
        sr[:n] = src
        tg[:n] = tgt
        va[:n] = True
        fn = B._jitted_global("span_update_grid", _span_grid_compute)
        out_min, out_max = self._run_kernel(
            "span_update_grid", fn, (mn, mx, sr, tg, va, base)
        )
        return (
            np.asarray(out_min)[:n],
            np.asarray(out_max)[:n],
        )


def grid_merge_host(min_block, max_block, src, tgt, base_epoch: int):
    """Numpy mirror of `_span_grid_compute` — the fallback engine when no
    SpanPlane is wired (and the differential oracle for the kernel)."""
    e = np.int64(base_epoch) + np.arange(SPAN_GRID_EPOCHS, dtype=np.int64)
    e = e[None, :]
    src_c = np.asarray(src, np.int64)[:, None]
    tgt_c = np.asarray(tgt, np.int64)[:, None]
    new_min = np.minimum(
        np.asarray(min_block, np.int64),
        np.where(e < src_c, tgt_c, np.int64(INT32_UNSET)),
    )
    new_max = np.maximum(
        np.asarray(max_block, np.int64),
        np.where((e > src_c) & (e <= tgt_c), tgt_c, 0),
    )
    return new_min.astype(np.int32), new_max.astype(np.int32)


__all__ = [
    "SPAN_GRID_EPOCHS",
    "INT32_UNSET",
    "SpanPlane",
    "grid_merge_host",
]
