"""Batch-signing benchmark (BASELINE config 5: 50k concurrent attestation
signings as one device batch — signer/src/signer.rs:173-229's rayon fan-out
mapped onto the accelerator's batch axis).

Usage: [BENCH_N=16384] python tools/bench_sign.py
Prints one JSON line like bench.py.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

# blst single-core G2 sign ≈ 0.3 ms -> ~3300 sigs/s (sizing anchor)
BLST_SIGN_PER_SEC = 3300.0


def main() -> None:
    n = int(os.environ.get("BENCH_N", "16384"))
    import jax

    import bench
    from grandine_tpu.crypto.hash_to_curve import hash_to_g2
    from grandine_tpu.tpu import curve as C
    from grandine_tpu.tpu.bls import batch_sign_kernel, sign_bits_host

    bench._enable_compilation_cache()

    t0 = time.time()
    msgs = [b"sign-bench-%d" % (i % 64) for i in range(64)]
    mx, my, _ = C.g2_points_to_dev([hash_to_g2(m) for m in msgs])
    msg_x = np.ascontiguousarray(mx[np.arange(n) % 64])
    msg_y = np.ascontiguousarray(my[np.arange(n) % 64])
    msg_inf = np.zeros(n, bool)
    # fresh scalars per iteration + full result materialization, as a
    # signer sees them
    def fresh_bits(v: int):
        sks = [
            (0x1111 + v * 0x9E37 + 0x2468ACE * i) % (1 << 200) + 5
            for i in range(n)
        ]
        return sign_bits_host(sks, n)

    prep_s = time.time() - t0

    fn = jax.jit(batch_sign_kernel)
    t0 = time.time()
    out = fn(msg_x, msg_y, msg_inf, *fresh_bits(0))
    np.asarray(out[0])
    compile_s = time.time() - t0

    t0 = time.time()
    iters = 0
    while True:
        out = fn(msg_x, msg_y, msg_inf, *fresh_bits(iters + 1))
        np.asarray(out[0])
        iters += 1
        if time.time() - t0 > 15 or iters >= 5:
            break
    elapsed = time.time() - t0
    sigs_per_sec = n * iters / elapsed
    print(json.dumps({
        "metric": "bls_batch_sign_throughput",
        "value": round(sigs_per_sec, 1),
        "unit": "sigs/s",
        "vs_baseline": round(sigs_per_sec / BLST_SIGN_PER_SEC, 3),
    }))
    print(
        f"# n={n} iters={iters} elapsed={elapsed:.2f}s prep={prep_s:.1f}s "
        f"compile={compile_s:.1f}s platform={jax.devices()[0].platform}",
        file=sys.stderr,
    )


if __name__ == "__main__":
    main()
