"""Stage-level timing of the multi_verify kernel on the current device.

Times each pipeline stage separately (jit'd in isolation) through the
node profiler's shared `time_jit` primitive (grandine_tpu.runtime
.profiler) — every measurement ends in a host fetch of the result,
which waits for the device. Stages: scalar_mul G1 (rlc), scalar_mul G2, G2 rlc+sum
tree, miller_loop, miller+tree+final_exp, and the fused
multi_verify_kernel.

Usage: [BENCH_N=2048] python tools/profile_kernels.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    n = int(os.environ.get("BENCH_N", "2048"))
    import jax
    import jax.numpy as jnp

    import bench
    from grandine_tpu.tpu import curve as C
    from grandine_tpu.tpu import field as F
    from grandine_tpu.tpu import limbs as L
    from grandine_tpu.tpu import pairing as TP
    from grandine_tpu.tpu.bls import multi_verify_kernel

    bench._enable_compilation_cache()

    print(f"platform={jax.devices()[0].platform} n={n}", file=sys.stderr)
    t0 = time.time()
    args = bench.build_batch(n)
    (pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
     msg_x, msg_y, msg_inf, r_bits) = args
    print(f"prep {time.time() - t0:.1f}s", file=sys.stderr)

    from grandine_tpu.runtime.profiler import time_jit as timed

    def g1_rlc(pk_x, pk_y, pk_inf, r_bits):
        qx, qy = L.split(jnp.asarray(pk_x)), L.split(jnp.asarray(pk_y))
        p = C.scalar_mul(qx, qy, pk_inf, jnp.transpose(r_bits), C.FP_OPS)
        return L.merge(p[0])

    def g2_rlc(sig_x, sig_y, sig_inf, r_bits):
        qx, qy = F.fp2_split(jnp.asarray(sig_x)), F.fp2_split(jnp.asarray(sig_y))
        p = C.scalar_mul(qx, qy, sig_inf, jnp.transpose(r_bits), C.FP2_OPS)
        return F.fp2_merge(p[0])

    def g2_rlc_sum(sig_x, sig_y, sig_inf, r_bits):
        qx, qy = F.fp2_split(jnp.asarray(sig_x)), F.fp2_split(jnp.asarray(sig_y))
        p = C.scalar_mul(qx, qy, sig_inf, jnp.transpose(r_bits), C.FP2_OPS)
        s = C.sum_points(p, C.FP2_OPS)
        return F.fp2_merge(s[0])

    def _pairs(pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf):
        P = (
            L.split(jnp.asarray(pk_x)),
            L.split(jnp.asarray(pk_y)),
            L.const_fp(L.ONE_MONT_DIGITS, (n,)),
        )
        Q = (
            F.fp2_split(jnp.asarray(msg_x)),
            F.fp2_split(jnp.asarray(msg_y)),
            F.fp2_one((n,)),
        )
        return P, Q, jnp.asarray(pk_inf) | jnp.asarray(msg_inf)

    def miller(*xs):
        P, Q, inf = _pairs(*xs)
        f = TP.miller_loop(P, Q, inf)
        return F.fp2_merge(f[0][0])

    def tree_and_fe(*xs):
        P, Q, inf = _pairs(*xs)
        f = TP.miller_loop(P, Q, inf)
        e = TP.final_exponentiation(TP.fp12_product_tree(f))
        return F.fp2_merge(e[0][0])

    timed("scalar_mul G1 (64b rlc)", g1_rlc, pk_x, pk_y, pk_inf, r_bits)
    timed("scalar_mul G2 (64b rlc)", g2_rlc, sig_x, sig_y, sig_inf, r_bits)
    timed("G2 rlc + sum tree", g2_rlc_sum, sig_x, sig_y, sig_inf, r_bits)
    timed("miller_loop (n pairs)", miller,
          pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf)
    timed("miller+tree+final_exp", tree_and_fe,
          pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf)
    timed("FUSED multi_verify", multi_verify_kernel, *args, iters=3)


if __name__ == "__main__":
    main()
