"""Stage-level timing of the GROUPED multi_verify kernel at the bench shape.

Times each pipeline stage jit'd in isolation through the node
profiler's shared `time_jit` primitive (grandine_tpu.runtime.profiler),
each measurement ended by a host fetch of the result:
  G1 GLV ladders, G2 GLV ladders, G2 sum tree, G1 grouped sum,
  miller loops (M+1), final exp alone, and the fused grouped kernel.

Usage: [BENCH_N=16384] [BENCH_MSGS=64] python tools/profile_grouped.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    n = int(os.environ.get("BENCH_N", "16384"))
    m = int(os.environ.get("BENCH_MSGS", "64"))
    import jax
    import jax.numpy as jnp

    import bench
    from grandine_tpu.tpu import curve as C
    from grandine_tpu.tpu import field as F
    from grandine_tpu.tpu import limbs as L
    from grandine_tpu.tpu import pairing as TP
    from grandine_tpu.tpu import bls as B

    bench._enable_compilation_cache()

    print(f"platform={jax.devices()[0].platform} n={n} m={m}", file=sys.stderr)
    t0 = time.time()
    flat = bench.build_batch(n, m)
    args = bench.regroup_batch(flat, m)
    (pk_x, pk_y, pk_inf, sig_x, sig_y, sig_inf,
     msg_x, msg_y, msg_inf, r_bits) = args
    k = n // m
    print(f"prep {time.time() - t0:.1f}s", file=sys.stderr)

    from grandine_tpu.runtime.profiler import time_jit

    def timed(name, fn, *xs, iters=4):
        time_jit(name, fn, *xs, iters=iters)

    def g1_ladders(pk_x, pk_y, pk_inf, r_bits):
        pk = B._g1_in(B._flat_km(pk_x, m, k), B._flat_km(pk_y, m, k))
        pk_inf_f = B._flat_km(pk_inf, m, k)
        lo, hi = B._rlc_ladders(B._flat_km(r_bits, m, k))
        rpk = C.scalar_mul_glv(pk[0], pk[1], pk_inf_f, lo, hi,
                               B._g1_endo(m * k), C.FP_OPS)
        return L.merge(rpk[0])

    def g1_ladders_gsum(pk_x, pk_y, pk_inf, r_bits):
        pk = B._g1_in(B._flat_km(pk_x, m, k), B._flat_km(pk_y, m, k))
        pk_inf_f = B._flat_km(pk_inf, m, k)
        lo, hi = B._rlc_ladders(B._flat_km(r_bits, m, k))
        rpk = C.scalar_mul_glv(pk[0], pk[1], pk_inf_f, lo, hi,
                               B._g1_endo(m * k), C.FP_OPS)
        gpk = C.sum_points_grouped(rpk, k, C.FP_OPS)
        return L.merge(gpk[0])

    def g2_ladders(sig_x, sig_y, sig_inf, r_bits):
        sig = B._g2_in(B._flat_km(sig_x, m, k), B._flat_km(sig_y, m, k))
        sig_inf_f = B._flat_km(sig_inf, m, k)
        lo, hi = B._rlc_ladders(B._flat_km(r_bits, m, k))
        rsig = C.scalar_mul_glv(sig[0], sig[1], sig_inf_f, lo, hi,
                                B._g2_endo(m * k), C.FP2_OPS)
        return F.fp2_merge(rsig[0])

    def g2_ladders_sum(sig_x, sig_y, sig_inf, r_bits):
        sig = B._g2_in(B._flat_km(sig_x, m, k), B._flat_km(sig_y, m, k))
        sig_inf_f = B._flat_km(sig_inf, m, k)
        lo, hi = B._rlc_ladders(B._flat_km(r_bits, m, k))
        rsig = C.scalar_mul_glv(sig[0], sig[1], sig_inf_f, lo, hi,
                                B._g2_endo(m * k), C.FP2_OPS)
        s = C.sum_points(rsig, C.FP2_OPS)
        return F.fp2_merge(s[0])

    def millers(pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf):
        # M pairs (group sums stubbed by the first member key per group)
        P = (
            L.split(jnp.asarray(pk_x[:, 0])),
            L.split(jnp.asarray(pk_y[:, 0])),
            L.const_fp(L.ONE_MONT_DIGITS, (m,)),
        )
        Q = (
            F.fp2_split(jnp.asarray(msg_x)),
            F.fp2_split(jnp.asarray(msg_y)),
            F.fp2_one((m,)),
        )
        inf = jnp.asarray(pk_inf[:, 0]) | jnp.asarray(msg_inf)
        f = TP.miller_loop(P, Q, inf)
        return F.fp2_merge(f[0][0])

    def miller_tree_fe(pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf):
        P = (
            L.split(jnp.asarray(pk_x[:, 0])),
            L.split(jnp.asarray(pk_y[:, 0])),
            L.const_fp(L.ONE_MONT_DIGITS, (m,)),
        )
        Q = (
            F.fp2_split(jnp.asarray(msg_x)),
            F.fp2_split(jnp.asarray(msg_y)),
            F.fp2_one((m,)),
        )
        inf = jnp.asarray(pk_inf[:, 0]) | jnp.asarray(msg_inf)
        f = TP.miller_loop(P, Q, inf)
        e = TP.final_exponentiation(TP.fp12_product_tree(f))
        return F.fp2_merge(e[0][0])

    timed("G1 glv ladders (N)", g1_ladders, pk_x, pk_y, pk_inf, r_bits)
    timed("G1 ladders+group sum", g1_ladders_gsum, pk_x, pk_y, pk_inf, r_bits)
    timed("G2 glv ladders (N)", g2_ladders, sig_x, sig_y, sig_inf, r_bits)
    timed("G2 ladders + sum tree", g2_ladders_sum, sig_x, sig_y, sig_inf, r_bits)
    timed("miller loops (M)", millers, pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf)
    timed("miller+tree+final_exp", miller_tree_fe,
          pk_x, pk_y, pk_inf, msg_x, msg_y, msg_inf)
    timed("FUSED grouped kernel", B.grouped_multi_verify_kernel, *args, iters=3)


if __name__ == "__main__":
    main()
