"""Honest kernel microbenchmarks (the measurement matrix that picked the
limb-list scanned-CIOS montmul — see limbs.py module docstring).

Methodology notes:
  - every timing below ends in a host fetch of (a slice of) the result,
    which waits for the device.
  - chains and rotating inputs keep a loop from timing one cached call.

Historical matrix (v5e, N=16384, per-montmul-per-element):
  (N, 26) trailing-limb array + scan/concat CIOS    ~47 ns  (round-2 design)
  same, fully unrolled straight-line                ~47 ns  (concats remain)
  one array per limb, fully unrolled                ~12 ns  (~200 s compile)
  one array per limb, scanned CIOS                  ~12 ns  (~1 s compile,
                                                    but ~100-op adds: an XLA
                                                    pass quadratic in graph
                                                    size killed full kernels)
  (26, batch) limb-major array, scanned CIOS        ~12 ns  (shipping: 1-op
                                                    adds, small graphs)
The limb-major forms eliminate the cross-lane concatenates entirely.

Usage: [N=16384] [K=64] python tools/kernel_microbench.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from grandine_tpu.tpu import limbs as L
from grandine_tpu.tpu import curve as C

N = int(os.environ.get("N", "16384"))
K = int(os.environ.get("K", "64"))


def rand_fp(rng, shape):
    return jnp.asarray(
        rng.integers(0, L.MASK, (L.NLIMBS,) + shape, dtype=np.int32)
    )


def force(out):
    np.asarray(jax.tree.leaves(out)[0])


def timeit(name, f, args, iters, unit_count):
    out = f(*args)
    t0 = time.time()
    force(out)
    compile_like = time.time() - t0
    t0 = time.time()
    for _ in range(iters):
        out = f(*args)
    force(out)
    wall = (time.time() - t0) / iters
    print(f"{name:30s} run={wall*1000:9.3f} ms  {wall/unit_count*1e9:8.2f} ns/unit"
          f"  (first={compile_like:.1f}s)", flush=True)


def main():
    print(f"platform={jax.devices()[0].platform} N={N} K={K}")
    rng = np.random.default_rng(0)
    a, b = rand_fp(rng, (N,)), rand_fp(rng, (N,))

    def chain(al, bl):
        def body(x, _):
            return L.montmul(x, bl), None
        out, _ = lax.scan(body, al, None, length=K)
        return out

    timeit(f"montmul chain{K}", jax.jit(chain), (a, b), 10, K * N)

    qx, qy = rand_fp(rng, (N,)), rand_fp(rng, (N,))
    q_inf = jnp.zeros((N,), bool)
    bits = jnp.asarray(rng.integers(0, 2, (64, N), dtype=np.int32))
    f = jax.jit(lambda qx, qy, qi, b: C.scalar_mul(qx, qy, qi, b, C.FP_OPS))
    timeit("G1 scalar_mul (64-bit)", f, (qx, qy, q_inf, bits), 3, N)

    f2 = jax.jit(lambda p: C.sum_points(p, C.FP_OPS))
    timeit("G1 sum_points tree", f2, ((qx, qy, qx),), 3, N)




def extra_adds():
    """Cost of the elementwise ops between montmuls at kernel shapes."""
    rng = np.random.default_rng(1)
    a = rand_fp(rng, (2, N))
    b = rand_fp(rng, (2, N))

    def chain_add(x, y):
        def body(c, _):
            return L.add_mod(c, y), None
        out, _ = lax.scan(body, x, None, length=64)
        return out

    timeit("add_mod chain64 (2,N)", jax.jit(chain_add), (a, b), 10, 64 * N)

    def chain_select(x, y):
        cond = x[0] > y[0]
        def body(c, _):
            return L.select(cond[0], L.add_mod(c, y), c), None
        out, _ = lax.scan(body, x, None, length=64)
        return out

    timeit("add+select chain64 (2,N)", jax.jit(chain_select), (a, b), 10, 64 * N)


if __name__ == "__main__":
    if os.environ.get("EXTRA"):
        print(f"platform={jax.devices()[0].platform} N={N}")
        extra_adds()
    else:
        main()
