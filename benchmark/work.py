"""Work counts of one batch-verification call, from its shapes alone.

The roofline share of the verify kernel divides the LEAST time the chip
could take for the call by the time the trace shows. The numerator is
counted here for a stated textbook algorithm, never for the code that
happens to run: a later PR that changes MSM windows, limb width, or fuses
stages changes the kernel's time and must not change this count.

Shapes: n items (signature sets), w members in the widest item, m distinct
messages. The algorithm (random-linear-combination batch verification of
BLS12-381 min-pk aggregates, as in the reference client's blst path):

  per item   w-1 mixed additions on G1 (the item's aggregate key);
             one 64-bit double-and-add on G1 (randomizer x key) and one
             on G2 (randomizer x signature): 64 doublings, 32 additions;
             one subgroup ladder on G2 by |x| (63 doublings, 5 additions)
  per call   n-m G1 additions (keys of one message combined), n-1 G2
             additions (signatures summed);
             m+1 optimal-ate Miller loops over |x| (63 doubling steps, 5
             addition steps each, one shared Fp12 squaring per step);
             one final exponentiation

in base-field multiplications M (a squaring counts as one): Fp2 mul 3,
Fp2 sq 2; Jacobian doubling 2 mul + 5 sq, mixed addition 7 mul + 4 sq, in
the point's field; Miller doubling step 25 + line x f 39, addition step
41 + 39, Fp12 squaring 36; final exponentiation 7,920 (easy part 260, hard
part five exponentiations by |x| at 63 cyclotomic squarings of 18 and 5
multiplications of 54, ten further multiplications, Frobenius maps 100).

One M is costed as one 381-bit Montgomery multiplication in 8-bit limbs:
3 x 48^2 multiply-adds = 13,824 int8 operations, which is why the compute
bound is taken against the chip's int8 peak (the kernels themselves run
int32 on the vector unit; the int8 peak is the chip's best case for
integer work, so the share is a floor on what is left to gain).

Bytes, per item: 96 (signature) + 32 (signing root) + 4w (member
indices) in, w registry rows of 96 B gathered, 1 B of verdict out.
"""

from __future__ import annotations

import json
import os

G1_DBL, G1_ADD = 7, 11
G2_DBL, G2_ADD = 2 * 3 + 5 * 2, 7 * 3 + 4 * 2
RLC_BITS = 64
X_DOUBLINGS, X_ADDITIONS = 63, 5
MILLER_DBL_STEP, MILLER_ADD_STEP, LINE_MUL, FP12_SQ = 25, 41, 39, 36
FINAL_EXP = 260 + 5 * (63 * 18 + 5 * 54) + 10 * 54 + 100
INT8_OPS_PER_M = 2 * 3 * 48 * 48
ITEM_BYTES_IN, INDEX_BYTES, REGISTRY_ROW_BYTES, VERDICT_BYTES = 128, 4, 96, 1


def verify_call(n: int, w: int, m: int) -> dict:
    """{"field_mults", "int8_ops", "bytes"} of one call."""
    if not (n >= 1 and w >= 1 and 1 <= m <= n):
        raise ValueError(f"not a verify call: n={n} w={w} m={m}")
    ladder = RLC_BITS * (G1_DBL + G2_DBL) + RLC_BITS // 2 * (G1_ADD + G2_ADD)
    subgroup = X_DOUBLINGS * G2_DBL + X_ADDITIONS * G2_ADD
    per_item = (w - 1) * G1_ADD + ladder + subgroup
    pair = (X_DOUBLINGS * (MILLER_DBL_STEP + LINE_MUL)
            + X_ADDITIONS * (MILLER_ADD_STEP + LINE_MUL))
    per_call = ((n - m) * G1_ADD + (n - 1) * G2_ADD
                + X_DOUBLINGS * FP12_SQ + (m + 1) * pair + FINAL_EXP)
    mults = n * per_item + per_call
    moved = n * (ITEM_BYTES_IN + w * (INDEX_BYTES + REGISTRY_ROW_BYTES)
                 + VERDICT_BYTES)
    return {"field_mults": mults, "int8_ops": mults * INT8_OPS_PER_M,
            "bytes": moved}


def load_peaks(device_kind: str, path: "str | None" = None) -> dict:
    """The chip's published peaks. An unknown device is an error, never a
    default."""
    path = path or os.path.join(os.path.dirname(__file__), "peaks.json")
    with open(path) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path}: add the "
            "chip with its source, do not guess"
        )
    return table[device_kind]


def least_seconds(calls: "list[dict]", peaks: dict) -> "tuple[float, str]":
    """(least seconds the chip could take for `calls`, which bound)."""
    counted = [verify_call(c["n"], c["w"], c["m"]) for c in calls]
    ops = sum(c["int8_ops"] for c in counted)
    moved = sum(c["bytes"] for c in counted)
    by_compute = ops / peaks["int8_ops_per_s"]
    by_memory = moved / peaks["hbm_bytes_per_s"]
    if by_compute >= by_memory:
        return by_compute, "compute"
    return by_memory, "memory"


def roofline_pct(run: dict) -> "float | None":
    """What the `<kernel>_roofline` readers return: the least time the
    chip could take for the window's calls, per call, over the kernel time
    per call that the trace shows; nothing where no trace was taken."""
    trace, calls = run["trace"], run["calls"]
    if not trace or not trace["kernel_calls"] or not calls or not run["peaks"]:
        return None
    least, _bound = least_seconds(calls, run["peaks"])
    per_call = least / len(calls)
    return 100.0 * per_call * trace["kernel_calls"] / trace["kernel_s"]
