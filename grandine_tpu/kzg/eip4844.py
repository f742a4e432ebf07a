"""EIP-4844 KZG operations — reference: kzg_utils/src/eip_4844.rs (the six
public functions over rust-kzg-blst) and the deneb
polynomial-commitments.md spec they implement.

The hot path is the G1 multi-scalar multiplication (one per commitment /
proof): on device it is ONE batched scalar-mul launch + a log-depth sum
tree over the existing TPU curve kernels; the host fallback is a windowed
Pippenger. Single-proof verification (2 pairings) runs on the anchor
pairing.

Batch verification has a full device plane (`KzgDeviceBackend`, the
`blob_kzg` entry of the scheme dispatch table): host prep decodes and
subgroup-checks the G1 inputs, computes the Fiat–Shamir challenges and
barycentric evaluations, and lays the WHOLE batch equation

    e(Σ rⁱ(Cᵢ − yᵢG1 + zᵢWᵢ), G2) · e(−Σ rⁱWᵢ, [τ]G2) == 1

out as ONE flat scalar-mul batch in four contiguous groups
(commitments·rⁱ | proofs·rⁱzᵢ | generator·(−Σrⁱyᵢ) | proofs·(−rⁱ)); the
device then runs one ladder, one grouped sum tree, and a width-4
multi-pairing check against [G2, G2, G2, τG2] — a single dispatch per
batch. The challenge r is deterministic, so the device verdict is
IDENTICAL to the host batch path on every input (forged included), and
the n == 1 batch is algebraically the single-verify equation.
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence

from grandine_tpu.crypto import bls as A
from grandine_tpu.crypto.curves import G1, G2, Point, g1_infinity
from grandine_tpu.crypto.pairing import pairing_check
from grandine_tpu.kzg import fr
from grandine_tpu.kzg.setup import TrustedSetup, official_setup

BLS_MODULUS = fr.BLS_MODULUS
BYTES_PER_FIELD_ELEMENT = 32
FIAT_SHAMIR_PROTOCOL_DOMAIN = b"FSBLOBVERIFY_V1_"
RANDOM_CHALLENGE_KZG_BATCH_DOMAIN = b"RCKZGBATCH___V1_"
KZG_ENDIANNESS = "big"

G1_POINT_AT_INFINITY = bytes([0xC0]) + b"\x00" * 47

#: flip to False to force the host Pippenger MSM (no JAX)
USE_DEVICE_MSM = True

#: flip to False to force the host pairing tail of batch verification
USE_DEVICE_KZG = True


class KzgError(ValueError):
    pass


# ------------------------------------------------------------ (de)serialize


def _bytes_to_bls_field(b: bytes) -> int:
    v = int.from_bytes(b, KZG_ENDIANNESS)
    if v >= BLS_MODULUS:
        raise KzgError("field element out of range")
    return v


def _field_to_bytes(v: int) -> bytes:
    return int(v).to_bytes(BYTES_PER_FIELD_ELEMENT, KZG_ENDIANNESS)


def _blob_to_polynomial(blob: bytes, width: int) -> "list[int]":
    if len(blob) != width * BYTES_PER_FIELD_ELEMENT:
        raise KzgError(f"blob must be {width * BYTES_PER_FIELD_ELEMENT} bytes")
    return [
        _bytes_to_bls_field(blob[i * 32 : (i + 1) * 32]) for i in range(width)
    ]


def _g1_from_commitment_bytes(b: bytes) -> Point:
    try:
        return A.g1_from_bytes(bytes(b), subgroup_check=True)
    except A.BlsError as e:
        raise KzgError(f"invalid G1 encoding: {e}") from e


def _hash_to_bls_field(data: bytes) -> int:
    return int.from_bytes(hashlib.sha256(data).digest(), KZG_ENDIANNESS) % BLS_MODULUS


def _compute_challenge(blob: bytes, commitment: bytes, width: int) -> int:
    degree_poly = width.to_bytes(16, KZG_ENDIANNESS)
    return _hash_to_bls_field(
        FIAT_SHAMIR_PROTOCOL_DOMAIN + degree_poly + blob + commitment
    )


# ----------------------------------------------------------------------- MSM


def _msm_host(points: "Sequence[Point]", scalars: "Sequence[int]") -> Point:
    """Windowed Pippenger MSM (host fallback)."""
    window = 8
    acc_total = g1_infinity()
    n_windows = (255 + window - 1) // window
    for w in range(n_windows - 1, -1, -1):
        shift = w * window
        buckets: "dict[int, Point]" = {}
        for p, s in zip(points, scalars):
            digit = (s >> shift) & ((1 << window) - 1)
            if digit:
                cur = buckets.get(digit)
                buckets[digit] = p if cur is None else cur + p
        if w != n_windows - 1:
            for _ in range(window):
                acc_total = acc_total.double()
        # Σ d·B_d via descending running sums weighted by digit gaps
        running = g1_infinity()
        window_sum = g1_infinity()
        digits = sorted(buckets, reverse=True)
        for i, digit in enumerate(digits):
            running = running + buckets[digit]
            next_digit = digits[i + 1] if i + 1 < len(digits) else 0
            window_sum = window_sum + running.mul(digit - next_digit)
        acc_total = acc_total + window_sum
    return acc_total


def _msm_device(setup: TrustedSetup, scalars: "Sequence[int]") -> Point:
    """Device MSM over the setup's (cached, limb-form) G1 points: one
    batched scalar-mul kernel + a complete-addition sum tree."""
    import jax
    import numpy as np

    from grandine_tpu.tpu import curve as C
    from grandine_tpu.tpu import limbs as L

    cache = setup._dev_cache
    if cache is None:
        n = setup.width
        xs = np.zeros((n, L.NLIMBS), np.int32)
        ys = np.zeros((n, L.NLIMBS), np.int32)
        inf = np.zeros(n, bool)
        for i, pt in enumerate(setup.g1_lagrange_brp):
            xs[i], ys[i], inf[i] = C.g1_point_to_dev(pt)
        cache = setup._dev_cache = (xs, ys, inf)
    xs, ys, inf = cache

    from grandine_tpu.tpu.bls import _jitted_global, dispatch_scope

    def msm_kernel(px, py, p_inf, bits):
        import jax.numpy as jnp

        qx, qy = L.split(jnp.asarray(px)), L.split(jnp.asarray(py))
        jac = C.scalar_mul(qx, qy, p_inf, jnp.transpose(bits), C.FP_OPS)
        X, Y, Z = C.sum_points(jac, C.FP_OPS)
        return L.merge(X), L.merge(Y), L.merge(Z)

    # ONE process-wide jitted wrapper; jit re-specializes per setup width,
    # and each width is a distinct ledger signature (tools/shapes contract)
    fn = _jitted_global("kzg_msm", msm_kernel)
    bits = C.scalars_to_bits_msb([s % BLS_MODULUS for s in scalars], 255)
    args = (xs, ys, inf, bits)
    from grandine_tpu.tpu.bls import _node_profiler

    with dispatch_scope("kzg_msm", args), \
            _node_profiler().annotate("kzg_msm", len(scalars)):
        X, Y, Z = fn(*args)
    import numpy as np

    return C.dev_to_g1_point(np.asarray(X), np.asarray(Y), np.asarray(Z))


def _g1_lincomb(setup: TrustedSetup, scalars: "Sequence[int]") -> Point:
    if USE_DEVICE_MSM:
        try:
            return _msm_device(setup, scalars)
        except ImportError:
            pass  # no JAX: host path
        except Exception as e:
            import warnings

            warnings.warn(
                f"device MSM failed ({e!r}); falling back to host Pippenger"
            )
    return _msm_host(setup.g1_lagrange_brp, scalars)


# ------------------------------------------------------------ the six calls


def blob_to_kzg_commitment(
    blob: bytes, setup: "Optional[TrustedSetup]" = None
) -> bytes:
    setup = setup or official_setup()
    poly = _blob_to_polynomial(bytes(blob), setup.width)
    return A.g1_to_bytes(_g1_lincomb(setup, poly))


def compute_kzg_proof(
    blob: bytes, z_bytes: bytes, setup: "Optional[TrustedSetup]" = None
) -> "tuple[bytes, bytes]":
    """Returns (proof, y) for the evaluation p(z) = y."""
    setup = setup or official_setup()
    poly = _blob_to_polynomial(bytes(blob), setup.width)
    z = _bytes_to_bls_field(bytes(z_bytes))
    proof, y = _compute_kzg_proof_impl(poly, z, setup)
    return proof, _field_to_bytes(y)


def _compute_kzg_proof_impl(poly, z: int, setup: TrustedSetup):
    roots = setup.roots_brp
    y = fr.evaluate_polynomial_in_evaluation_form(poly, z, roots)
    # quotient q_i = (f_i - y) / (w_i - z), with the special row when
    # z equals a root (spec compute_kzg_proof_impl)
    width = setup.width
    denoms = [(w - z) % BLS_MODULUS for w in roots]
    inv_denoms = fr.batch_inverse(denoms)
    q = [0] * width
    special = None
    for i in range(width):
        if denoms[i] == 0:
            special = i
            continue
        q[i] = (poly[i] - y) % BLS_MODULUS * inv_denoms[i] % BLS_MODULUS
    if special is not None:
        # q_m = sum_{i != m} f_i * w_i / (m_root * (m_root - w_i))... spec:
        # build from the other rows
        m = special
        zm = roots[m]
        inv_z = fr.batch_inverse(
            [zm * ((zm - w) % BLS_MODULUS) % BLS_MODULUS for w in roots]
        )
        acc = 0
        for i in range(width):
            if i == m:
                continue
            acc += (
                (poly[i] - y)
                % BLS_MODULUS
                * roots[i]
                % BLS_MODULUS
                * inv_z[i]
                % BLS_MODULUS
            )
        q[m] = acc % BLS_MODULUS
    return A.g1_to_bytes(_g1_lincomb(setup, q)), y


def verify_kzg_proof(
    commitment_bytes: bytes,
    z_bytes: bytes,
    y_bytes: bytes,
    proof_bytes: bytes,
    setup: "Optional[TrustedSetup]" = None,
) -> bool:
    """e(P - [y]G1, G2) == e(proof, [tau - z]G2) — spec verify_kzg_proof."""
    setup = setup or official_setup()
    commitment = _g1_from_commitment_bytes(commitment_bytes)
    proof = _g1_from_commitment_bytes(proof_bytes)
    z = _bytes_to_bls_field(bytes(z_bytes))
    y = _bytes_to_bls_field(bytes(y_bytes))
    return _verify_kzg_proof_impl(commitment, z, y, proof, setup)


def _verify_kzg_proof_impl(commitment, z, y, proof, setup) -> bool:
    # X_minus_z = [tau]G2 - [z]G2 ; P_minus_y = commitment - [y]G1
    x_minus_z = setup.tau_g2 + (-G2.mul(z) if z else _g2_zero())
    p_minus_y = commitment + (-G1.mul(y) if y else g1_infinity())
    # e(P - y, G2) * e(-proof, X - z) == 1
    return pairing_check([(p_minus_y, G2), (-proof, x_minus_z)])


def _g2_zero():
    from grandine_tpu.crypto.curves import g2_infinity

    return g2_infinity()


def compute_blob_kzg_proof(
    blob: bytes, commitment_bytes: bytes, setup: "Optional[TrustedSetup]" = None
) -> bytes:
    setup = setup or official_setup()
    _g1_from_commitment_bytes(commitment_bytes)  # validate encoding
    poly = _blob_to_polynomial(bytes(blob), setup.width)
    z = _compute_challenge(bytes(blob), bytes(commitment_bytes), setup.width)
    proof, _y = _compute_kzg_proof_impl(poly, z, setup)
    return proof


def verify_blob_kzg_proof(
    blob: bytes,
    commitment_bytes: bytes,
    proof_bytes: bytes,
    setup: "Optional[TrustedSetup]" = None,
) -> bool:
    setup = setup or official_setup()
    commitment = _g1_from_commitment_bytes(commitment_bytes)
    proof = _g1_from_commitment_bytes(proof_bytes)
    poly = _blob_to_polynomial(bytes(blob), setup.width)
    z = _compute_challenge(bytes(blob), bytes(commitment_bytes), setup.width)
    y = fr.evaluate_polynomial_in_evaluation_form(poly, z, setup.roots_brp)
    return _verify_kzg_proof_impl(commitment, z, y, proof, setup)


def verify_blob_kzg_proof_batch(
    blobs: "Sequence[bytes]",
    commitments: "Sequence[bytes]",
    proofs: "Sequence[bytes]",
    setup: "Optional[TrustedSetup]" = None,
) -> bool:
    """Random-linear-combination batch verification (spec
    verify_blob_kzg_proof_batch): ONE pairing check for N blobs."""
    setup = setup or official_setup()
    n = len(blobs)
    if not (n == len(commitments) == len(proofs)):
        raise KzgError("length mismatch")
    if n == 0:
        return True
    if n == 1:
        return verify_blob_kzg_proof(blobs[0], commitments[0], proofs[0], setup)

    commitment_points = [_g1_from_commitment_bytes(c) for c in commitments]
    proof_points = [_g1_from_commitment_bytes(p) for p in proofs]
    zs, ys = [], []
    for blob, commitment in zip(blobs, commitments):
        poly = _blob_to_polynomial(bytes(blob), setup.width)
        z = _compute_challenge(bytes(blob), bytes(commitment), setup.width)
        zs.append(z)
        ys.append(
            fr.evaluate_polynomial_in_evaluation_form(poly, z, setup.roots_brp)
        )

    # powers of r from the spec's batch-challenge domain
    data = (
        RANDOM_CHALLENGE_KZG_BATCH_DOMAIN
        + setup.width.to_bytes(8, KZG_ENDIANNESS)
        + n.to_bytes(8, KZG_ENDIANNESS)
    )
    for commitment, z, y, proof in zip(commitments, zs, ys, proofs):
        data += bytes(commitment) + _field_to_bytes(z) + _field_to_bytes(y) + bytes(proof)
    r = _hash_to_bls_field(data)
    r_powers = [pow(r, i, BLS_MODULUS) for i in range(n)]

    if USE_DEVICE_KZG:
        got = _batch_pairing_device(
            setup, commitment_points, proof_points, zs, ys, r_powers
        )
        if got is not None:
            return got

    # Σ r^i (C_i - [y_i]G1 + z_i·proof_i)  vs  Σ r^i proof_i under tau:
    #   e(Σ r^i(C_i - y_i + z_i·W_i), G2) == e(Σ r^i W_i, [tau]G2)
    proof_lincomb = g1_infinity()
    rhs_lincomb = g1_infinity()
    for ri, C_pt, W_pt, z, y in zip(
        r_powers, commitment_points, proof_points, zs, ys
    ):
        proof_lincomb = proof_lincomb + W_pt.mul(ri)
        interp = C_pt + (-G1.mul(y) if y else g1_infinity())
        interp = interp + W_pt.mul(z)
        rhs_lincomb = rhs_lincomb + interp.mul(ri)
    return pairing_check(
        [(rhs_lincomb, G2), (-proof_lincomb, setup.tau_g2)]
    )


# ----------------------------------------------------- device batch verify


def _blob_verify_kernel(px, py, p_inf, bits, q2x, q2y):
    """One-dispatch batch blob-proof verdict. Inputs (REST format):
    px/py (4s, 26) affine G1 Montgomery coords, p_inf (4s,) bool, bits
    (4s, 255) MSB-first scalar bits, q2x/q2y (4, 2, 26) affine G2 coords
    [G2, G2, G2, τG2]. The flat batch is four contiguous s-groups (see
    module docstring); the grouped sum tree yields the four pairing P's
    directly. Returns the (1,) bool verdict."""
    import jax.numpy as jnp

    from grandine_tpu.tpu import curve as C
    from grandine_tpu.tpu import field as F
    from grandine_tpu.tpu import limbs as L
    from grandine_tpu.tpu import pairing as TP

    s = int(px.shape[0]) // 4
    qx, qy = L.split(jnp.asarray(px)), L.split(jnp.asarray(py))
    jac = C.scalar_mul(qx, qy, p_inf, jnp.transpose(bits), C.FP_OPS)
    X, Y, Z = C.sum_points_contiguous(jac, s, C.FP_OPS)
    # a group sum CAN legitimately be infinity (adversarial cancellation)
    # — the pairing needs the mask explicitly; one fused Montgomery
    # reduction pulls the relaxed Z into the 8p-bounded zero test's range
    one4 = L.const_fp(L.ONE_MONT_DIGITS, (4,))
    inf = L.is_zero_val(L.montmul(Z, one4))
    Qx, Qy = F.fp2_split(jnp.asarray(q2x)), F.fp2_split(jnp.asarray(q2y))
    return TP.multi_pairing_check((X, Y, Z), (Qx, Qy, F.fp2_one((4,))), inf)


def _setup_for_width(width: int) -> TrustedSetup:
    """Blob width → trusted setup: the official 4096 setup in production,
    the INSECURE known-tau dev setup for test widths."""
    if width == 4096:
        return official_setup()
    from grandine_tpu.kzg.setup import dev_setup

    return dev_setup(width)


class KzgDeviceBackend:
    """The blob_kzg scheme backend (built via schemes.get("blob_kzg"),
    one per lane; also the tail of `verify_blob_kzg_proof_batch` when
    USE_DEVICE_KZG). All verdict-relevant decoding (G1 subgroup checks,
    blob field-element range checks) and the Fiat–Shamir transcript run
    on host in `prepare`; the device evaluates the batch equation in one
    dispatch. Deterministic challenge → verdicts identical to the host
    batch path bit-for-bit."""

    ASYNC_SEAM = ("verify_blobs_async",)
    #: bucket cap: lane batches pad into {4, 8}; anything larger degrades
    #: to the host path rather than minting an unwarmed ladder shape
    MAX_ITEMS = 8

    def __init__(self, *, metrics=None, tracer=None, lane: str = "blob_kzg",
                 mesh=None) -> None:
        self.metrics = metrics
        self.tracer = tracer
        self.lane = lane
        self._g2_dev: dict = {}  # (setup name, width) → (q2x, q2y)

    def _count_kernel(self, kernel: str, sigs: int) -> None:
        if self.metrics is not None:
            self.metrics.device_kernel_calls.labels(kernel).inc()
            if sigs:
                self.metrics.device_kernel_sigs.labels(kernel).inc(sigs)

    def _g2_cache(self, setup: TrustedSetup):
        key = (setup.name, setup.width)
        hit = self._g2_dev.get(key)
        if hit is None:
            from grandine_tpu.tpu import curve as C

            q2x, q2y, _inf = C.g2_points_to_dev([G2, G2, G2, setup.tau_g2])
            hit = self._g2_dev[key] = (q2x, q2y)
        return hit

    def prepare(self, items):
        """Scheduler item geometry (message=blob, public_keys=(commitment,),
        signature=proof) → (status, payload): "ok" → device arrays,
        "invalid" → some item can never verify (the batch must FAIL so
        bisection isolates against the host twin), "mixed"/"oversize" →
        host degradation (per-item verdicts stay correct)."""
        n = len(items)
        if n == 0:
            return "ok", ()
        if n > self.MAX_ITEMS:
            return "oversize", None
        widths = set()
        for it in items:
            keys = it.public_keys
            if keys is None or len(keys) != 1:
                return "invalid", None
            blob_len = len(bytes(it.message))
            if blob_len % BYTES_PER_FIELD_ELEMENT:
                return "invalid", None
            widths.add(blob_len // BYTES_PER_FIELD_ELEMENT)
        if len(widths) != 1:
            # blob widths select the trusted setup — a mixed batch has no
            # single device shape; host degradation handles each item
            return "mixed", None
        width = widths.pop()
        if width < 2 or width & (width - 1):
            return "invalid", None
        setup = _setup_for_width(width)
        return self.prepare_raw(
            [bytes(it.message) for it in items],
            [bytes(it.public_keys[0]) for it in items],
            [bytes(it.signature) for it in items],
            setup,
        )

    def prepare_raw(self, blobs, commitments, proofs, setup: TrustedSetup):
        """Raw byte triples → (status, payload) — the shared prep of the
        scheduler path and verify_blob_kzg_proof_batch's device tail."""
        n = len(blobs)
        if n == 0:
            return "ok", ()
        try:
            commitment_points = [
                _g1_from_commitment_bytes(c) for c in commitments
            ]
            proof_points = [_g1_from_commitment_bytes(p) for p in proofs]
            zs, ys = [], []
            for blob, commitment in zip(blobs, commitments):
                poly = _blob_to_polynomial(bytes(blob), setup.width)
                z = _compute_challenge(
                    bytes(blob), bytes(commitment), setup.width
                )
                zs.append(z)
                ys.append(
                    fr.evaluate_polynomial_in_evaluation_form(
                        poly, z, setup.roots_brp
                    )
                )
        except KzgError:
            return "invalid", None
        data = (
            RANDOM_CHALLENGE_KZG_BATCH_DOMAIN
            + setup.width.to_bytes(8, KZG_ENDIANNESS)
            + n.to_bytes(8, KZG_ENDIANNESS)
        )
        for commitment, z, y, proof in zip(commitments, zs, ys, proofs):
            data += (
                bytes(commitment) + _field_to_bytes(z)
                + _field_to_bytes(y) + bytes(proof)
            )
        r = _hash_to_bls_field(data)
        r_powers = [pow(r, i, BLS_MODULUS) for i in range(n)]
        return "ok", self.pack(
            setup, commitment_points, proof_points, zs, ys, r_powers
        )

    def pack(self, setup, commitment_points, proof_points, zs, ys, r_powers):
        """Decoded points + challenges → the kernel's array payload: the
        four-group flat MSM batch of the module docstring."""
        import numpy as np

        from grandine_tpu.tpu import curve as C
        from grandine_tpu.tpu import limbs as L
        from grandine_tpu.tpu.bls import _bucket

        n = len(commitment_points)
        q = BLS_MODULUS
        bn = _bucket(n, lo=4, hi=self.MAX_ITEMS)
        total = 4 * bn
        px = np.zeros((total, L.NLIMBS), np.int32)
        py = np.zeros((total, L.NLIMBS), np.int32)
        pinf = np.ones(total, bool)  # pads: infinity with scalar 0
        scalars = [0] * total
        for i, (cp, wp, z, ri) in enumerate(
            zip(commitment_points, proof_points, zs, r_powers)
        ):
            px[i], py[i], pinf[i] = C.g1_point_to_dev(cp)
            scalars[i] = ri
            px[bn + i], py[bn + i], pinf[bn + i] = C.g1_point_to_dev(wp)
            scalars[bn + i] = ri * z % q
            px[3 * bn + i] = px[bn + i]
            py[3 * bn + i] = py[bn + i]
            pinf[3 * bn + i] = pinf[bn + i]
            scalars[3 * bn + i] = (q - ri) % q  # −Σ rⁱWᵢ via negated scalars
        px[2 * bn], py[2 * bn], pinf[2 * bn] = C.g1_point_to_dev(G1)
        scalars[2 * bn] = (-sum(
            ri * y for ri, y in zip(r_powers, ys)
        )) % q
        bits = C.scalars_to_bits_msb(scalars, 255)
        q2x, q2y = self._g2_cache(setup)
        return (px, py, pinf, bits, q2x, q2y, n)

    def verify_blobs_async(self, prep):
        """Dispatch the packed batch; returns the zero-arg settle (forces
        the device verdict)."""
        if not prep:
            return lambda: True
        import numpy as np

        from grandine_tpu.tpu.bls import _jitted_global, dispatch_scope

        px, py, pinf, bits, q2x, q2y, n = prep
        fn = _jitted_global("kzg_blob_verify", _blob_verify_kernel)
        args = (px, py, pinf, bits, q2x, q2y)
        scope = dispatch_scope("kzg_blob_verify", args, self.metrics)
        self._count_kernel("kzg_blob_verify", n)
        from grandine_tpu.tpu.bls import _node_profiler

        prof_scope = _node_profiler().annotate("kzg_blob_verify", n)
        if self.tracer is not None:
            with scope, self.tracer.span(
                "device_dispatch",
                {"kernel": "kzg_blob_verify", "lane": self.lane},
            ):
                with prof_scope:
                    out = fn(*args)
        else:
            with scope, prof_scope:
                out = fn(*args)

        def settle() -> bool:
            return bool(np.asarray(out).all())

        return settle


_DEVICE_BACKEND: "Optional[KzgDeviceBackend]" = None


def _batch_pairing_device(
    setup, commitment_points, proof_points, zs, ys, r_powers
):
    """Device tail of verify_blob_kzg_proof_batch: the inputs are already
    decoded and the challenge fixed, so the verdict CANNOT differ from
    the host tail — any device failure returns None and the caller falls
    back. Batches beyond the warmed buckets also decline (None) rather
    than mint a novel ladder shape."""
    global _DEVICE_BACKEND
    if len(commitment_points) > KzgDeviceBackend.MAX_ITEMS:
        return None
    try:
        if _DEVICE_BACKEND is None:
            _DEVICE_BACKEND = KzgDeviceBackend()
        prep = _DEVICE_BACKEND.pack(
            setup, commitment_points, proof_points, zs, ys, r_powers
        )
        return _DEVICE_BACKEND.verify_blobs_async(prep)()
    except ImportError:
        return None
    except Exception as e:
        import warnings

        warnings.warn(
            f"device KZG batch verify failed ({e!r}); "
            "falling back to host pairing"
        )
        return None


def host_check_item(item) -> bool:
    """VerifyItem adapter (blob_kzg lane geometry) — the scheduler's
    bisection leaf and host degradation pass. Never raises: undecodable
    bytes are a False verdict, exactly as the device path scores them."""
    keys = item.public_keys
    if keys is None or len(keys) != 1:
        return False
    blob = bytes(item.message)
    width = len(blob) // BYTES_PER_FIELD_ELEMENT
    if len(blob) % BYTES_PER_FIELD_ELEMENT or width < 2 or width & (width - 1):
        return False
    try:
        return verify_blob_kzg_proof(
            blob, bytes(keys[0]), bytes(item.signature),
            _setup_for_width(width),
        )
    except KzgError:
        return False


__all__ = [
    "KzgError",
    "KzgDeviceBackend",
    "blob_to_kzg_commitment",
    "compute_kzg_proof",
    "compute_blob_kzg_proof",
    "verify_kzg_proof",
    "verify_blob_kzg_proof",
    "verify_blob_kzg_proof_batch",
    "host_check_item",
    "G1_POINT_AT_INFINITY",
]
