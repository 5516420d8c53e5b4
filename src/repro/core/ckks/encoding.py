"""CKKS canonical-embedding encode/decode.

Slots: z in C^{N/2} (we use real payloads) are the evaluations of the message
polynomial m(X) at the 2N-th roots zeta^{idx_j}, idx_j = 5^j mod 2N. Using all
2N roots lets both directions run as a single length-2N FFT:

  encode:  c_k = (2/N) * Re( FFT(scatter(z, idx))[k] ),   k < N
  decode:  z_j = (2N * IFFT(pad(c, 2N)))[idx_j]

(rows of the embedding are orthogonal: E E^H = N I, see DESIGN.md).

Two paths:
  * numpy/f64 host path — exact-enough for any delta (used by the FL client
    runtime and all tight tests);
  * jnp/complex64 jittable path — used inside the fully-jitted encrypted FL
    round; relative precision ~2**-24, which sits below the CKKS noise floor
    for delta <= 2**26 (validated in tests/test_ckks.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ckks.params import CkksContext
from repro.kernels import ref as _ref


@functools.lru_cache(maxsize=32)
def _root_indices(n_poly: int) -> np.ndarray:
    """idx_j = 5^j mod 2N for j = 0..N/2-1."""
    idx = np.empty(n_poly // 2, dtype=np.int64)
    cur = 1
    for j in range(n_poly // 2):
        idx[j] = cur
        cur = cur * 5 % (2 * n_poly)
    return idx


# ---------------------------------------------------------------------------
# numpy / float64 host path
# ---------------------------------------------------------------------------

def encode_centered(values: np.ndarray, ctx: CkksContext,
                    delta: float | None = None) -> np.ndarray:
    """Real values [B, slots] -> CENTERED integer coefficients i64[B, N].

    The pre-RNS half of encode_np — FFT interpolation and delta scaling
    with no modular reduction.  This is everything a transcipher thin
    client computes (core/ckks/transcipher.py): no NTT, no per-limb
    arithmetic.  `encode_np(v) == encode_centered(v) % qs` bit-exactly
    (numpy's int64 `%` returns non-negative residues), which is the
    transcipher bit-identity anchor.
    """
    if values.ndim == 1:
        values = values[None]
    b = values.shape[0]
    n = ctx.n_poly
    assert values.shape[1] == ctx.slots, (values.shape, ctx.slots)
    delta = float(delta if delta is not None else ctx.delta)
    idx = _root_indices(n)
    buf = np.zeros((b, 2 * n), dtype=np.complex128)
    buf[:, idx] = values.astype(np.float64)
    c = (2.0 / n) * np.real(np.fft.fft(buf, axis=-1))[:, :n]
    return np.rint(c * delta).astype(np.int64)  # [B, N]


def encode_np(values: np.ndarray, ctx: CkksContext, delta: float | None = None
              ) -> np.ndarray:
    """Real values [B, slots] -> coefficient-domain residues u32[B, L, N]."""
    c_int = encode_centered(values, ctx, delta)
    qs = np.asarray(ctx.primes, dtype=np.int64)[None, :, None]
    return (c_int[:, None, :] % qs).astype(np.uint32)  # [B, L, N]


def decode_np(residues: np.ndarray, ctx: CkksContext, scale: float) -> np.ndarray:
    """Coefficient-domain residues u32[B, L, N] -> real values [B, slots].

    Garner CRT reconstruction (exact per-step u64), centered, then f64 FFT.
    """
    b, n_limbs, n = residues.shape
    assert n == ctx.n_poly
    primes = ctx.primes[:n_limbs]
    x = residues.astype(np.uint64)
    # Garner: value = t0 + q0*t1 + q0*q1*t2 + ...
    ts = [x[:, 0, :]]
    prods: list[int] = [1]
    for i in range(1, n_limbs):
        qi = primes[i]
        acc = ts[0] % qi
        mod_prod = 1
        for k in range(1, i):
            mod_prod = mod_prod * primes[k - 1] % qi
            acc = (acc + ts[k] % qi * (mod_prod % qi)) % qi
        # full product q0..q_{i-1} mod qi
        full = 1
        for k in range(i):
            full = full * primes[k] % qi
        inv = pow(full, -1, qi)
        ti = (x[:, i, :] + qi - acc) % qi * inv % qi
        ts.append(ti)
        prods.append(prods[-1] * primes[i - 1])
    # exact big-int accumulation: f64 would round above 2**53 (3+ limbs),
    # turning ~2**88 mod-Q representatives into O(2**35) coefficient error.
    value = np.zeros((b, n), dtype=object)
    prod = 1
    for i, t in enumerate(ts):
        value += t.astype(object) * prod
        prod *= int(primes[i])
    big_q = 1
    for p in primes:
        big_q *= int(p)
    value = np.where(value > big_q // 2, value - big_q, value)
    c = (value / float(scale)).astype(np.float64)
    z = 2 * n * np.fft.ifft(np.pad(c, ((0, 0), (0, n))), axis=-1)
    return np.real(z[:, _root_indices(n)])


# ---------------------------------------------------------------------------
# jnp / complex64 jittable path
# ---------------------------------------------------------------------------

def encode_jnp(values, ctx: CkksContext, delta: float | None = None):
    """Real values f32[B, slots] -> coefficient residues u32[B, L, N]."""
    n = ctx.n_poly
    delta = float(delta if delta is not None else ctx.delta)
    idx = jnp.asarray(_root_indices(n))
    b = values.shape[0]
    buf = jnp.zeros((b, 2 * n), dtype=jnp.complex64)
    buf = buf.at[:, idx].set(values.astype(jnp.complex64))
    c = (2.0 / n) * jnp.real(jnp.fft.fft(buf, axis=-1))[:, :n]
    c_int = jnp.rint(c * delta).astype(jnp.int32)
    # limb axis broadcast against the stacked prime table — no per-limb loop
    return _ref.mod_reduce_centered(c_int[:, None, :],
                                    ctx.tables.qs[:, None])  # [B, L, N]


def decode_jnp(residues, ctx: CkksContext, scale: float):
    """u32[B, 2, N] coefficient residues -> f32[B, slots].

    Two-limb Garner with exact u32 steps; the 64-bit combine x0 + q0*t1 and
    the mod-Q centering run in (hi, lo) u32 pairs (mod-Q representatives are
    ~2**58 — f32 would quantize at 2**34, far above the CKKS noise floor).
    Only the small centered magnitude is converted to float.

    The decode runs on the host's XLA CPU device whatever the default
    backend: f32 division and the inverse FFT round differently on a TPU,
    and a decrypted model must be the same bits on every machine (the gold
    KATs pin them).  The result comes back as an uncommitted array, so
    what is computed from it next runs on the default device.
    """
    assert residues.shape[1] == 2, "jnp decode path supports 2 limbs"
    cpu = jax.devices("cpu")[0]
    out = _decode_graph(jax.device_put(residues, cpu), ctx, float(scale))
    return jnp.asarray(np.asarray(out))


@functools.partial(jax.jit, static_argnames=("ctx", "scale"))
def _decode_graph(residues, ctx: CkksContext, scale: float):
    n = ctx.n_poly
    q0, q1 = ctx.primes[0], ctx.primes[1]
    lc1 = ctx.limbs[1]
    x0 = residues[:, 0, :]
    x1 = residues[:, 1, :]
    # t1 = (x1 - x0) * q0^{-1} mod q1   (exact u32 Montgomery)
    x0_mod_q1 = jnp.where(x0 >= np.uint32(q1), x0 - np.uint32(q1), x0)
    diff = _ref.mod_sub(x1, x0_mod_q1, np.uint32(q1))
    inv_q0_mont = np.uint32(pow(q0, -1, q1) * (1 << 32) % q1)
    t1 = _ref.mont_mul(diff, jnp.broadcast_to(inv_q0_mont, diff.shape),
                       np.uint32(q1), np.uint32(lc1.qinv_neg))
    # v = x0 + q0 * t1  (exact 64-bit in u32 pairs), then center mod Q
    hi, lo = _ref.mul_wide(t1, np.uint32(q0))
    hi, lo = _ref.add_wide(hi, lo, jnp.zeros_like(x0), x0)
    big_q = int(q0) * int(q1)
    q_hi, q_lo = np.uint32(big_q >> 32), np.uint32(big_q & 0xFFFFFFFF)
    h_hi, h_lo = np.uint32((big_q // 2) >> 32), np.uint32((big_q // 2) & 0xFFFFFFFF)
    neg = _ref.gt_wide(hi, lo, h_hi, h_lo)
    mag_hi, mag_lo = _ref.sub_wide(q_hi, q_lo, hi, lo)
    mag = jnp.where(neg, _ref.wide_to_f32(mag_hi, mag_lo),
                    _ref.wide_to_f32(hi, lo))
    value = jnp.where(neg, -mag, mag)
    c = value / jnp.float32(scale)
    z = 2 * n * jnp.fft.ifft(jnp.pad(c, ((0, 0), (0, n))).astype(jnp.complex64),
                             axis=-1)
    return jnp.real(z[:, jnp.asarray(_root_indices(n))]).astype(jnp.float32)


def encode_scalar_residues(w: float, ctx: CkksContext, delta: float | None = None,
                           mont: bool = True) -> np.ndarray:
    """Scalar plaintext (constant poly) per-limb residues, optionally in
    Montgomery form — the FedAvg weight encoding. Returns u32[L]."""
    return encode_weights_mont([w], ctx, delta=delta, mont=mont)[0]


def encode_weights_mont(weights, ctx: CkksContext, delta: float | None = None,
                        mont: bool = True) -> np.ndarray:
    """Batch of scalar weights -> stacked per-limb residues u32[C, L].

    Vectorized over both axes (exact: w*delta < 2**31 and q < 2**30, so the
    int64 intermediates r * 2**32 < 2**62 never overflow); this is the weight
    table handed to the fused weighted_sum/weighted_accum kernels.
    """
    delta = float(delta if delta is not None else ctx.delta)
    w_int = np.asarray([int(round(float(w) * delta)) for w in weights],
                       dtype=np.int64)[:, None]                  # [C, 1]
    qs = np.asarray(ctx.primes, dtype=np.int64)[None, :]         # [1, L]
    r = w_int % qs
    if mont:
        r = (r << 32) % qs
    return r.astype(np.uint32)
