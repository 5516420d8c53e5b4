"""One benchmark per paper table/figure, run on this host's CPU backend.

The paper benchmarks wall-clock of CPU HE libraries (PALISADE/TenSEAL); we
benchmark our own TPU-native u32 CKKS running on the CPU backend, so the
*ratios* (HE vs plaintext, selective vs full) are comparable even though
absolute times differ.  Communication numbers use the serialized-size model
(exact byte accounting, hardware independent).

Tables covered:
  table4   Vanilla fully-encrypted aggregation vs plaintext across model
           sizes (comp ratio + comm ratio)           [paper Table 4]
  table6   Crypto-parameter sweep: packing batch size x scaling bits
                                                     [paper Table 6]
  table7   Selective-encryption ratio sweep on a ViT-sized model
                                                     [paper Table 7]
  fig7     Overhead vs selection ratio across model sizes  [Figure 7]
  fig14a   Server aggregation cost vs number of clients    [Figure 14a]
  fig8     Training-cycle time distribution with/without optimization
           at AWS-region bandwidth                   [Figure 8]
  dp_adv   Privacy-budget advantage (1-p) vs (1-p)^2 law   [Remarks 3.12-14]
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import dp, selection
from repro.core.ckks import cipher, encoding
from repro.core.ckks import params as ckks_params

# paper Table 4 model inventory (name, n_params)
PAPER_MODELS = [
    ("Linear", 101),
    ("TimeSeriesTransformer", 5_609),
    ("MLP-2FC", 79_510),
    ("LeNet", 88_648),
    ("RNN-2LSTM", 822_570),
    ("CNN-2conv2fc", 1_663_370),
    ("MobileNet", 3_315_428),
    ("ResNet-18", 12_556_426),
    ("ResNet-50", 25_557_032),
    ("ViT", 86_389_248),
    ("BERT", 109_482_240),
]

BW_CASES = {"IB": 5e9, "SAR": 592e6, "MAR": 15.6e6}    # paper §D.5


def _time(f, *args, reps=3):
    f(*args)                       # compile/warm
    jax.block_until_ready(f(*args))
    t0 = time.time()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def _bench_agg(ctx, n_values: int, n_clients: int = 3):
    """Wall-clock one encrypted aggregation of n_values params (CPU) and
    the plaintext equivalent.  Returns dict of times + sizes."""
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    n_ct = ctx.num_ciphertexts(n_values)
    rng = np.random.RandomState(0)
    vals = rng.randn(n_ct, ctx.slots).astype(np.float32)
    coeffs = jnp.asarray(encoding.encode_np(vals, ctx))

    enc = jax.jit(lambda c, k: cipher.encrypt_coeffs(ctx, pk, c, k).data)
    t_enc = _time(enc, coeffs, jax.random.PRNGKey(1))

    ct = cipher.encrypt_coeffs(ctx, pk, coeffs, jax.random.PRNGKey(1))
    cts = cipher.Ciphertext(
        data=jnp.broadcast_to(ct.data, (n_clients,) + ct.data.shape),
        scale=ct.scale)
    w = [1.0 / n_clients] * n_clients
    agg = jax.jit(lambda d: cipher.weighted_sum(
        ctx, cipher.Ciphertext(data=d, scale=ct.scale), w).data)
    t_agg = _time(agg, cts.data)

    # not jitted: the decode runs on the host CPU device (encoding.decode_jnp)
    dec = lambda d: cipher.decrypt_values(  # noqa: E731
        ctx, sk, cipher.Ciphertext(data=d, scale=ct.scale * ctx.delta))
    t_dec = _time(dec, agg(cts.data))

    plain = jnp.asarray(rng.randn(n_clients, n_values).astype(np.float32))
    pl = jax.jit(lambda x: jnp.einsum(
        "c,cp->p", jnp.asarray(w, jnp.float32), x))
    t_plain = _time(pl, plain)

    return {
        "t_he": t_enc + t_agg + t_dec,
        "t_enc": t_enc, "t_agg": t_agg, "t_dec": t_dec,
        "t_plain": t_plain,
        "ct_bytes": ctx.encrypted_bytes(n_values),
        "pt_bytes": ctx.plaintext_bytes(n_values),
    }


def table4(ctx=None, max_params=2_000_000):
    """HE vs plaintext aggregation across model sizes (sub-sampled: models
    above max_params use the measured per-ciphertext rate — exact, since
    cost is linear in ciphertext count; Figure 2 observation)."""
    ctx = ctx or ckks_params.make_context(n_poly=8192, n_limbs=2,
                                          delta_bits=26)
    # measure the per-ciphertext rate once at a calibration size
    calib_n = 512 * ctx.slots
    base = _bench_agg(ctx, calib_n)
    per_ct_he = base["t_he"] / ctx.num_ciphertexts(calib_n)
    per_val_plain = base["t_plain"] / calib_n
    rows = []
    for name, n in PAPER_MODELS:
        if n <= max_params:
            r = _bench_agg(ctx, n)
            t_he, t_plain = r["t_he"], r["t_plain"]
            measured = True
        else:
            t_he = per_ct_he * ctx.num_ciphertexts(n)
            t_plain = per_val_plain * n
            measured = False
        rows.append({
            "model": name, "params": n,
            "t_he_s": t_he, "t_plain_s": t_plain,
            "comp_ratio": t_he / max(t_plain, 1e-9),
            "ct_bytes": ctx.encrypted_bytes(n),
            "pt_bytes": ctx.plaintext_bytes(n),
            "comm_ratio": ctx.encrypted_bytes(n)
                          / max(1, ctx.plaintext_bytes(n)),
            "measured": measured,
        })
    return rows


def table6():
    """Packing batch size x scaling bits: comp/comm/accuracy proxy."""
    rows = []
    n_values = 200_000
    rng = np.random.RandomState(0)
    for n_poly in (2048, 4096, 8192):
        for delta_bits in (14, 20, 26):
            ctx = ckks_params.make_context(n_poly=n_poly, n_limbs=2,
                                           delta_bits=delta_bits)
            sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
            v = rng.randn(1, ctx.slots).astype(np.float32)
            ct = cipher.encrypt_coeffs(
                ctx, pk, jnp.asarray(encoding.encode_np(v, ctx)),
                jax.random.PRNGKey(1))
            w = cipher.mul_plain_scalar(ctx, ct, 0.5)
            err = float(np.abs(cipher.decrypt_values_np(ctx, sk, w)
                               - 0.5 * v).max())
            r = _bench_agg(ctx, 64 * ctx.slots)
            scale_t = ctx.num_ciphertexts(n_values) / 64
            rows.append({
                "batch_size": ctx.slots, "scaling_bits": delta_bits,
                "comp_s": r["t_he"] * scale_t,
                "comm_bytes": ctx.encrypted_bytes(n_values),
                "decrypt_abs_err": err,
            })
    return rows


def table7(n_params=86_389_248):
    """Selection-ratio sweep (ViT-sized): overhead vs Enc w/ 0%."""
    ctx = ckks_params.make_context(n_poly=8192, n_limbs=2, delta_bits=26)
    base = _bench_agg(ctx, 64 * ctx.slots)
    per_ct = base["t_he"] / 64
    per_val_plain = base["t_plain"] / (64 * ctx.slots)
    rows = []
    t0 = per_val_plain * n_params
    b0 = ctx.plaintext_bytes(n_params)
    for ratio in (0.0, 0.1, 0.3, 0.5, 0.7, 1.0):
        n_enc = int(n_params * ratio)
        t = per_ct * ctx.num_ciphertexts(n_enc) \
            + per_val_plain * (n_params - n_enc)
        comm = ctx.encrypted_bytes(n_enc) \
            + ctx.plaintext_bytes(n_params - n_enc)
        rows.append({"ratio": ratio, "comp_s": t, "comm_bytes": comm,
                     "comp_ratio": t / t0, "comm_ratio": comm / b0})
    return rows


def fig7(ratios=(0.1, 0.5, 1.0)):
    """Overhead vs selection ratio across paper model sizes (size model)."""
    ctx = ckks_params.make_context(n_poly=8192, n_limbs=2, delta_bits=26)
    rows = []
    for name, n in PAPER_MODELS[3::2]:
        for p in ratios:
            n_enc = int(n * p)
            rows.append({
                "model": name, "ratio": p,
                "comm_bytes": ctx.encrypted_bytes(n_enc)
                              + ctx.plaintext_bytes(n - n_enc)})
    return rows


def fig14a(client_counts=(2, 4, 8, 16, 32)):
    """Server aggregation cost vs number of clients."""
    ctx = ckks_params.make_context(n_poly=4096, n_limbs=2, delta_bits=26)
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    v = rng.randn(32, ctx.slots).astype(np.float32)
    ct = cipher.encrypt_coeffs(ctx, pk,
                               jnp.asarray(encoding.encode_np(v, ctx)),
                               jax.random.PRNGKey(1))
    rows = []
    for c in client_counts:
        data = jnp.broadcast_to(ct.data, (c,) + ct.data.shape)
        w = [1.0 / c] * c
        agg = jax.jit(lambda d: cipher.weighted_sum(
            ctx, cipher.Ciphertext(data=d, scale=ct.scale), w).data)
        rows.append({"clients": c, "t_agg_s": _time(agg, data)})
    return rows


def fig8(model_params=25_557_032, ratio=0.3, train_s=30.0):
    """ResNet-50-scale training-cycle decomposition at SAR bandwidth:
    plaintext vs HE-unoptimized vs HE w/ selective encryption."""
    ctx = ckks_params.make_context(n_poly=8192, n_limbs=2, delta_bits=26)
    base = _bench_agg(ctx, 64 * ctx.slots)
    per_ct = base["t_he"] / 64
    bw = BW_CASES["SAR"]
    rows = []
    for mode, p in (("plaintext", 0.0), ("he_full", 1.0),
                    ("he_selective", ratio)):
        n_enc = int(model_params * p)
        he_t = per_ct * ctx.num_ciphertexts(n_enc)
        comm_b = ctx.encrypted_bytes(n_enc) \
            + ctx.plaintext_bytes(model_params - n_enc)
        rows.append({
            "mode": mode, "train_s": train_s,
            "he_s": he_t, "comm_s": 2 * comm_b / bw,
            "total_s": train_s + he_t + 2 * comm_b / bw,
        })
    return rows


def dp_advantage(p_grid=(0.1, 0.3, 0.5, 0.7, 0.9)):
    """Empirical (1-p) vs (1-p)^2 privacy-budget law on synthetic
    sensitivities (Remarks 3.12-3.14)."""
    s = np.random.RandomState(0).rand(500_000)
    j = dp.epsilon_all_plaintext(s, b=1.0)
    rows = []
    for p in p_grid:
        out = dp.selection_advantage(s, p, b=1.0)
        rows.append({
            "p": p,
            "eps_random/J": out["eps_random"] / j,
            "eps_selective/J": out["eps_selective"] / j,
            "law_random": 1 - p,
            "law_selective": (1 - p) ** 2,
        })
    return rows
