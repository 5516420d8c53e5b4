"""Threshold-HE federated learning (paper Appendix B): no single client
holds the full secret key; decryption requires every party's partial
decryption (additive n-of-n) or any t of n (Shamir).

    PYTHONPATH=src python examples/threshold_fl.py
"""
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro import configs
from repro.core.ckks import cipher, encoding, threshold
from repro.core.ckks import params as ckks_params
from repro.core.secure_agg import AggregatorConfig
from repro.data import make_client_streams
from repro.fl import ClientConfig, FLClient, FLRunConfig, FLTask
from repro.launch import compile_cache


def microbenchmark(ctx):
    """Appendix-B style microbenchmark: single-key vs threshold FedAvg."""
    rng = np.random.RandomState(0)
    vals = rng.randn(8, ctx.slots).astype(np.float32)
    coeffs = jnp.asarray(encoding.encode_np(vals, ctx))

    # single key
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    t0 = time.time()
    ct = cipher.encrypt_coeffs(ctx, pk, coeffs, jax.random.PRNGKey(1))
    out = cipher.decrypt_values_np(ctx, sk, ct)
    t_single = time.time() - t0
    err_single = np.abs(out - vals).max()

    # two-party threshold
    parties, tpk = threshold.threshold_keygen(ctx, jax.random.PRNGKey(2), 2)
    t0 = time.time()
    ct = cipher.encrypt_coeffs(ctx, tpk, coeffs, jax.random.PRNGKey(3))
    partials = [threshold.partial_decrypt(ctx, p, ct,
                                          jax.random.PRNGKey(10 + i))
                for i, p in enumerate(parties)]
    out = encoding.decode_np(
        np.asarray(threshold.combine_partials(ctx, ct, partials)),
        ctx, ct.scale)
    t_thresh = time.time() - t0
    err_thresh = np.abs(out - vals).max()
    print(f"single-key: {t_single:.3f}s err={err_single:.2e} | "
          f"2-party threshold: {t_thresh:.3f}s err={err_thresh:.2e} "
          f"(smudging noise dominates)")


def main():
    compile_cache.enable()
    ctx = ckks_params.make_context(n_poly=2048, n_limbs=2, delta_bits=24)
    print("== threshold-HE microbenchmark (Appendix B / Figure 12) ==")
    microbenchmark(ctx)

    print("\n== threshold-HE federated training ==")
    cfg = dataclasses.replace(configs.get_config("qwen1.5-0.5b", smoke=True),
                              n_layers=2, d_model=64, d_ff=128, vocab=512)
    from repro.models import build_model
    model = build_model(cfg)
    streams = make_client_streams(3, cfg.vocab, seq_len=32, batch_size=4)
    clients = [FLClient(i, model, streams[i], ClientConfig(local_steps=4))
               for i in range(3)]
    task = FLTask(model, clients,
                  AggregatorConfig(p_ratio=0.2, strategy="top_p"),
                  FLRunConfig(n_rounds=4, threshold_mode=True, seed=0),
                  ctx=ctx)
    for l in task.run():
        print(f"round {l.round} loss={l.loss:.4f} "
              f"clients={l.n_participating}")
    print("threshold FL OK — no party ever held the full secret key")


if __name__ == "__main__":
    main()
