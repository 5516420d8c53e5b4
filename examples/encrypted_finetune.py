"""End-to-end driver: federated training of a ~100M-param LM with
HE-protected aggregation for a few hundred local steps total.

Runs the full paper pipeline (Figure 3): threshold-free key agreement ->
sensitivity maps -> HE mask agreement -> encrypted FedAvg rounds, with
dropout + checkpointing enabled.

    PYTHONPATH=src python examples/encrypted_finetune.py [--rounds 20]
    (defaults sized to finish on a laptop CPU; --big uses the ~100M model)
"""
import argparse
import dataclasses
import time

import numpy as np
import jax

from repro import configs
from repro.core.ckks import params as ckks_params
from repro.core.secure_agg import AggregatorConfig
from repro.data import make_client_streams
from repro.fl import ClientConfig, FLClient, FLRunConfig, FLTask
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--p-ratio", type=float, default=0.1)
    ap.add_argument("--big", action="store_true",
                    help="~100M-param model (slow on CPU)")
    ap.add_argument("--ckpt-dir", default="/tmp/fedml_he_finetune")
    args = ap.parse_args()

    if args.big:
        cfg = ModelConfig(
            name="lm-100m", family="dense", n_layers=8, d_model=768,
            n_heads=12, n_kv_heads=12, d_ff=2048, vocab=32000,
            tie_embeddings=True, attn_chunk=256)
    else:
        cfg = dataclasses.replace(
            configs.get_config("qwen1.5-0.5b", smoke=True),
            n_layers=2, d_model=128, d_ff=256, vocab=2048)
    model = build_model(cfg)
    print(f"model {cfg.name}: {cfg.param_count()/1e6:.1f}M params")

    streams = make_client_streams(args.clients, cfg.vocab,
                                  seq_len=args.seq, batch_size=args.batch,
                                  alpha=0.5, seed=0)
    clients = [FLClient(i, model, streams[i],
                        ClientConfig(local_steps=args.local_steps, lr=1e-3,
                                     sensitivity_probes=2))
               for i in range(args.clients)]

    ctx = ckks_params.make_context(n_poly=2048, n_limbs=2, delta_bits=24)
    task = FLTask(
        model, clients,
        AggregatorConfig(p_ratio=args.p_ratio, strategy="top_p"),
        FLRunConfig(n_rounds=args.rounds, dropout_prob=0.05,
                    ckpt_dir=args.ckpt_dir, ckpt_every=2, seed=0),
        ctx=ctx)

    t0 = time.time()
    task.agree_encryption_mask()
    rep = task.aggregator.overhead_report()
    print(f"mask agreed in {time.time()-t0:.1f}s: "
          f"{rep['n_enc']}/{rep['n_total']} params encrypted "
          f"({rep['ratio']:.0%}), {rep['n_ciphertexts']} cts/client, "
          f"comm {rep['bytes_total']/1e6:.1f}MB vs "
          f"{rep['bytes_all_plain']/1e6:.1f}MB plaintext "
          f"({rep['comm_ratio']:.2f}x)")

    logs = task.run()
    for l in logs:
        print(f"round {l.round:3d} loss={l.loss:.4f} "
              f"clients={l.n_participating} dropped={l.n_dropped} "
              f"comm={l.comm_bytes/1e6:.1f}MB wall={l.wall_s:.1f}s")
    total_steps = args.rounds * args.clients * args.local_steps
    print(f"total local steps {total_steps}; "
          f"loss {logs[0].loss:.3f} -> {logs[-1].loss:.3f}")


if __name__ == "__main__":
    main()
