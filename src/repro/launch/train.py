"""End-to-end training driver.

Builds the full config of --arch (its published widths); --smoke builds
the reduced one, which is what runs on a CPU.  Includes checkpoint/restart fault tolerance: kill
the process mid-run and re-launch — it resumes from the last checkpoint.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 50 --batch 8 --seq 32 --ckpt-dir /tmp/run1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data import SyntheticLM, dirichlet_partition
from repro.launch import compile_cache, steps
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.models.sharding import axis_env_from_mesh
from repro.optim import AdamWConfig, adamw_init


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="reduced-width config (default: the full config)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def main():
    args = parser().parse_args()
    compile_cache.enable()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh()
    with jax.sharding.set_mesh(mesh):
        ax = axis_env_from_mesh(mesh)
        model = build_model(cfg, ax)
        params = model.init(jax.random.PRNGKey(0))
        opt_state = adamw_init(params)
        step_fn = jax.jit(steps.make_train_step(
            model, AdamWConfig(lr=args.lr), warmup=10,
            total_steps=args.steps), donate_argnums=(0, 1))

        start = 0
        mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
        if mgr:
            tree, s, _ = mgr.restore({"p": params, "o": opt_state})
            if tree is not None:
                params = jax.tree_util.tree_map(jnp.asarray, tree["p"])
                opt_state = jax.tree_util.tree_map(jnp.asarray, tree["o"])
                start = s + 1
                print(f"resumed from step {s}")

        prior = dirichlet_partition(1, cfg.vocab, alpha=100.0)[0]
        stream = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                             batch_size=args.batch, client_prior=prior)
        t0 = time.time()
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     stream.next_batch().items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                tok_s = args.batch * args.seq * (step - start + 1) \
                    / max(1e-9, time.time() - t0)
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} tok/s={tok_s:.0f}")
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save(step, {"p": params, "o": opt_state})
        if mgr:
            mgr.save(args.steps - 1, {"p": params, "o": opt_state})
    print("done")


if __name__ == "__main__":
    main()
