"""Worker for `benchmarks/run.py uplink-sharded`: one host-device count per
process.

jax locks the device count at first initialization, so each measurement
point runs in its own subprocess with

    XLA_FLAGS=--xla_force_host_platform_device_count=<n>

set by the parent (see README.md "Environment variables & flags").  The
worker times the CLIENT uplink hot path:

  * single-device vs sharded `encrypt_values_seeded` (weights -> seeded
    ciphertext, encode FFT + sampling + NTTs in one dispatch; chunks shard
    along ``data``, limbs along ``model``);
  * frame packing of the seeded update (seed, c0 chunks) vs the full
    ciphertext, recording measured bytes per update for both;

asserts bit-parity between the sharded and single-device ciphertexts, and
prints one JSON object on the last stdout line for the parent to collect
into BENCH_uplink_sharded.json.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, required=True,
                    help="devices of this point (host devices on the CPU)")
    ap.add_argument("--n-poly", type=int, default=2048)
    ap.add_argument("--n-limbs", type=int, default=2)
    ap.add_argument("--n-chunks", type=int, default=8)
    ap.add_argument("--reps", type=int, default=10)
    return ap


def measure(args: argparse.Namespace) -> dict:
    """One measurement point on jax.devices()[:args.devices]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ckks import cipher, params as ckks_params
    from repro.core.ckks.sharded import ShardedHe
    from repro.core.secure_agg import ProtectedUpdate
    from repro.kernels import ops
    from repro.launch.mesh import make_he_mesh
    from repro.wire import compress as wc
    from repro.wire import stream as ws

    assert jax.device_count() >= args.devices, (
        f"worker expected {args.devices} devices, found "
        f"{jax.device_count()}; on the CPU the parent must set "
        "XLA_FLAGS=--xla_force_host_platform_device_count")

    ctx = ckks_params.make_context(n_poly=args.n_poly, n_limbs=args.n_limbs,
                                   delta_bits=26)
    mesh = make_he_mesh(args.n_limbs, args.devices)
    eng = ShardedHe(ctx, mesh)
    rng = np.random.RandomState(0)
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    vals = jnp.asarray(
        rng.randn(args.n_chunks, ctx.slots).astype(np.float32)) * 0.1
    key = jax.random.PRNGKey(1)
    a_seed = 4242

    def timeit(fn, *a, reps=args.reps):
        out = fn(*a)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
        t0 = time.time()
        for _ in range(reps):
            out = fn(*a)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready()
            if hasattr(x, "block_until_ready") else x, out)
        return (time.time() - t0) / reps

    # -- seeded encrypt: sharded vs single-device fused ---------------------
    single_s = timeit(
        lambda: cipher.encrypt_values_seeded(ctx, sk, vals, key, a_seed).data)
    sharded_s = timeit(
        lambda: eng.encrypt_values_seeded(sk, vals, key, a_seed).data)
    ct1 = cipher.encrypt_values_seeded(ctx, sk, vals, key, a_seed)
    ct2 = eng.encrypt_values_seeded(sk, vals, key, a_seed)
    parity = bool(np.array_equal(np.asarray(ct1.data), np.asarray(ct2.data)))

    # -- pk-path encrypt (also data-sharded now) ----------------------------
    pk_single_s = timeit(
        lambda: cipher.encrypt_values(ctx, pk, vals, key).data)
    pk_sharded_s = timeit(lambda: eng.encrypt_values(pk, vals, key).data)

    # -- wire: seeded vs full frame bytes for the same update ---------------
    upd = ProtectedUpdate(ct=ct2, plain=jnp.zeros((0,), jnp.float32))
    sct = wc.seed_compress(ct2, a_seed)
    blob_seeded = ws.pack_update_frames(upd, cid=0, n_samples=1, rnd=0,
                                        seeded=sct)
    blob_full = ws.pack_update_frames(upd, cid=0, n_samples=1, rnd=0)

    result = {
        "devices": args.devices,
        "mesh": dict(mesh.shape),
        "n_poly": args.n_poly,
        "n_limbs": args.n_limbs,
        "n_chunks": args.n_chunks,
        "backend": ops.get_backend(),
        "encrypt_seeded_single_ms": single_s * 1e3,
        "encrypt_seeded_sharded_ms": sharded_s * 1e3,
        "encrypt_pk_single_ms": pk_single_s * 1e3,
        "encrypt_pk_sharded_ms": pk_sharded_s * 1e3,
        "sharded_parity": parity,
        "seeded_bytes_per_update": len(blob_seeded),
        "full_bytes_per_update": len(blob_full),
        "uplink_ratio": len(blob_seeded) / len(blob_full),
    }
    return result


def main() -> None:
    print(json.dumps(measure(parser().parse_args())))


if __name__ == "__main__":
    sys.exit(main())
