"""Worker for `benchmarks/run.py agg-sharded`: one host-device count per
process.

jax locks the device count at first initialization, so each measurement
point runs in its own subprocess with

    XLA_FLAGS=--xla_force_host_platform_device_count=<n>

set by the parent (see README.md "Environment variables & flags").  The
worker times

  * sharded vs single-device fused `weighted_sum` (the server hot loop);
  * streaming ingest of serialized-shape chunk batches through the
    chunk-batched `weighted_accum_chunks` flush, recording the launch
    count per flush;

and prints one JSON object on the last stdout line for the parent to
collect into BENCH_agg_sharded.json.
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
    ap.add_argument("--n-clients", type=int, default=8)
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
    from repro.kernels import ops, ref
    from repro.launch.mesh import make_he_mesh
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

    # -- weighted_sum: sharded vs single-device fused -----------------------
    raw = ref.rand_limbed_np(rng, ctx, (args.n_clients, args.n_chunks, 2))
    data = jnp.asarray(np.moveaxis(raw, -2, -3))   # [C, chunks, L, 2, N]
    cts = cipher.Ciphertext(data=data, scale=float(ctx.delta))
    weights = [1.0 / args.n_clients] * args.n_clients

    single_s = timeit(lambda: cipher.weighted_sum(ctx, cts, weights).data)
    sharded_s = timeit(lambda: eng.weighted_sum(cts, weights).data)
    parity = bool(np.array_equal(
        np.asarray(cipher.weighted_sum(ctx, cts, weights).data),
        np.asarray(eng.weighted_sum(cts, weights).data)))

    # -- streaming ingest: chunk-batched flush ------------------------------
    upd_data = [jnp.asarray(np.moveaxis(
        ref.rand_limbed_np(rng, ctx, (args.n_chunks, 2)), -2, -3))
        for _ in range(args.n_clients)]

    from repro.core.secure_agg import ProtectedUpdate

    def run_ingest(sharded):
        ing = ws.StreamIngest(ctx, sharded=sharded)
        for d in upd_data:
            ing.ingest_update(
                ProtectedUpdate(ct=cipher.Ciphertext(
                    data=d, scale=float(ctx.delta)),
                    plain=jnp.zeros((0,), jnp.float32)),
                1.0 / args.n_clients)
        out = ing.finalize()
        out.ct.data.block_until_ready()
        return ing

    ing = run_ingest(None)                      # warm the jitted flush
    t0 = time.time()
    ing = run_ingest(None)
    ingest_single_s = time.time() - t0
    launches_per_update = ing.accum_launches / max(1, ing.clients_ingested)
    run_ingest(eng)                             # warm the sharded flush
    t0 = time.time()
    run_ingest(eng)
    ingest_sharded_s = time.time() - t0

    result = {
        "devices": args.devices,
        "mesh": dict(mesh.shape),
        "n_poly": args.n_poly,
        "n_limbs": args.n_limbs,
        "n_clients": args.n_clients,
        "n_chunks": args.n_chunks,
        "backend": ops.get_backend(),
        "weighted_sum_single_ms": single_s * 1e3,
        "weighted_sum_sharded_ms": sharded_s * 1e3,
        "sharded_parity": parity,
        "stream_ingest_single_ms": ingest_single_s * 1e3,
        "stream_ingest_sharded_ms": ingest_sharded_s * 1e3,
        "accum_launches": ing.accum_launches,
        "clients_ingested": ing.clients_ingested,
        "launches_per_update": launches_per_update,
    }
    return result


def main() -> None:
    print(json.dumps(measure(parser().parse_args())))


if __name__ == "__main__":
    sys.exit(main())
