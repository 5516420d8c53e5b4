"""Benchmark harness: one entry per paper table/figure + kernel
microbenchmarks + the roofline summary table from dry-run artifacts.

  PYTHONPATH=src python -m benchmarks.run               # everything
  PYTHONPATH=src python -m benchmarks.run table4 fig8   # subset
  PYTHONPATH=src python -m benchmarks.run --help        # modes + env vars

Environment (full list in README.md "Environment variables & flags"):
  REPRO_HE_BACKEND=ref|pallas|pallas4   backend for every HE op (default
      ref; pallas4 = 4-step transpose NTT kernels)
  XLA_FLAGS=--xla_force_host_platform_device_count=<n>
      simulate <n> devices on one host; must be set before the first jax
      import.  `agg-sharded` and `uplink-sharded` spawn their own
      subprocess per device count, so they need no flags from the caller.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from repro.launch import compile_cache


def _rows(title, rows, keys=None):
    print(f"\n== {title} ==")
    if not rows:
        print("(no rows)")
        return
    keys = keys or list(rows[0].keys())
    print(",".join(keys))
    for r in rows:
        print(",".join(_fmt(r.get(k)) for k in keys))


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def _timeit(fn, *args, reps: int = 5):
    """Mean wall time of fn(*args) after one warmup call; blocks on every
    output leaf so async dispatch cannot fake speedups."""
    import jax

    def _block(x):
        return x.block_until_ready() if hasattr(x, "block_until_ready") \
            else x

    out = fn(*args)
    jax.tree_util.tree_map(_block, out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.tree_util.tree_map(_block, out)
    return (time.time() - t0) / reps


def bench_table4():
    """Paper Table 4: fully-encrypted aggregation vs plaintext."""
    from benchmarks import paper_tables
    _rows("Table 4: fully-encrypted aggregation vs plaintext",
          paper_tables.table4())


def bench_table6():
    """Paper Table 6: crypto parameter sweep."""
    from benchmarks import paper_tables
    _rows("Table 6: crypto parameter sweep", paper_tables.table6())


def bench_table7():
    """Paper Table 7: selective-encryption ratio sweep (ViT-sized)."""
    from benchmarks import paper_tables
    _rows("Table 7: selective-encryption ratio sweep (ViT-sized)",
          paper_tables.table7())


def bench_fig7():
    """Paper Figure 7: overhead vs selection ratio."""
    from benchmarks import paper_tables
    _rows("Figure 7: overhead vs selection ratio", paper_tables.fig7())


def bench_fig8():
    """Paper Figure 8: training-cycle decomposition (SAR bandwidth)."""
    from benchmarks import paper_tables
    _rows("Figure 8: training-cycle decomposition (SAR bandwidth)",
          paper_tables.fig8())


def bench_fig14a():
    """Paper Figure 14a: aggregation cost vs clients."""
    from benchmarks import paper_tables
    _rows("Figure 14a: aggregation cost vs clients", paper_tables.fig14a())


def bench_dp():
    """Remarks 3.12-3.14: privacy-budget laws."""
    from benchmarks import paper_tables
    _rows("Remarks 3.12-3.14: privacy-budget laws",
          paper_tables.dp_advantage())


def bench_kernels():
    """Microbenchmark the HE kernels (ref backend on CPU; Pallas interpret
    parity is asserted in tests)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.ckks import params as ckks_params
    from repro.kernels import ref

    rows = []
    for n_poly in (2048, 8192):
        ctx = ckks_params.make_context(n_poly=n_poly, n_limbs=2,
                                       delta_bits=26)
        lc = ctx.limbs[0]
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randint(0, lc.q, size=(64, n_poly))
                        .astype(np.uint32))
        tw = jnp.asarray(lc.psi_rev_mont)
        f = jax.jit(lambda x: ref.ntt_fwd(x, tw, jnp.uint32(lc.q),
                                          jnp.uint32(lc.qinv_neg)))
        f(x).block_until_ready()
        t0 = time.time()
        for _ in range(5):
            out = f(x)
        out.block_until_ready()
        dt = (time.time() - t0) / 5
        rows.append({"kernel": "ntt_fwd", "N": n_poly, "batch": 64,
                     "us_per_poly": dt / 64 * 1e6})
    _rows("Kernel microbenchmarks (ref backend, CPU)", rows)


def bench_he():
    """Limb-fused HE engine vs the per-limb dispatch baseline.

    The baseline reproduces the seed engine's execution model — an eager
    Python loop dispatching one single-limb kernel per RNS limb — against
    the fused engine's one-jitted-graph-per-op over u32[..., L, N].
    Emits BENCH_he.json (repo root) for the bench trajectory.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.ckks import cipher, encoding
    from repro.core.ckks import params as ckks_params
    from repro.kernels import ops, ref

    n_poly, n_limbs, n_clients, batch = 8192, 2, 8, 8
    ctx = ckks_params.make_context(n_poly=n_poly, n_limbs=n_limbs,
                                   delta_bits=26)
    t = ctx.tables
    rng = np.random.RandomState(0)

    def rand_limbed(shape):
        return jnp.asarray(ref.rand_limbed_np(rng, ctx, shape))

    timeit = _timeit

    # -- per-limb baselines: eager loop, one single-limb ref op per limb ----
    def per_limb_ntt_fwd(x):
        return jnp.stack(
            [ref.ntt_fwd(x[..., i, :], jnp.asarray(lc.psi_rev_mont),
                         np.uint32(lc.q), np.uint32(lc.qinv_neg))
             for i, lc in enumerate(ctx.limbs)], axis=-2)

    def per_limb_ntt_inv(x):
        return jnp.stack(
            [ref.ntt_inv(x[..., i, :], jnp.asarray(lc.psi_inv_rev_mont),
                         np.asarray(lc.n_inv_mont), np.uint32(lc.q),
                         np.uint32(lc.qinv_neg))
             for i, lc in enumerate(ctx.limbs)], axis=-2)

    def per_limb_weighted_sum(cts, w):
        return jnp.stack(
            [ref.he_weighted_sum(cts[..., i, :],
                                 w[:, i].reshape((n_clients, 1, 1)),
                                 np.uint32(lc.q), np.uint32(lc.qinv_neg))
             for i, lc in enumerate(ctx.limbs)], axis=-2)

    # -- fused engine: one jitted graph per op ------------------------------
    token = ops.backend_token()
    fused_ntt_fwd = jax.jit(lambda x: ops.ntt_fwd(x, ctx))
    fused_ntt_inv = jax.jit(lambda x: ops.ntt_inv(x, ctx))
    fused_weighted_sum = jax.jit(lambda c, w: ops.weighted_sum(c, w, ctx))

    x = rand_limbed((batch,))
    cts = rand_limbed((n_clients, batch))
    w_mont = jnp.asarray(encoding.encode_weights_mont(
        [1.0 / n_clients] * n_clients, ctx))

    from repro import obs
    rows, results = [], {"n_poly": n_poly, "n_limbs": n_limbs,
                         "n_clients": n_clients, "batch": batch,
                         "backend": ops.get_backend(), "token": str(token),
                         "provenance": obs.provenance(), "ops": {}}
    cases = [
        ("ntt_fwd", lambda: timeit(per_limb_ntt_fwd, x),
         lambda: timeit(fused_ntt_fwd, x)),
        ("ntt_inv", lambda: timeit(per_limb_ntt_inv, x),
         lambda: timeit(fused_ntt_inv, x)),
        ("weighted_sum", lambda: timeit(per_limb_weighted_sum, cts, w_mont),
         lambda: timeit(fused_weighted_sum, cts, w_mont)),
    ]
    for name, base_fn, fused_fn in cases:
        base_s, fused_s = base_fn(), fused_fn()
        rows.append({"op": name, "per_limb_ms": base_s * 1e3,
                     "fused_ms": fused_s * 1e3,
                     "speedup": base_s / fused_s})
        results["ops"][name] = {"per_limb_ms": base_s * 1e3,
                                "fused_ms": fused_s * 1e3,
                                "speedup": base_s / fused_s}

    # -- end-to-end encrypt/decrypt (fused jitted graphs) -------------------
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    vals = jnp.asarray(rng.randn(2, ctx.slots).astype(np.float32))
    coeffs = encoding.encode_jnp(vals, ctx)
    key = jax.random.PRNGKey(1)
    enc_s = timeit(lambda: cipher.encrypt_coeffs(ctx, pk, coeffs, key).data)
    ct = cipher.encrypt_coeffs(ctx, pk, coeffs, key)
    dec_s = timeit(lambda: cipher.decrypt_to_coeffs(ctx, sk, ct))
    for name, s in (("encrypt", enc_s), ("decrypt", dec_s)):
        rows.append({"op": name, "per_limb_ms": float("nan"),
                     "fused_ms": s * 1e3, "speedup": float("nan")})
        results["ops"][name] = {"fused_ms": s * 1e3}

    _merge_bench_he(results)
    _rows(f"HE engine: per-limb baseline vs limb-fused "
          f"(N={n_poly}, L={n_limbs}, C={n_clients}, backend="
          f"{ops.get_backend()}; BENCH_he.json written)", rows)


def _merge_bench_he(update: dict) -> None:
    """Merge keys into BENCH_he.json so `he` and `ntt` can each refresh
    their own section without clobbering the other's rows."""
    path = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "BENCH_he.json"))
    try:
        with open(path) as f:
            doc = json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        doc = {}
    doc.update(update)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def bench_ntt():
    """Flat limb-grid NTT kernel vs the 4-step transpose NTT ("pallas4")
    at N in {4096, 8192, 16384} x L in {1, 2, 3}, both directions.

    Both kernels run through their Pallas path (interpret mode on CPU, so
    the numbers track kernel structure/dispatch, not real TPU lane
    behaviour — DESIGN.md §10 explains where the 4-step layout wins on
    hardware).  Appends an "ntt4" section to BENCH_he.json.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.ckks import params as ckks_params
    from repro.kernels import ntt, ref

    batch, reps = 4, 3

    def timeit(fn, *args):
        return _timeit(fn, *args, reps=reps)

    interpret = jax.default_backend() == "cpu"
    rows = []
    for n_poly in (4096, 8192, 16384):
        for n_limbs in (1, 2, 3):
            ctx = ckks_params.make_context(
                n_poly=n_poly, n_limbs=n_limbs,
                delta_bits=12 if n_limbs == 1 else 26)
            t = ctx.tables
            rng = np.random.RandomState(0)
            x = jnp.asarray(ref.rand_limbed_np(rng, ctx, (batch,)))
            flat_fwd = jax.jit(lambda x, t=t: ntt.ntt_fwd_fused(
                x, t.psi_rev_mont, t.qs, t.qinv_negs, interpret=interpret))
            four_fwd = jax.jit(lambda x, t=t: ntt.ntt4_fwd_fused(
                x, t.ntt4_psi1_mont, t.ntt4_psi2_mont, t.ntt4_corr_mont,
                t.qs, t.qinv_negs, interpret=interpret))
            flat_inv = jax.jit(lambda x, t=t: ntt.ntt_inv_fused(
                x, t.psi_inv_rev_mont, t.n_inv_monts, t.qs, t.qinv_negs,
                interpret=interpret))
            four_inv = jax.jit(lambda x, t=t: ntt.ntt4_inv_fused(
                x, t.ntt4_psi1_inv_mont, t.ntt4_psi2_inv_mont,
                t.ntt4_corr_inv_mont, t.n_inv_monts, t.qs, t.qinv_negs,
                interpret=interpret))
            y = flat_fwd(x)
            parity = bool(
                np.array_equal(np.asarray(y), np.asarray(four_fwd(x)))
                and np.array_equal(np.asarray(flat_inv(y)),
                                   np.asarray(four_inv(y))))
            n1, n2 = ckks_params.ntt4_split(n_poly)
            rows.append({
                "n_poly": n_poly, "n_limbs": n_limbs, "split": f"{n1}x{n2}",
                "fwd_fused_ms": timeit(flat_fwd, x) * 1e3,
                "fwd_4step_ms": timeit(four_fwd, x) * 1e3,
                "inv_fused_ms": timeit(flat_inv, y) * 1e3,
                "inv_4step_ms": timeit(four_inv, y) * 1e3,
                "bit_parity": parity,
            })
    from repro import obs
    _merge_bench_he({"ntt4": {"batch": batch, "interpret": interpret,
                              "provenance": obs.provenance(),
                              "rows": rows}})
    _rows("NTT: flat limb-grid kernel vs 4-step transpose kernel "
          f"(batch={batch}, interpret={interpret}; BENCH_he.json "
          "'ntt4' section written)", rows)


def bench_wire():
    """Measured bytes-on-wire (repro.wire): serialized uplink per policy,
    streaming-ingest stats, and recovery error — real payloads, not the
    byte model."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import wire
    from repro.core.ckks import cipher
    from repro.core.ckks import params as ckks_params
    from repro.core.secure_agg import AggregatorConfig, SelectiveHEAggregator
    from repro.wire import stream as ws

    ctx = ckks_params.make_context(n_poly=1024, n_limbs=2, delta_bits=24)
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    model = {"w": jnp.asarray(rng.randn(4096, 4), jnp.float32)}
    sens = np.abs(rng.randn(4096 * 4))
    agg = SelectiveHEAggregator.build(
        ctx, model, sens, AggregatorConfig(p_ratio=0.1, strategy="top_p"))
    n_clients = 4
    clients = [jax.tree_util.tree_map(lambda x, i=i: x + 0.02 * i, model)
               for i in range(n_clients)]
    expect = jax.tree_util.tree_map(lambda *xs: sum(xs) / n_clients, *clients)
    naive = ctx.encrypted_bytes(agg.part.n_total, packed=False)
    est = agg.overhead_report()["bytes_total"]

    policies = [
        ("full_f32", False, "f32"),
        ("seeded_f32", True, "f32"),
        ("seeded_f16", True, "f16"),
        ("seeded_i8", True, "i8"),
    ]
    rows = []
    for name, seed_cts, codec in policies:
        blobs = []
        for i, m in enumerate(clients):
            key = jax.random.PRNGKey(100 + i)
            if seed_cts:
                upd = agg.client_protect_seeded(m, sk, key, a_seed=7000 + i)
                sct = wire.seed_compress(upd.ct, 7000 + i)
            else:
                upd, sct = agg.client_protect(m, pk, key), None
            blobs.append(ws.pack_update_frames(
                upd, cid=i, n_samples=4, rnd=0, seeded=sct,
                plain_codec=codec))
        ingest = ws.StreamIngest(ctx)
        for b in blobs:
            ingest.ingest(b, 1.0 / n_clients)
        rec = agg.client_recover_params(ingest.finalize(), sk)
        err = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(rec),
            jax.tree_util.tree_leaves(expect)))
        per_client = len(blobs[0])
        rows.append({
            "policy": name,
            "measured_B_per_client": per_client,
            "estimated_B_per_client": est,
            "vs_naive_all_enc": naive / per_client,
            "peak_chunk_buffers": ingest.peak_chunk_buffers,
            "recover_err": err,
        })
    _rows("Wire: measured bytes-on-wire per client "
          f"(N={ctx.n_poly}, {n_clients} clients, p=0.1, "
          f"naive all-encrypted = {naive} B)", rows)


def _run_sharded_workers(module: str, bench: str, artifact: str,
                         ndevs=(1, 2, 8)) -> dict:
    """Shared scaffold for the per-device-count benchmarks.

    On the CPU, jax locks the device count at first init, so each point
    runs `module` in its own subprocess with JAX_PLATFORMS=cpu and
    XLA_FLAGS=--xla_force_host_platform_device_count=<n>, and collects the
    worker's last stdout line as JSON.  That needs a parent that has not
    imported JAX: the device count is fixed by then, and on a TPU host a
    parent holding the chip would leave the children none.  So with JAX
    imported this refuses to spawn — except on a TPU, where every point
    that fits the chips runs in this process over jax.devices()[:n],
    with no forced host devices.

    Writes {bench, per_devices} to `artifact` (repo root) only if EVERY
    point run succeeded — a partial artifact would silently shrink the
    README table.  Returns per_devices.
    """
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    per_dev = {}
    if "jax" in sys.modules:
        import importlib

        import jax
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                f"{bench}: refusing to spawn device-count workers from a "
                "process that has already imported JAX — its device count "
                "is fixed and a child cannot share its devices.  Run "
                f"`python -m benchmarks.run {bench.replace('_', '-')}` in "
                "a fresh process (benchmarks.run schedules these modes "
                "before any mode that imports JAX).")
        worker = importlib.import_module(module)
        n_chips = len(jax.devices())
        for ndev in ndevs:
            if ndev <= n_chips:
                per_dev[str(ndev)] = worker.measure(
                    worker.parser().parse_args(["--devices", str(ndev)]))
        if not per_dev:
            raise RuntimeError(f"{bench}: no point of {ndevs} fits "
                               f"{n_chips} TPU chips")
    else:
        for ndev in ndevs:
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={ndev}"
            env["PYTHONPATH"] = os.path.join(root, "src") + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
                else "")
            proc = subprocess.run(
                [sys.executable, "-m", module, "--devices", str(ndev)],
                cwd=root, env=env, capture_output=True, text=True)
            out_lines = proc.stdout.strip().splitlines()
            if proc.returncode != 0 or not out_lines:
                raise RuntimeError(
                    f"{bench} worker ndev={ndev} failed ({artifact} left "
                    f"untouched):\n{proc.stdout}\n{proc.stderr}")
            per_dev[str(ndev)] = json.loads(out_lines[-1])
    from repro import obs
    with open(os.path.join(root, artifact), "w") as f:
        json.dump({"bench": bench, "provenance": obs.provenance(),
                   "per_devices": per_dev}, f, indent=2)
    return per_dev


def bench_agg_sharded():
    """Multi-chip sharded HE aggregation vs the single-device fused engine.

    Subprocess per device count (see _run_sharded_workers).  Records
    sharded vs single-device weighted_sum, the streaming-ingest flush (one
    chunk-batched accumulate launch per update), and bit-parity flags.
    Emits BENCH_agg_sharded.json (repo root).
    """
    per_dev = _run_sharded_workers("benchmarks.agg_sharded", "agg_sharded",
                                   "BENCH_agg_sharded.json")
    rows = []
    for ndev in sorted(per_dev, key=int):
        r = per_dev[ndev]
        rows.append({
            "devices": int(ndev), "mesh": str(r["mesh"]),
            "ws_single_ms": r["weighted_sum_single_ms"],
            "ws_sharded_ms": r["weighted_sum_sharded_ms"],
            "parity": r["sharded_parity"],
            "ingest_ms": r["stream_ingest_single_ms"],
            "ingest_sharded_ms": r["stream_ingest_sharded_ms"],
            "launches_per_update": r["launches_per_update"],
        })
    _rows("Sharded HE aggregation: 1/2/8 host devices vs single-device "
          "fused baseline (BENCH_agg_sharded.json written)", rows)


def bench_uplink_sharded():
    """Sharded client uplink (seeded encrypt) vs the single-device path.

    Times `ShardedHe.encrypt_values_seeded` (weights -> seeded ciphertext,
    chunks sharded over `data`, limbs over `model`) against
    `cipher.encrypt_values_seeded`, plus the pk encrypt pair and the
    measured seeded-vs-full frame bytes.  Subprocess per device count (see
    _run_sharded_workers).  Emits BENCH_uplink_sharded.json (repo root).
    """
    per_dev = _run_sharded_workers("benchmarks.uplink_sharded",
                                   "uplink_sharded",
                                   "BENCH_uplink_sharded.json")
    rows = []
    for ndev in sorted(per_dev, key=int):
        r = per_dev[ndev]
        rows.append({
            "devices": int(ndev), "mesh": str(r["mesh"]),
            "seeded_single_ms": r["encrypt_seeded_single_ms"],
            "seeded_sharded_ms": r["encrypt_seeded_sharded_ms"],
            "pk_single_ms": r["encrypt_pk_single_ms"],
            "pk_sharded_ms": r["encrypt_pk_sharded_ms"],
            "parity": r["sharded_parity"],
            "uplink_ratio": r["uplink_ratio"],
        })
    _rows("Sharded client uplink: seeded encrypt at 1/2/8 host devices vs "
          "single-device (BENCH_uplink_sharded.json written)", rows)


def bench_tune(smoke: bool = False):
    """Autotuner sweep (kernels/tune.py): measure every launch-config
    candidate per (op, N, L, B) point, record the winners.

    Full mode writes BENCH_tune.json (repo root) — the default-vs-tuned
    table the README renders — and saves the tuning cache to
    REPRO_HE_TUNE_CACHE (falling back to tuning/<platform>.json) for
    `REPRO_HE_BACKEND=auto` runs.  `--smoke` sweeps one tiny point per op
    with reps=1 and touches no repo artifacts (the cache still goes to
    REPRO_HE_TUNE_CACHE if set) — the CI docs job uses it to exercise the
    sweep -> save -> load path end to end.
    """
    import jax
    from repro import obs
    from repro.core.ckks import params as ckks_params
    from repro.kernels import ops, tune

    if smoke:
        points = [(64, 2, 4)]
        op_names = ("ntt_fwd", "mul_add")
        reps = 1
    else:
        points = [(2048, 2, 8), (8192, 2, 8)]
        op_names = ops.OPS
        reps = 2

    tune.clear_cache()
    rows = []
    for n_poly, n_limbs, b in points:
        ctx = ckks_params.make_context(
            n_poly=n_poly, n_limbs=n_limbs,
            delta_bits=20 if n_poly <= 256 else 26)
        for op in op_names:
            res = tune.sweep_op(op, ctx, b=b, reps=reps)
            rows.append(res.to_row())

    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    cache_out = tune.cache_path()
    if cache_out is None and not smoke:
        cache_out = os.path.join(root, "tuning",
                                 f"{jax.default_backend()}.json")
        os.makedirs(os.path.dirname(cache_out), exist_ok=True)
    if cache_out:
        tune.save_cache(cache_out)
        # prove the round trip: what we just wrote must resolve identically
        n_loaded = tune.load_cache(cache_out)
        assert n_loaded == len(rows), (n_loaded, len(rows))

    if not smoke:
        with open(os.path.join(root, "BENCH_tune.json"), "w") as f:
            json.dump({"provenance": obs.provenance(),
                       "interpret": jax.default_backend() == "cpu",
                       "cache": cache_out, "rows": rows}, f, indent=2)
            f.write("\n")

    regressions = [r for r in rows if r["tuned_ms"] > r["default_ms"]]
    assert not regressions, regressions  # winner includes the default
    _rows("Autotuner sweep: default vs tuned per (op, N, L, B) "
          + ("[smoke — no artifacts]" if smoke
             else "(BENCH_tune.json + tuning cache written)"),
          rows, keys=["op", "n", "l", "b", "backend", "default_ms",
                      "tuned_ms", "speedup", "candidates", "pruned"])


def bench_uplink_hybrid(smoke: bool = False):
    """Transcipher (hybrid-HE) thin-client uplink vs the seeded-CKKS
    client: measured client-side encrypt wall-time, modeled client FLOPs,
    and measured frame bytes, plus the bit-parity of the two aggregates
    through StreamIngest (DESIGN.md §15).  Full mode writes
    BENCH_uplink_hybrid.json (repo root); --smoke shrinks the shapes and
    touches no repo artifacts.
    """
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import obs
    from repro.core.ckks import cipher, encoding
    from repro.core.ckks import params as ckks_params
    from repro.core.ckks import transcipher as tc
    from repro.wire import compress as wc
    from repro.wire import stream as ws

    if smoke:
        n_poly, n_limbs, delta_bits, n_chunks, reps = 256, 2, 20, 2, 1
    else:
        n_poly, n_limbs, delta_bits, n_chunks, reps = 2048, 2, 24, 32, 5
    ctx = ckks_params.make_context(n_poly=n_poly, n_limbs=n_limbs,
                                   delta_bits=delta_bits)
    sk, _pk = cipher.keygen(ctx, jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    values = rng.randn(n_chunks, ctx.slots).astype(np.float32) * 0.05
    plain = rng.randn(64).astype(np.float32)
    vals_j = jnp.asarray(values)
    key = jax.random.PRNGKey(7)
    a_seed, cid, rnd = 9001, 0, 0

    # modeled client arithmetic (documented, not measured): both paths pay
    # the length-2N encode FFT (~5 n log2 n real flops); the seeded client
    # additionally runs L forward NTTs per chunk (N/2 log2 N butterflies,
    # ~8 int-ops each: one Montgomery modmul + two modadds) plus the RNS
    # noise/rounding stack the model ignores — so the ratio is a floor.
    fft_flops = 5.0 * (2 * n_poly) * math.log2(2 * n_poly)
    ntt_flops = n_limbs * (n_poly / 2) * math.log2(n_poly) * 8
    flops_seeded = n_chunks * (fft_flops + ntt_flops)
    flops_masked = n_chunks * fft_flops

    rows, per_derive = [], {}
    for dname, derive in (("fold_chunk", wc.DERIVE_FOLD_CHUNK),
                          ("ctr", wc.DERIVE_CTR)):
        cm, sm = tc.provision(ctx, sk, key, a_seed, n_chunks, derive=derive)

        def seeded_client():
            return cipher.encrypt_values_seeded(ctx, sk, vals_j, key, a_seed,
                                                derive=derive).data

        def masked_client():
            return tc.mask_values(ctx, cm, values)

        t_seeded = _timeit(seeded_client, reps=reps)
        t_masked = _timeit(masked_client, reps=reps)

        # measured wire frames, both directions of the acceptance invariant
        coeffs = jnp.asarray(encoding.encode_np(values, ctx))
        ct_ref = cipher.encrypt_coeffs_seeded(ctx, sk, coeffs, key, a_seed,
                                              derive=derive)
        from repro.core.secure_agg import ProtectedUpdate
        blob_seeded = ws.pack_update_frames(
            ProtectedUpdate(ct=ct_ref, plain=jnp.asarray(plain)),
            cid=cid, n_samples=1, rnd=rnd,
            seeded=wc.seed_compress(ct_ref, a_seed, derive))
        mc = wc.MaskedChunk(masked=masked_client(), a_seed=a_seed,
                            scale=cm.scale, derive=derive)
        blob_masked = ws.pack_masked_update_frames(
            mc, wc.seed_compress(cm.seed_ct, cm.escrow_a_seed, derive),
            plain, cid=cid, n_samples=1, rnd=rnd)

        ing_a = ws.StreamIngest(ctx)
        ing_a.ingest(blob_seeded, 1.0)
        ing_b = ws.StreamIngest(ctx,
                                transcipher_materials={(cid, rnd): sm})
        ing_b.ingest(blob_masked, 1.0)
        parity = bool(np.array_equal(
            np.asarray(ing_a.finalize().ct.data),
            np.asarray(ing_b.finalize().ct.data)))

        r = {
            "derive": dname,
            "seeded_encrypt_ms": t_seeded * 1e3,
            "masked_encrypt_ms": t_masked * 1e3,
            "encrypt_speedup": t_seeded / t_masked,
            "client_mflops_seeded": flops_seeded / 1e6,
            "client_mflops_masked": flops_masked / 1e6,
            "seeded_B": len(blob_seeded),
            "masked_B": len(blob_masked),
            "uplink_ratio": len(blob_masked) / len(blob_seeded),
            "model_ct_B": tc.seeded_uplink_bytes(n_chunks, n_limbs, n_poly),
            "model_masked_B": tc.masked_uplink_bytes(n_chunks, n_poly),
            "bit_parity": parity,
        }
        assert parity, f"transcipher/seeded aggregate bits differ ({dname})"
        rows.append(r)
        per_derive[dname] = r

    if not smoke:
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        with open(os.path.join(root, "BENCH_uplink_hybrid.json"), "w") as f:
            json.dump({"bench": "uplink_hybrid",
                       "provenance": obs.provenance(),
                       "n_poly": n_poly, "n_limbs": n_limbs,
                       "n_chunks": n_chunks, "delta_bits": delta_bits,
                       "reps": reps, "per_derive": per_derive}, f, indent=2)
            f.write("\n")

    _rows("Hybrid (transcipher) uplink vs seeded CKKS client "
          f"(N={n_poly}, L={n_limbs}, chunks={n_chunks}"
          + (" [smoke — no artifacts]" if smoke
             else "; BENCH_uplink_hybrid.json written") + ")",
          rows)


def bench_roofline():
    """Summarize dry-run artifacts (run repro.launch.dryrun first)."""
    art_dir = os.path.join(os.path.dirname(__file__), "artifacts")
    rows = []
    if os.path.isdir(art_dir):
        for fn in sorted(os.listdir(art_dir)):
            if not fn.endswith(".json"):
                continue
            a = json.load(open(os.path.join(art_dir, fn)))
            r = a["roofline"]
            rows.append({
                "arch": a["arch"], "shape": a["shape"], "mesh": a["mesh"],
                "tag": a.get("tag", ""),
                "compute_ms": r["compute_s"] * 1e3,
                "memory_ms": r["memory_s"] * 1e3,
                "collective_ms": r["collective_s"] * 1e3,
                "dominant": r["dominant"],
                "flops_ratio": r["flops_ratio"],
                "roofline_frac": r["roofline_fraction"],
            })
    _rows("Roofline terms from dry-run artifacts", rows)


def bench_selective(smoke: bool = False):
    """Paper-scale selective encryption end to end (benchmarks/selective.py):
    fine-tune -> sensitivity -> HE mask agreement -> partitioned seeded wire
    -> sharded streaming aggregation -> recover, swept over p; full mode
    writes BENCH_selective.json."""
    from benchmarks.selective import run_selective

    run_selective(smoke=smoke)


def bench_serve(smoke: bool = False):
    """Aggregation-service sustained updates/sec (benchmarks/serve.py):
    10k simulated clients per round, partial quorum (seal at target,
    stragglers dropped), background worker folding round r while round
    r+1 submits; full mode writes BENCH_serve.json."""
    from benchmarks.serve import run_serve

    run_serve(smoke=smoke)


ALL = {
    "table4": bench_table4,
    "table6": bench_table6,
    "table7": bench_table7,
    "fig7": bench_fig7,
    "fig8": bench_fig8,
    "fig14a": bench_fig14a,
    "dp": bench_dp,
    "kernels": bench_kernels,
    "he": bench_he,
    "ntt": bench_ntt,
    "wire": bench_wire,
    "agg-sharded": bench_agg_sharded,
    "uplink-sharded": bench_uplink_sharded,
    "uplink-hybrid": bench_uplink_hybrid,
    "tune": bench_tune,
    "roofline": bench_roofline,
    "selective": bench_selective,
    "serve": bench_serve,
}


def main() -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        description="FedML-HE reproduction benchmark harness.",
        epilog="modes:\n" + "\n".join(
            f"  {name:<12} "
            + ((fn.__doc__ or "").strip().splitlines() or [""])[0]
            for name, fn in ALL.items())
        + "\n\nenvironment (canonical list: README.md 'Environment "
          "variables & flags'):\n"
          "  REPRO_HE_BACKEND=ref|pallas|pallas4|auto\n"
          "      backend for every HE op (default ref; pallas runs the\n"
          "      kernels in interpret mode on CPU; pallas4 swaps the NTT\n"
          "      family for the 4-step transpose kernels, DESIGN.md §10;\n"
          "      auto resolves per op/shape from the tuning cache,\n"
          "      DESIGN.md §12)\n"
          "  REPRO_HE_TUNE_CACHE=path\n"
          "      JSON tuning cache for the 'tune' mode and auto backend\n"
          "  XLA_FLAGS=--xla_force_host_platform_device_count=<n>\n"
          "      simulate <n> host devices; must be set before the first\n"
          "      jax import ('agg-sharded' / 'uplink-sharded' manage this\n"
          "      themselves via subprocess workers)\n"
          "  REPRO_WIRE_VERSION=1|2\n"
          "      pin the wire emit version (default 2; 1 = legacy layout\n"
          "      for staged rollouts)\n"
          "  REPRO_UPLINK_MODE=auto|full|seeded|transcipher\n"
          "      default uplink path for FLClient.protect_and_pack\n"
          "      (transcipher = thin-client hybrid-HE, DESIGN.md §15)")
    ap.add_argument("modes", nargs="*", metavar="mode",
                    help="benchmark modes to run (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tune/selective/serve modes: tiny sweep, no repo "
                         "artifacts (CI exercises the full code path)")
    args = ap.parse_args()
    compile_cache.enable()
    names = args.modes or list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        ap.error(f"unknown mode(s) {unknown}; choose from {list(ALL)}")
    # the worker-spawning modes first: they need a parent without JAX
    names.sort(key=lambda n: n not in ("agg-sharded", "uplink-sharded"))
    for n in names:
        t0 = time.time()
        if n in ("tune", "selective", "serve", "uplink-hybrid"):
            ALL[n](smoke=args.smoke)
        else:
            ALL[n]()
        print(f"[{n} done in {time.time()-t0:.1f}s]")


if __name__ == "__main__":
    main()
