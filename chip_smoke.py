"""One encrypted federated round of qwen1.5-0.5b on a TPU — the quickest
proof that the system runs on the chip.

    python chip_smoke.py [--seed 0]        # phases (a)-(c), one chip
    python chip_smoke.py --chips 4         # phase (d) only, four chips

Phases run in order in this one process, and none catches another's
failure:

  (a) device  — jax.devices()[0] must be a TPU (there is no CPU fallback);
  (b) gold    — every vector of tests/golden/ckks_kats.json reproduced
                bit-exactly under the ref, pallas and pallas4 backends,
                with the Pallas kernels compiled for the chip;
  (c) round   — repro.fl.FLTask at the published widths of qwen1.5-0.5b
                (configs.get_config FULL): 2 clients x 2 local AdamW steps
                on synthetic streams, N=8192 / L=2 CKKS, p=0.1 random
                selective encryption, the seeded wire path, the pallas
                backend; the decrypted encrypted partition must match the
                plaintext FedAvg within the CKKS bound and the plaintext
                partition must match exactly;
  (d) sharded — with --chips 4 only: sharded encrypt (pk and seeded) and
                chunk-batched ingest of a (c)-sized encrypted partition on
                a (data=2, model=2) mesh, bit-for-bit against one device,
                shards on 4 distinct devices, no collective in the
                compiled encrypt graphs.

Everything comes from --seed; nothing is read from outside the checkout.
The last stdout line is the JSON result; every other line comes before it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")
TESTS = os.path.join(REPO, "tests")

# the CKKS bound the repository's FedAvg tests hold decrypted aggregates to
# (tests/test_core.py, tests/test_ckks.py)
CKKS_ATOL = 2e-2
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def log(msg: str) -> None:
    print(msg, flush=True)


def _ready(x):
    import jax
    return jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def phase_device(want_count: int) -> dict:
    import importlib.metadata

    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (devices[0] is "
                         f"{d.platform!r}); this check runs on the chip only")
    if len(devs) < want_count:
        raise SystemExit(f"chip_smoke: needs {want_count} TPU chips, JAX "
                         f"found {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log(f"[a] device platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}; host "
        f"decode device {jax.devices('cpu')[0]}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# (b) gold KATs on the chip
# ---------------------------------------------------------------------------


def phase_gold() -> None:
    import numpy as np

    from repro.kernels import ops

    if ops._interpret():
        raise RuntimeError("Pallas kernels would run in interpret mode")
    sys.path.insert(0, TESTS)
    import gold

    golden = gold.load_kats()
    for backend in ("ref", "pallas", "pallas4"):
        t0 = time.perf_counter()
        ops.set_backend(backend)
        got = gold.compute_kats()
        if set(got) != set(golden):
            raise RuntimeError(f"{backend}: KAT names differ from the "
                               "golden file")
        bad = sorted(k for k in golden
                     if not np.array_equal(got[k], golden[k]))
        if bad:
            raise RuntimeError(f"{backend}: {len(bad)} KATs drifted from "
                               f"tests/golden/ckks_kats.json: {bad}")
        log(f"[b] gold backend={backend} {len(golden)}/{len(golden)} "
            f"vectors bit-exact, interpret={ops._interpret()} "
            f"({time.perf_counter() - t0:.1f} s)")


# ---------------------------------------------------------------------------
# (c) one encrypted FL round
# ---------------------------------------------------------------------------


def run_round(cfg, ctx, *, n_clients: int = 2, local_steps: int = 2,
              batch: int = 4, seq: int = 512, p_ratio: float = 0.1,
              backend: str = "pallas", seed: int = 0, say=log) -> dict:
    """One round of repro.fl.FLTask with the seeded wire path; checks the
    recovered global model against the plaintext FedAvg of the clients'
    local models and returns the round's numbers.  Raises on a mismatch.
    """
    import jax
    import numpy as np

    from repro.core import packing
    from repro.core.secure_agg import AggregatorConfig
    from repro.data import make_client_streams
    from repro.fl import ClientConfig, FLClient, FLRunConfig, FLTask
    from repro.kernels import ops
    from repro.models import build_model
    from repro.wire.compress import WirePolicy

    ops.set_backend(backend)
    stages: dict[str, float] = {}
    losses: dict[int, list[float]] = {}
    local: dict[int, tuple] = {}

    def timed(name, fn):
        def run(*a, **k):
            t0 = time.perf_counter()
            out = _ready(fn(*a, **k))
            dt = time.perf_counter() - t0
            stages[name] = stages.get(name, 0.0) + dt
            say(f"[c] stage {name}: {dt:.3f} s")
            return out
        return run

    model = build_model(cfg)
    streams = make_client_streams(n_clients, cfg.vocab, seq_len=seq,
                                  batch_size=batch, seed=seed)
    clients = [FLClient(i, model, streams[i],
                        ClientConfig(local_steps=local_steps, lr=1e-4))
               for i in range(n_clients)]
    task = FLTask(model, clients,
                  AggregatorConfig(p_ratio=p_ratio, strategy="random",
                                   seed=seed),
                  FLRunConfig(n_rounds=1, seed=seed,
                              wire_policy=WirePolicy()),
                  ctx=ctx)
    agg = task.agree_encryption_mask()
    part = agg.part
    say(f"[c] model {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
        f"{agg.spec.total} params; CKKS N={ctx.n_poly} L={ctx.n_limbs} "
        f"delta=2^{int(np.log2(ctx.delta))}; p={p_ratio}: "
        f"{part.n_enc} encrypted params in {part.n_chunks} ciphertexts")
    say(f"[c] selector strategy=random (sensitivity-free): HE mask "
        f"agreement over all {agg.spec.total} sensitivities would be "
        f"~{-(-agg.spec.total // ctx.slots)} ciphertexts per client — "
        "streaming work (ROADMAP R2), not part of this round")

    for c in clients:
        step = c._step

        def traced_step(*a, _cid=c.cid, _step=step):
            out = _step(*a)
            losses.setdefault(_cid, []).append(float(out[2]))
            return out

        c._step = traced_step
        train = timed("train", c.local_train)

        def capture(params, _cid=c.cid, _train=train):
            params_out, loss = _train(params)
            vec, _ = packing.flatten_params(params_out)
            enc, plain = packing.split_by_mask(vec, part)
            local[_cid] = (np.asarray(enc), np.asarray(plain))
            return params_out, loss

        c.local_train = capture
        c.protect_and_pack = timed("encrypt+pack", c.protect_and_pack)
    task.server.aggregate_wire = timed("ingest+fold",
                                       task.server.aggregate_wire)
    task._recover = timed("decrypt+recover", task._recover)

    t0 = time.perf_counter()
    (log_,) = task.run()
    wall = time.perf_counter() - t0

    # plaintext FedAvg reference, in the server's own order and precision
    ns = np.asarray([c.n_samples for c in clients], dtype=np.float64)
    weights = ns / ns.sum()
    plain_ref = np.zeros_like(local[0][1])
    enc_ref = np.zeros(local[0][0].shape, dtype=np.float64)
    for cid, w in enumerate(weights):
        plain_ref += np.float32(w) * local[cid][1]
        enc_ref += w * local[cid][0].astype(np.float64)
    vec, _ = packing.flatten_params(task.global_params)
    enc_got, plain_got = packing.split_by_mask(vec, part)
    enc_got = np.asarray(enc_got, dtype=np.float64)
    max_err = float(np.max(np.abs(enc_got - enc_ref)))
    plain_exact = bool(np.array_equal(np.asarray(plain_got), plain_ref))

    for cid in sorted(losses):
        say(f"[c] client {cid} loss per step: "
            + " ".join(f"{x:.6f}" for x in losses[cid]))
    say(f"[c] ciphertexts per client: {part.n_chunks}; uplink bytes "
        f"(measured on the wire, all clients): {log_.comm_up_bytes}")
    say("[c] wall time per stage (s): "
        + ", ".join(f"{k}={v:.3f}" for k, v in stages.items())
        + f"; round={wall:.3f}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    say(f"[c] peak_bytes_in_use: "
        f"{peak if peak is not None else 'not reported by this backend'}")
    say(f"[c] encrypted partition max |decrypted - plaintext FedAvg| = "
        f"{max_err:.3e} (bound {CKKS_ATOL}); plaintext partition "
        f"bit-exact: {plain_exact}")
    finite = all(np.isfinite(x) for v in losses.values() for x in v)
    if not (finite and max_err <= CKKS_ATOL and plain_exact):
        raise RuntimeError(
            f"round check failed: losses finite={finite}, max_err="
            f"{max_err} (bound {CKKS_ATOL}), plain bit-exact={plain_exact}")
    return {"losses": losses, "n_ciphertexts": part.n_chunks,
            "uplink_bytes": log_.comm_up_bytes, "max_err": max_err,
            "stages_s": stages, "round_s": wall, "peak_bytes": peak}


def phase_round(seed: int) -> None:
    from repro import configs
    from repro.core.ckks import params as ckks_params

    cfg = configs.get_config("qwen1.5-0.5b")
    ctx = ckks_params.make_context()
    run_round(cfg, ctx, seed=seed)


# ---------------------------------------------------------------------------
# (d) four chips: sharded encrypt + ingest against one device
# ---------------------------------------------------------------------------


def run_sharded(ctx, k: int, *, seed: int = 0, say=log) -> None:
    """Sharded encrypt (pk and seeded) and chunk-batched ingest of k
    ciphertexts on a (data=2, model=2) mesh of 4 devices, against one
    device.  Raises unless bit-identical, spread over 4 devices, and the
    compiled encrypt graphs hold no collective."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.ckks import cipher
    from repro.core.ckks import sharded as sh
    from repro.core.secure_agg import ProtectedUpdate
    from repro.kernels import ops
    from repro.launch.mesh import make_he_mesh
    from repro.wire import compress as wc
    from repro.wire import stream as ws

    log = say
    ops.set_backend("pallas")
    mesh = make_he_mesh(ctx.n_limbs, 4)
    eng = sh.ShardedHe(ctx, mesh)
    log(f"[d] mesh {dict(mesh.shape)} over {len(mesh.devices.flat)} "
        f"devices; {k} ciphertexts of N={ctx.n_poly}, L={ctx.n_limbs}")
    sk, pk = cipher.keygen(ctx, jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    vals = jnp.asarray((0.01 * rng.randn(k, ctx.slots)).astype(np.float32))
    key = jax.random.PRNGKey(seed + 1)
    a_seed = 1000 + seed
    token = ops.backend_token()

    def same(a, b):
        return bool(np.array_equal(np.asarray(a), np.asarray(b)))

    def devices_of(x):
        return {s.device for s in x.addressable_shards}

    t0 = time.perf_counter()
    one_seeded = _ready(cipher.encrypt_values_seeded(ctx, sk, vals, key,
                                                     a_seed))
    t1 = time.perf_counter()
    four_seeded = _ready(eng.encrypt_values_seeded(sk, vals, key, a_seed))
    t2 = time.perf_counter()
    one_pk = _ready(cipher.encrypt_values(ctx, pk, vals, key))
    t3 = time.perf_counter()
    four_pk = _ready(eng.encrypt_values(pk, vals, key))
    t4 = time.perf_counter()
    checks = {
        "seeded encrypt bit-identical": same(one_seeded.data,
                                             four_seeded.data),
        "pk encrypt bit-identical": same(one_pk.data, four_pk.data),
        "seeded shards on 4 devices": len(devices_of(four_seeded.data)) == 4,
        "pk shards on 4 devices": len(devices_of(four_pk.data)) == 4,
    }
    log(f"[d] encrypt wall (s, first call, compile included): seeded one="
        f"{t1 - t0:.3f} four={t2 - t1:.3f}; pk one={t3 - t2:.3f} "
        f"four={t4 - t3:.3f}")

    a_base = jax.random.PRNGKey(a_seed)
    for name, lowered in (
            ("seeded", sh._encrypt_seeded_values_graph.lower(
                eng, token, sk["s_mont"], vals, key, a_base,
                cipher.DERIVE_FOLD_CHUNK)),
            ("pk", sh._encrypt_values_graph.lower(
                eng, token, pk["pk0_mont"], pk["pk1_mont"], vals, key))):
        hlo = lowered.compile().as_text()
        found = [c for c in COLLECTIVES if c in hlo]
        checks[f"{name} encrypt HLO collective-free"] = not found
        log(f"[d] compiled {name} encrypt HLO collectives: {found or 'none'}")

    # the sharded streaming fold of the seeded update against the
    # single-device fused weighted sum of the same ciphertexts (the
    # stream's fold is bit-identical to it by construction, and one
    # launch costs far less chip time than a second per-chunk ingest)
    blob = ws.pack_update_frames(
        ProtectedUpdate(ct=one_seeded, plain=jnp.zeros((0,), jnp.float32)),
        cid=0, n_samples=1, rnd=0, seeded=wc.seed_compress(one_seeded,
                                                            a_seed))
    t0 = time.perf_counter()
    ing = ws.StreamIngest(ctx, sharded=eng)
    ing.ingest(blob, 0.5)
    four = _ready(ing.finalize().ct.data)
    log(f"[d] chunk-batched ingest of {k} chunks on four devices: "
        f"{time.perf_counter() - t0:.3f} s, {ing.accum_launches} "
        f"accumulate launch(es)")
    one = cipher.weighted_sum(ctx, cipher.Ciphertext(
        data=one_seeded.data[None], scale=one_seeded.scale), [0.5])
    checks["ingest bit-identical"] = same(one.data, four)
    for name, ok in checks.items():
        log(f"[d] {name}: {ok}")
    if not all(checks.values()):
        raise RuntimeError(f"sharded phase failed: "
                           f"{[n for n, ok in checks.items() if not ok]}")


def phase_sharded(seed: int) -> None:
    from repro import configs
    from repro.core.ckks import params as ckks_params

    ctx = ckks_params.make_context()
    cfg = configs.get_config("qwen1.5-0.5b")
    # the encrypted partition of phase (c): p=0.1 of the published model
    k = -(-int(round(0.1 * cfg.param_count())) // ctx.slots)
    run_sharded(ctx, k, seed=seed)


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded phase (d) on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isfile(os.path.join(SRC, "repro", "fl", "orchestrator.py")):
        sys.exit("chip_smoke: the repository's src/repro is not beside "
                 "this script")
    sys.path.insert(0, SRC)
    # the tuning cache is an untracked file; libtpu logs go nowhere
    os.environ.pop("REPRO_HE_TUNE_CACHE", None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.launch import compile_cache
    compile_cache.enable()

    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded(args.seed)
    else:
        phase_gold()
        phase_round(args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
