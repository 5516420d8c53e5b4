"""Autotuner tests (kernels/tune.py + the `auto` backend, DESIGN.md §12).

Four contracts:

  1. **Bit-exactness across the FULL swept grid** — every candidate the
     tuner can ever pick (backend x block_b x ntt4_split) must
     reproduce the checked-in gold KATs exactly.  Tuning may only change
     launch geometry, never bits.
  2. **Cache round-trip** — save -> load resolves to the same
     (backend, config); stale entries (wrong platform tag, unknown op,
     bogus backend, malformed config) are ignored one by one.
  3. **`auto` registry behaviour** — dispatch resolves through the cache,
     `backend_token()` carries the tuner generation (so cached jitted
     graphs retrace on cache changes) exactly when `auto` is assigned.
  4. **Ragged batches** — B not divisible by block_b on every op.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.ckks import params as ckks_params
from repro.kernels import ops, ref, tune

import gold


@pytest.fixture(autouse=True)
def _restore_registry_and_cache():
    """Every test runs against a clean tuner cache and leaves the backend
    registry exactly as it found it."""
    old = {op: ops.get_backend(op) for op in ops.OPS}
    tune.clear_cache()
    try:
        yield
    finally:
        for op, name in old.items():
            ops.set_backend(name, op=op)
        tune.clear_cache()


def _ctx(name="n64_l2"):
    return ckks_params.make_context(**gold.KAT_CONTEXTS[name])


def _inputs(op, ctx, b, seed=7):
    rng = np.random.RandomState(seed)
    l = ctx.n_limbs

    def rand(shape):
        return jnp.asarray(ref.rand_limbed_np(rng, ctx, shape))

    w = jnp.asarray(rng.randint(
        1, int(np.asarray(ctx.tables.qs).min()),
        size=(max(b, 4), l)).astype(np.uint32))
    if op in ("ntt_fwd", "ntt_inv"):
        return (rand((b,)),)
    if op == "mul_add":
        return (rand((b,)), rand((b,)), rand((b,)))
    if op == "mod_lift":
        # full-range u32 words with no limb axis: the pre-RNS masked rows
        # of the transcipher uplink (DESIGN.md §15)
        return (jnp.asarray(rng.randint(
            0, 1 << 32, size=(b, ctx.n_poly),
            dtype=np.uint64).astype(np.uint32)),)
    if op == "weighted_sum":
        return (rand((3, b)), w[:3])
    if op == "weighted_accum":
        return (rand((b,)), rand((b,)), w[0])
    if op == "weighted_accum_chunks":
        return (rand((b,)), rand((b,)), w[:b])
    raise ValueError(op)


# ---------------------------------------------------------------------------
# 1. full swept grid is bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ctx_name", sorted(gold.KAT_CONTEXTS))
@pytest.mark.parametrize("op", tune.NTT_OPS)
def test_full_ntt_grid_reproduces_golden_kats(ctx_name, op):
    """Every (backend, block_b, ntt4_split) candidate reproduces
    the golden NTT vectors bit-exactly — the tuner cannot pick a config
    that changes ciphertext bits."""
    golden = gold.load_kats()[f"{ctx_name}/{op}"]
    ctx = _ctx(ctx_name)
    rng = np.random.RandomState(12345)
    x = jnp.asarray(ref.rand_limbed_np(rng, ctx, (2,)))  # the KAT input
    t = ctx.tables.take(ctx.n_limbs)
    cands = tune.candidates(op, ctx.n_poly, ctx.n_limbs, 2, interpret=True)
    # the grid really is the full cross product, not a truncation
    splits = ckks_params.ntt4_split_candidates(ctx.n_poly)
    blocks = [blk for blk in tune.BLOCK_CANDIDATES if blk <= 2]
    assert len(cands) >= 1 + len(blocks) * (1 + len(splits))
    for cand in cands:
        got = np.asarray(ops.run_config(op, cand.backend, cand.config, t, x))
        np.testing.assert_array_equal(
            got, golden,
            err_msg=f"swept config drifted from golden KAT: {cand}")


@pytest.mark.parametrize("op", [o for o in ops.OPS if o not in tune.NTT_OPS])
def test_full_block_grid_matches_ref(op):
    """Non-NTT ops: every block_b candidate is bit-identical to the jnp
    oracle (block size only re-tiles the grid)."""
    ctx = _ctx()
    b = 6
    args = _inputs(op, ctx, b)
    t = ctx.tables.take(ctx.n_limbs)
    want = np.asarray(ops.run_config(op, "ref", None, t, *args))
    for cand in tune.candidates(op, ctx.n_poly, ctx.n_limbs, b,
                                interpret=True):
        got = np.asarray(ops.run_config(op, cand.backend, cand.config, t,
                                        *args))
        np.testing.assert_array_equal(got, want, err_msg=str(cand))


# ---------------------------------------------------------------------------
# 2. cache round-trip + staleness
# ---------------------------------------------------------------------------


def test_cache_round_trip(tmp_path):
    import jax

    platform = jax.default_backend()
    cfg = tune.KernelConfig(block_b=2, ntt4_split=(16, 4))
    tune.put("ntt_fwd", 64, 2, 5, platform, "pallas4", cfg,
             tuned_ms=1.0, default_ms=2.0)
    tune.put("mul_add", 64, 2, 5, platform, "pallas",
             tune.KernelConfig(block_b=16))
    path = tmp_path / "cache.json"
    tune.save_cache(str(path))
    tune.clear_cache()
    assert tune.resolve("ntt_fwd", 64, 2, 5, True) == \
        ("ref", tune.default_config("ntt_fwd"))
    assert tune.load_cache(str(path)) == 2
    backend, got = tune.resolve("ntt_fwd", 64, 2, 5, True)
    assert (backend, got) == ("pallas4", cfg)
    backend, got = tune.resolve("mul_add", 64, 2, 5, True)
    assert (backend, got) == ("pallas", tune.KernelConfig(block_b=16))
    # the meta block records where the numbers came from
    doc = json.loads(path.read_text())
    assert doc["meta"]["platform"] == platform
    assert doc["version"] == tune.CACHE_VERSION


def test_stale_entries_ignored(tmp_path):
    """Entries for another platform, unknown ops, bogus backends, or
    malformed configs load as 'no entry', never as garbage."""
    import jax

    platform = jax.default_backend()
    good_key = tune.shape_key("ntt_fwd", 64, 2, 5, platform)
    doc = {
        "version": tune.CACHE_VERSION,
        "entries": {
            good_key: {"backend": "pallas",
                       "config": {"block_b": 4}},
            tune.shape_key("ntt_fwd", 64, 2, 5, "not_a_platform"):
                {"backend": "pallas", "config": {"block_b": 2}},
            tune.shape_key("no_such_op", 64, 2, 5, platform):
                {"backend": "pallas", "config": {"block_b": 2}},
            tune.shape_key("ntt_inv", 64, 2, 5, platform):
                {"backend": "auto", "config": {"block_b": 2}},
            tune.shape_key("mul_add", 64, 2, 5, platform):
                {"backend": "pallas", "config": {"block_b": "huge"}},
        },
    }
    path = tmp_path / "stale.json"
    path.write_text(json.dumps(doc))
    assert tune.load_cache(str(path)) == 1
    assert tune.resolve("ntt_fwd", 64, 2, 5, True) == \
        ("pallas", tune.KernelConfig(block_b=4))
    for op in ("ntt_inv", "mul_add"):
        assert tune.resolve(op, 64, 2, 5, True) == \
            ("ref", tune.default_config(op))


def test_missing_cache_file_loads_empty(tmp_path):
    assert tune.load_cache(str(tmp_path / "absent.json")) == 0
    (tmp_path / "garbage.json").write_text("{not json")
    assert tune.load_cache(str(tmp_path / "garbage.json")) == 0


# ---------------------------------------------------------------------------
# 3. `auto` registry behaviour
# ---------------------------------------------------------------------------


def test_auto_dispatch_resolves_from_cache():
    import jax

    ctx = _ctx()
    rng = np.random.RandomState(3)
    x = jnp.asarray(ref.rand_limbed_np(rng, ctx, (5,)))
    ops.set_backend("ref")
    want = np.asarray(ops.ntt_fwd(x, ctx))
    ops.set_backend("auto")
    # miss -> fallback, still bit-exact
    np.testing.assert_array_equal(np.asarray(ops.ntt_fwd(x, ctx)), want)
    # hit -> the cached pallas4 variant config, still bit-exact
    tune.put("ntt_fwd", ctx.n_poly, ctx.n_limbs, 5, jax.default_backend(),
             "pallas4", tune.KernelConfig(block_b=2, ntt4_split=(16, 4)))
    np.testing.assert_array_equal(np.asarray(ops.ntt_fwd(x, ctx)), want)


def test_backend_token_carries_tune_generation():
    ops.set_backend("pallas")
    tok = ops.backend_token()
    assert not any(k == "tune" for k, _ in tok), tok
    ops.set_backend("auto")
    tok1 = ops.backend_token()
    assert any(k == "tune" for k, _ in tok1), tok1
    # a cache edit bumps the generation -> new static jit key -> retrace
    tune.put("ntt_fwd", 64, 2, 5, "cpu", "pallas",
             tune.KernelConfig(block_b=2))
    tok2 = ops.backend_token()
    assert tok2 != tok1
    tune.clear_cache()
    assert ops.backend_token() != tok2


def test_auto_in_env_canon_and_set_backend():
    assert "auto" in ops.BACKENDS
    ops.set_backend("ref")  # pin a uniform base: the env leg may start auto
    ops.set_backend("auto", op="mul_add")
    assert ops.get_backend("mul_add") == "auto"
    assert ops.get_backend() == "mixed"


def test_unknown_env_backend_fails_at_import_with_pointer():
    """REPRO_HE_BACKEND=bogus must fail AT IMPORT with an actionable
    message naming the README env table, not as a later bare KeyError."""
    env = dict(os.environ, REPRO_HE_BACKEND="bogus")
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-c", "import repro.kernels.ops"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode != 0
    assert "REPRO_HE_BACKEND" in proc.stderr
    assert "bogus" in proc.stderr
    assert "README" in proc.stderr


def test_provenance_stamps_tuner_state():
    from repro import obs

    ops.set_backend("auto")
    prov = obs.provenance()
    assert prov["tune"]["entries"] == 0
    tune.put("ntt_fwd", 64, 2, 5, "cpu", "pallas",
             tune.KernelConfig(block_b=2))
    assert obs.provenance()["tune"]["entries"] == 1
    ops.set_backend("ref")
    assert "tune" not in obs.provenance()


# ---------------------------------------------------------------------------
# 4. ragged batches
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", ops.OPS)
def test_ragged_batch_every_op(op):
    """B=5 with block 2/4: the cdiv grid's last partial tile is handled on
    every op, bit-exactly."""
    ctx = _ctx()
    b = 5
    args = _inputs(op, ctx, b)
    t = ctx.tables.take(ctx.n_limbs)
    want = np.asarray(ops.run_config(op, "ref", None, t, *args))
    for blk in (2, 4, 16):
        got = np.asarray(ops.run_config(
            op, "pallas", tune.KernelConfig(block_b=blk), t, *args))
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{op} block_b={blk}")
    if op in tune.NTT_OPS:
        got = np.asarray(ops.run_config(
            op, "pallas4",
            tune.KernelConfig(block_b=2, ntt4_split=(16, 4)),
            t, *args))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# sweep machinery
# ---------------------------------------------------------------------------


def test_sweep_winner_never_loses_to_default():
    ctx = _ctx()
    res = tune.sweep_op("mul_add", ctx, b=4, reps=1)
    assert res.tuned_ms <= res.default_ms
    assert res.n_candidates >= 1 + len(
        [blk for blk in tune.BLOCK_CANDIDATES if blk <= 4])
    # the winner was recorded: auto now resolves to it
    backend, cfg = tune.resolve("mul_add", ctx.n_poly, ctx.n_limbs, 4,
                                ops._interpret())
    assert (backend, cfg) == (res.winner.backend, res.winner.config)


def test_roofline_pruning_skips_hopeless_candidates():
    """With the model on, clearly launch-bound configs (block_b=1 at a
    tiny shape) are skipped unmeasured; the default is never pruned."""
    ctx = _ctx()
    res = tune.sweep_op("ntt_fwd", ctx, b=5, reps=1, use_roofline=True)
    assert res.n_pruned > 0
    full = tune.sweep_op("ntt_fwd", ctx, b=5, reps=1, use_roofline=False)
    assert full.n_pruned == 0
