"""Pallas kernels vs pure-jnp ref: EXACT integer equality across shape
sweeps, plus the u32 construction vs a uint64 gold model."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.ckks import params as ckks_params
from repro.kernels import he_agg, lift, ntt, ops, pointwise, ref

import gold


def ctxs():
    return [ckks_params.make_test_context(n_poly=n, n_limbs=2)
            for n in (64, 256)]


@pytest.mark.parametrize("n_poly", [64, 256, 1024])
def test_mont_mul_matches_gold(n_poly):
    ctx = ckks_params.make_test_context(n_poly=n_poly, n_limbs=2)
    lc = ctx.limbs[0]
    rng = np.random.RandomState(0)
    a = rng.randint(0, lc.q, size=(3, n_poly)).astype(np.uint32)
    b = rng.randint(0, lc.q, size=(3, n_poly)).astype(np.uint32)
    ours = np.asarray(ref.mont_mul(jnp.asarray(a), jnp.asarray(b),
                                   np.uint32(lc.q), np.uint32(lc.qinv_neg)))
    gold_out = gold.gold_mont_mul(a, b, lc.q)
    np.testing.assert_array_equal(ours, gold_out)


def test_mod_ops_match_gold():
    ctx = ckks_params.make_test_context(n_poly=64, n_limbs=2)
    q = ctx.primes[0]
    rng = np.random.RandomState(1)
    a = rng.randint(0, q, size=(100,)).astype(np.uint32)
    b = rng.randint(0, q, size=(100,)).astype(np.uint32)
    np.testing.assert_array_equal(
        np.asarray(ref.mod_add(a, b, np.uint32(q))),
        ((a.astype(np.uint64) + b) % q).astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(ref.mod_sub(a, b, np.uint32(q))),
        ((a.astype(np.int64) - b) % q).astype(np.uint32))
    np.testing.assert_array_equal(
        np.asarray(ref.mod_neg(a, np.uint32(q))),
        ((-a.astype(np.int64)) % q).astype(np.uint32))


def test_wide_arithmetic():
    rng = np.random.RandomState(2)
    a = rng.randint(0, 1 << 32, size=(64,), dtype=np.uint64).astype(np.uint32)
    b = rng.randint(0, 1 << 32, size=(64,), dtype=np.uint64).astype(np.uint32)
    hi, lo = ref.mul_wide(jnp.asarray(a), jnp.asarray(b))
    wide = a.astype(np.uint64) * b.astype(np.uint64)
    np.testing.assert_array_equal(np.asarray(hi), (wide >> 32).astype(np.uint32))
    np.testing.assert_array_equal(np.asarray(lo),
                                  (wide & 0xFFFFFFFF).astype(np.uint32))


@pytest.mark.parametrize("n_poly", [64, 128])
def test_ntt_matches_quadratic_gold(n_poly):
    ctx = ckks_params.make_test_context(n_poly=n_poly, n_limbs=2)
    lc = ctx.limbs[0]
    psi = ckks_params.root_of_unity(lc.q, 2 * n_poly)
    rng = np.random.RandomState(3)
    x = rng.randint(0, lc.q, size=(2, n_poly)).astype(np.uint32)
    ours = np.asarray(ref.ntt_fwd(jnp.asarray(x),
                                  jnp.asarray(lc.psi_rev_mont),
                                  np.uint32(lc.q), np.uint32(lc.qinv_neg)))
    g = np.stack([gold.gold_ntt(x[i], lc.q, psi) for i in range(2)])
    np.testing.assert_array_equal(ours, g)


@pytest.mark.parametrize("n_poly", [64, 256, 2048])
@pytest.mark.parametrize("batch", [1, 3, 8, 13])
def test_ntt_roundtrip_exact(n_poly, batch):
    ctx = ckks_params.make_test_context(n_poly=n_poly, n_limbs=2)
    for lc in ctx.limbs:
        rng = np.random.RandomState(4)
        x = rng.randint(0, lc.q, size=(batch, n_poly)).astype(np.uint32)
        fwd = ref.ntt_fwd(jnp.asarray(x), jnp.asarray(lc.psi_rev_mont),
                          np.uint32(lc.q), np.uint32(lc.qinv_neg))
        inv = ref.ntt_inv(fwd, jnp.asarray(lc.psi_inv_rev_mont),
                          np.asarray(lc.n_inv_mont),
                          np.uint32(lc.q), np.uint32(lc.qinv_neg))
        np.testing.assert_array_equal(np.asarray(inv), x)


# ---------------------------------------------------------------------------
# Pallas limb-grid kernels (interpret mode) vs fused ref: exact equality
# ---------------------------------------------------------------------------


def _rand_limbed(rng, ctx, shape):
    return jnp.asarray(ref.rand_limbed_np(rng, ctx, shape))


@pytest.mark.parametrize("n_poly", [64, 256, 1024])
@pytest.mark.parametrize("batch", [1, 5, 8, 11])
def test_pallas_ntt_exact(n_poly, batch):
    ctx = ckks_params.make_test_context(n_poly=n_poly, n_limbs=2)
    t = ctx.tables
    rng = np.random.RandomState(5)
    x = _rand_limbed(rng, ctx, (batch,))
    a = ntt.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs,
                          interpret=True)
    b = ref.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ai = ntt.ntt_inv_fused(a, t.psi_inv_rev_mont, t.n_inv_monts, t.qs,
                           t.qinv_negs, interpret=True)
    bi = ref.ntt_inv_fused(b, t.psi_inv_rev_mont, t.n_inv_monts, t.qs,
                           t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(ai), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(ai), np.asarray(x))


@pytest.mark.parametrize("batch,n", [(1, 64), (7, 256), (16, 512)])
def test_pallas_mul_add_exact(batch, n):
    ctx = ckks_params.make_test_context(n_poly=max(n, 64), n_limbs=2)
    t = ctx.tables
    rng = np.random.RandomState(6)
    x, y, z = (_rand_limbed(rng, ctx, (batch,)) for _ in range(3))
    a = pointwise.mul_add_fused(x, y, z, t.qs, t.qinv_negs, interpret=True)
    b = ref.mul_add_fused(x, y, z, t.qs, t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("clients", [1, 2, 5, 16])
def test_pallas_he_agg_exact(clients):
    ctx = ckks_params.make_test_context(n_poly=256, n_limbs=2)
    t = ctx.tables
    rng = np.random.RandomState(7)
    cts = _rand_limbed(rng, ctx, (clients, 6))
    w = jnp.asarray(np.stack([rng.randint(0, int(q), size=(clients,))
                              for q in ctx.primes], axis=1).astype(np.uint32))
    a = he_agg.he_weighted_sum_fused(cts, w, t.qs, t.qinv_negs,
                                     interpret=True)
    b = ref.he_weighted_sum_fused(cts, w, t.qs, t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("batch", [1, 6])
def test_pallas_he_accum_exact(batch):
    ctx = ckks_params.make_test_context(n_poly=128, n_limbs=2)
    t = ctx.tables
    rng = np.random.RandomState(9)
    acc = _rand_limbed(rng, ctx, (batch,))
    ct = _rand_limbed(rng, ctx, (batch,))
    w = jnp.asarray(np.asarray([rng.randint(0, int(q)) for q in ctx.primes],
                               dtype=np.uint32))
    a = he_agg.he_weighted_accum_fused(acc, ct, w, t.qs, t.qinv_negs,
                                       interpret=True)
    b = ref.he_weighted_accum_fused(acc, ct, w, t.qs, t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ops_backend_dispatch_consistent():
    """ops.* with pallas backend == ops.* with ref backend, exactly."""
    ctx = ckks_params.make_test_context(n_poly=128, n_limbs=2)
    rng = np.random.RandomState(8)
    x = jnp.asarray(np.stack([rng.randint(0, q, size=(4, 128))
                              for q in ctx.primes], axis=1).astype(np.uint32))
    old = ops.get_backend()
    try:
        ops.set_backend("ref")
        a = ops.ntt_fwd(x, ctx)
        ops.set_backend("pallas")
        b = ops.ntt_fwd(x, ctx)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        ops.set_backend(old)


def test_pallas_accum_chunks_split_launches_exact(monkeypatch):
    """A weight table larger than the SMEM budget runs as several launches
    of whole rows, bit-identical to the oracle."""
    from repro.kernels import tiling

    monkeypatch.setattr(tiling, "SMEM_WORDS", 8)   # 4 rows per launch
    ctx = ckks_params.make_test_context(n_poly=128, n_limbs=2)
    t = ctx.tables
    rng = np.random.RandomState(10)
    acc = _rand_limbed(rng, ctx, (7, 2))
    cts = _rand_limbed(rng, ctx, (7, 2))
    w = jnp.asarray(rng.randint(1, min(ctx.primes), size=(7, 2))
                    .astype(np.uint32))
    a = he_agg.he_weighted_accum_chunks_fused(acc, cts, w, t.qs,
                                              t.qinv_negs, interpret=True,
                                              block_k=2)
    b = ref.he_weighted_accum_chunks_fused(acc, cts, w, t.qs, t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fn", [
    ntt.ntt_fwd_fused, ntt.ntt_inv_fused, ntt.ntt4_fwd_fused,
    ntt.ntt4_inv_fused, pointwise.mul_add_fused,
    he_agg.he_weighted_sum_fused, he_agg.he_weighted_accum_fused,
    he_agg.he_weighted_accum_chunks_fused, lift.mod_lift_fused])
def test_kernel_entry_points_take_interpret_explicitly(fn):
    """No Pallas entry point may default to interpret mode: a caller that
    forgot the flag would run the Python interpreter on a TPU."""
    import inspect

    p = inspect.signature(fn).parameters["interpret"]
    assert p.default is inspect.Parameter.empty, fn.__name__
