"""4-step transpose NTT (backend "pallas4"): exact bit-identity with the
ref oracle and the flat pallas kernel across the acceptance grid
N in {4096, 8192, 16384} x L in {1, 2, 3}, both directions, single-device
and 1/2/4-device limb-sharded meshes (interpret mode).

The sharded cases route through the same `ops.apply` + per-shard-table
shard_map plumbing the engine uses (core/ckks/sharded.py), so they cover
the new ntt4_* table fields riding the limb axis.  conftest.py forces 4
host devices, so every mesh case runs under plain tier-1.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.ckks import params as ckks_params
from repro.core.ckks import sharded as sh
from repro.kernels import ntt, ops, ref
from repro.launch.mesh import make_he_mesh

import gold

_NS = (4096, 8192, 16384)
_LS = (1, 2, 3)


@pytest.fixture(scope="module")
def ctxs():
    return {(n, l): ckks_params.make_context(n_poly=n, n_limbs=l,
                                             delta_bits=12 if l == 1 else 26)
            for n in _NS for l in _LS}


def _rand(ctx, batch, seed):
    rng = np.random.RandomState(seed)
    return jnp.asarray(ref.rand_limbed_np(rng, ctx, (batch,)))


def test_ntt4_split_shapes():
    assert ckks_params.ntt4_split(4096) == (64, 64)
    assert ckks_params.ntt4_split(8192) == (64, 128)
    assert ckks_params.ntt4_split(16384) == (128, 128)
    for n in (64, 256, 1024, 8192):
        n1, n2 = ckks_params.ntt4_split(n)
        assert n1 * n2 == n and n1 <= n2 <= 2 * n1


def test_ntt4_matches_quadratic_gold():
    """The 4-step output against the O(N^2) textbook model — independent of
    both the flat kernel and the jnp ref."""
    ctx = ckks_params.make_test_context(n_poly=64, n_limbs=2)
    t = ctx.tables
    lc = ctx.limbs[0]
    psi = ckks_params.root_of_unity(lc.q, 128)
    rng = np.random.RandomState(3)
    x = rng.randint(0, lc.q, size=(2, 64)).astype(np.uint32)
    xl = jnp.asarray(np.stack([x, x], axis=-2))          # [2, L=2, 64]
    ours = np.asarray(ntt.ntt4_fwd_fused(
        xl, t.ntt4_psi1_mont, t.ntt4_psi2_mont, t.ntt4_corr_mont, t.qs,
        t.qinv_negs, interpret=True))[:, 0, :]
    g = np.stack([gold.gold_ntt(x[i], lc.q, psi) for i in range(2)])
    np.testing.assert_array_equal(ours, g)


@pytest.mark.parametrize("n_limbs", _LS)
@pytest.mark.parametrize("n_poly", _NS)
def test_ntt4_bitexact_vs_ref_and_pallas(n_poly, n_limbs, ctxs):
    """Acceptance grid, single device: fwd and inv of the 4-step kernel
    equal the ref oracle AND the flat pallas kernel, exactly."""
    ctx = ctxs[(n_poly, n_limbs)]
    t = ctx.tables
    x = _rand(ctx, 2, seed=n_poly + n_limbs)
    want_fwd = ref.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs)
    got_fwd = ntt.ntt4_fwd_fused(x, t.ntt4_psi1_mont, t.ntt4_psi2_mont,
                                 t.ntt4_corr_mont, t.qs, t.qinv_negs,
                                 interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(got_fwd), np.asarray(want_fwd))
    flat_fwd = ntt.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs,
                                 interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(got_fwd), np.asarray(flat_fwd))

    want_inv = ref.ntt_inv_fused(want_fwd, t.psi_inv_rev_mont, t.n_inv_monts,
                                 t.qs, t.qinv_negs)
    got_inv = ntt.ntt4_inv_fused(got_fwd, t.ntt4_psi1_inv_mont,
                                 t.ntt4_psi2_inv_mont, t.ntt4_corr_inv_mont,
                                 t.n_inv_monts, t.qs, t.qinv_negs,
                                 interpret=True, block_b=2)
    np.testing.assert_array_equal(np.asarray(got_inv), np.asarray(want_inv))
    np.testing.assert_array_equal(np.asarray(got_inv), np.asarray(x))


def _sharded_ntt(ctx, mesh, x, op):
    """One shard_map dispatch of `op` with per-shard table slices — the
    engine's exact plumbing (limbs -> model axis, chunks -> data axis)."""
    def body(x, *tabs):
        return ops.apply(op, sh.local_tables(tabs), x)

    f = jax.shard_map(body, mesh=mesh,
                  in_specs=(P("data", "model", None),)
                  + sh.table_specs("model"),
                  out_specs=P("data", "model", None), check_vma=False)
    return f(x, *sh.table_arrays(ctx.tables))


@pytest.mark.parametrize("n_dev", [1, 2, 4])
@pytest.mark.parametrize("n_limbs", _LS)
@pytest.mark.parametrize("n_poly", _NS)
def test_ntt4_bitexact_sharded_mesh(n_poly, n_limbs, n_dev, ctxs):
    """Acceptance grid, 1/2/4-device meshes: the pallas4 NTT ops dispatched
    inside shard_map (per-shard ntt4_* tables) are bit-identical to the
    single-device ref, both directions."""
    if jax.device_count() < n_dev:
        pytest.skip(f"needs {n_dev} host devices, have {jax.device_count()}")
    ctx = ctxs[(n_poly, n_limbs)]
    mesh = make_he_mesh(n_limbs, n_dev)
    t = ctx.tables
    x = _rand(ctx, 4, seed=7 * n_poly + n_limbs + n_dev)
    old = {op: ops.get_backend(op) for op in ops.OPS}
    try:
        ops.set_backend("pallas4")
        got_fwd = _sharded_ntt(ctx, mesh, x, "ntt_fwd")
        want_fwd = ref.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs)
        np.testing.assert_array_equal(np.asarray(got_fwd),
                                      np.asarray(want_fwd))
        got_inv = _sharded_ntt(ctx, mesh, got_fwd, "ntt_inv")
        np.testing.assert_array_equal(np.asarray(got_inv), np.asarray(x))
    finally:
        for op, name in old.items():
            ops.set_backend(name, op=op)


def test_pallas4_registry_dispatch():
    """REPRO_HE_BACKEND=pallas4's runtime equivalent: set_backend('pallas4')
    flips the NTT family to the 4-step kernels, keeps every other op on the
    shared pallas implementation, and re-keys backend_token()."""
    ctx = ckks_params.make_test_context(n_poly=128, n_limbs=2)
    x = _rand(ctx, 3, seed=11)
    old = {op: ops.get_backend(op) for op in ops.OPS}
    try:
        ops.set_backend("ref")
        tok_ref = ops.backend_token()
        want = ops.ntt_fwd(x, ctx)
        ops.set_backend("pallas4")
        assert ops.get_backend() == "pallas4"
        assert ops.backend_token() != tok_ref
        got = ops.ntt_fwd(x, ctx)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(
            np.asarray(ops.ntt_inv(got, ctx)), np.asarray(x))
        # per-op: only the NTTs have a distinct pallas4 implementation
        assert ops._IMPL["weighted_sum"]["pallas4"] \
            is ops._IMPL["weighted_sum"]["pallas"]
        assert ops._IMPL["ntt_fwd"]["pallas4"] \
            is not ops._IMPL["ntt_fwd"]["pallas"]
    finally:
        for op, name in old.items():
            ops.set_backend(name, op=op)


@pytest.mark.parametrize("n_limbs", [1, 2, 3])
def test_ntt4_limb_dropped_tables(n_limbs):
    """take(l) slices the ntt4_* tables consistently: a limb-dropped input
    through pallas4 matches ref on the same slice."""
    ctx = ckks_params.make_test_context(
        n_poly=256, n_limbs=3, delta_bits=12)
    t = ctx.tables.take(n_limbs)
    rng = np.random.RandomState(n_limbs)
    x = jnp.asarray(ref.rand_limbed_np(rng, ctx, (2,))[:, :n_limbs])
    got = ntt.ntt4_fwd_fused(x, t.ntt4_psi1_mont, t.ntt4_psi2_mont,
                             t.ntt4_corr_mont, t.qs, t.qinv_negs,
                             interpret=True)
    want = ref.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
