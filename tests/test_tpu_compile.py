"""Compile the Pallas kernels and the sharded encrypt graphs for a described
TPU v5e (no chip attached) at the real CKKS size N=8192, L=2.

Interpret mode accepts block shapes the TPU compiler refuses, so these
compiles are what shows that every kernel family lowers for the chip:

  * each kernel's compiled HLO holds a `tpu_custom_call` — the Mosaic
    kernel itself, not a jnp substitute;
  * the sharded pk and seeded encrypt graphs, compiled over the four
    devices of a v5e:2x2, hold no collective.

The topology is described inside a module-scoped fixture (never at import,
in a skip condition or in conftest.py): only one process may load the TPU
library, and only the worker that runs this file needs it.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.core.ckks import cipher, params as ckks_params
from repro.core.ckks import sharded as sh
from repro.kernels import he_agg, lift, ntt, ops, pointwise

N, L, B = 8192, 2, 8
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ctx():
    return ckks_params.make_context(n_poly=N, n_limbs=L)


@pytest.fixture(scope="module")
def no_cache():
    """A described chip's executables cannot be read back from the
    persistent cache; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _kernel_cases(t):
    kw = dict(interpret=False)
    return {
        "ntt_fwd": (functools.partial(ntt.ntt_fwd_fused,
                                      psi_rev_mont=t.psi_rev_mont, qs=t.qs,
                                      qinv_negs=t.qinv_negs, **kw),
                    [(B, L, N)]),
        "ntt_inv": (functools.partial(ntt.ntt_inv_fused,
                                      psi_inv_rev_mont=t.psi_inv_rev_mont,
                                      n_inv_monts=t.n_inv_monts, qs=t.qs,
                                      qinv_negs=t.qinv_negs, **kw),
                    [(B, L, N)]),
        "ntt4_fwd": (functools.partial(ntt.ntt4_fwd_fused,
                                       psi1_mont=t.ntt4_psi1_mont,
                                       psi2_mont=t.ntt4_psi2_mont,
                                       corr_mont=t.ntt4_corr_mont, qs=t.qs,
                                       qinv_negs=t.qinv_negs, **kw),
                     [(B, L, N)]),
        "ntt4_inv": (functools.partial(
            ntt.ntt4_inv_fused, psi1_inv_mont=t.ntt4_psi1_inv_mont,
            psi2_inv_mont=t.ntt4_psi2_inv_mont,
            corr_inv_mont=t.ntt4_corr_inv_mont, n_inv_monts=t.n_inv_monts,
            qs=t.qs, qinv_negs=t.qinv_negs, **kw), [(B, L, N)]),
        "mul_add": (functools.partial(pointwise.mul_add_fused, qs=t.qs,
                                      qinv_negs=t.qinv_negs, **kw),
                    [(B, L, N)] * 3),
        "mod_lift": (functools.partial(lift.mod_lift_fused, qs=t.qs, **kw),
                     [(B, N)]),
        "weighted_sum": (functools.partial(he_agg.he_weighted_sum_fused,
                                           qs=t.qs, qinv_negs=t.qinv_negs,
                                           **kw),
                         [(4, B, L, N), (4, L)]),
        "weighted_accum": (functools.partial(he_agg.he_weighted_accum_fused,
                                             qs=t.qs, qinv_negs=t.qinv_negs,
                                             **kw),
                           [(B, L, N), (B, L, N), (L,)]),
        "weighted_accum_chunks": (functools.partial(
            he_agg.he_weighted_accum_chunks_fused, qs=t.qs,
            qinv_negs=t.qinv_negs, **kw),
            [(B, 2, L, N), (B, 2, L, N), (B, L)]),
    }


KERNELS = ("ntt_fwd", "ntt_inv", "ntt4_fwd", "ntt4_inv", "mul_add",
           "mod_lift", "weighted_sum", "weighted_accum",
           "weighted_accum_chunks")


@pytest.mark.parametrize("name", KERNELS)
def test_kernel_lowers_for_v5e(name, ctx, one_chip, no_cache):
    fn, shapes = _kernel_cases(ctx.tables)[name]
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.fixture
def native_pallas():
    """Steer the registry to compiled (not interpret-mode) Pallas kernels:
    this process's JAX backend is the CPU, the target is the described
    chip."""
    old_interp = ops._INTERPRET
    old = {op: ops.get_backend(op) for op in ops.OPS}
    ops._INTERPRET = False
    ops.set_backend("pallas")
    yield
    ops._INTERPRET = old_interp
    for op, b in old.items():
        ops.set_backend(b, op=op)


@pytest.mark.parametrize("graph", ["pk", "seeded"])
def test_sharded_encrypt_compiles_collective_free(graph, topo, ctx,
                                                  native_pallas, no_cache):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    eng = sh.ShardedHe(ctx, mesh)
    rep = NamedSharding(mesh, P())
    vals = jax.ShapeDtypeStruct((64, ctx.slots), jnp.float32,
                                sharding=NamedSharding(mesh, P("data")))
    table = jax.ShapeDtypeStruct((L, N), jnp.uint32, sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    token = ops.backend_token()
    if graph == "pk":
        lowered = sh._encrypt_values_graph.lower(eng, token, table, table,
                                                 vals, key)
    else:
        lowered = sh._encrypt_seeded_values_graph.lower(
            eng, token, table, vals, key, key, cipher.DERIVE_FOLD_CHUNK)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    found = [c for c in COLLECTIVES if c in hlo]
    assert not found, f"{graph} encrypt compiled with collectives {found}"
