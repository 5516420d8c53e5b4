"""Pallas TPU kernel: fused modular pointwise ops, limb-fused over all limbs.

`mul_add`:  out = x (*) y_mont + z  — the encrypt/decrypt workhorse:
    encrypt: c0 = pk0 (*) u + (e0 + m),  c1 = pk1 (*) u + e1
    decrypt: m~ = c1 (*) s + c0
Fusing the Montgomery multiply with the modular add keeps each operand to a
single HBM read (HE pointwise ops do ~0.5 int-op per byte: memory-bound).

The grid is (L, ceil(B / block_b)): the RNS limb is a grid coordinate (a
squeezed block dimension over the lane-dense u32[B, L, R, 128] view,
kernels/tiling.py) and the per-limb Montgomery constants (q, -q^{-1}) are
u32[L] tables prefetched into SMEM, so one `pallas_call` covers the whole
u32[B, L, N] tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels import tiling
from repro.kernels import tune as _tune


def _mul_add_body(q_ref, qi_ref, x_ref, y_ref, z_ref, o_ref):
    li = pl.program_id(0)
    q = q_ref[li]
    prod = _ref.mont_mul(x_ref[...], y_ref[...], q, qi_ref[li])
    o_ref[...] = _ref.mod_add(prod, z_ref[...], q)


@functools.lru_cache(maxsize=128)
def _build(l: int, rows: int, block_b: int, interpret: bool):
    tile = tiling.row_block(block_b, rows)

    @jax.jit
    def call(qs, qinv_negs, x, y, z):
        b = x.shape[0]
        return pl.pallas_call(
            _mul_add_body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(l, pl.cdiv(b, block_b)),
                in_specs=[tile, tile, tile], out_specs=tile),
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
            interpret=interpret,
        )(qs, qinv_negs, x, y, z)

    return call


def mul_add_fused(x, y_mont, z, qs, qinv_negs, *, interpret: bool,
                  block_b: int | None = None):
    """out = x (*) y_mont + z mod q_l, all limbs in one pallas_call.

    x, y_mont, z: u32[..., L, N]; qs, qinv_negs: u32[L].  block_b=None
    takes the shared default from tune.DEFAULT_BLOCK."""
    if block_b is None:
        block_b = _tune.default_block("mul_add")
    l, n = x.shape[-2], x.shape[-1]
    batch = x.shape[:-2]

    def view(a):
        return tiling.rows_view(jnp.broadcast_to(a, x.shape)
                                .reshape((-1, l, n)))

    x2, y2, z2 = view(x), view(y_mont), view(z)
    b, rows = x2.shape[0], x2.shape[-2]
    call = _build(l, rows, min(block_b, b), interpret)
    out = call(jnp.asarray(qs, jnp.uint32), jnp.asarray(qinv_negs, jnp.uint32),
               x2, y2, z2)
    return tiling.unrows(out, n).reshape(batch + (l, n))
