"""Pallas TPU kernel: per-limb modular lift of raw u32 rows.

`mod_lift`: out[b, l, n] = x[b, n] mod q_l — the keystream-expansion step
of the transcipher uplink (DESIGN.md §15): the server receives stream-
cipher-masked coefficients as full-range u32 words (no limb axis — the
client never touched RNS) and lifts each row into per-limb residues before
the forward NTT.  One launch covers the whole u32[B, N] -> u32[B, L, N]
expansion; the input tile is re-read once per limb grid step, which is the
point — the lift is the only op whose OUTPUT traffic (L x the input)
dominates, so the tile shape mirrors pointwise.py's and the limb index
only picks the modulus.

The grid is (L, ceil(B / block_b)) over the lane-dense u32[B, R, 128]
input view (kernels/tiling.py); the per-limb moduli are prefetched into
SMEM from the same u32[L] LimbTables row as every other kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels import tune as _tune


def _mod_lift_body(q_ref, x_ref, o_ref):
    # full-range u32 % u32 — unlike the Montgomery ops there is no < 2**30
    # precondition here: masked words span [1, 2**32 - 2] by construction
    # (core/ckks/transcipher.py's pad window), and lax.rem on uint32 is
    # exact for the whole range.
    o_ref[...] = x_ref[...] % q_ref[pl.program_id(0)]


@functools.lru_cache(maxsize=128)
def _build(l: int, rows: int, block_b: int, interpret: bool):
    x_tile = pl.BlockSpec((block_b, rows, tiling.LANES),
                          lambda li, bi, *_: (bi, 0, 0))
    o_tile = tiling.row_block(block_b, rows)

    @jax.jit
    def call(qs, x):
        b = x.shape[0]
        return pl.pallas_call(
            _mod_lift_body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(l, pl.cdiv(b, block_b)),
                in_specs=[x_tile], out_specs=o_tile),
            out_shape=jax.ShapeDtypeStruct((b, l, rows, tiling.LANES),
                                           jnp.uint32),
            interpret=interpret,
        )(qs, x)

    return call


def mod_lift_fused(x, qs, *, interpret: bool, block_b: int | None = None):
    """out[..., l, :] = x[..., :] mod q_l, all limbs in one pallas_call.

    x: u32[..., N] full-range words; qs: u32[L].  block_b=None takes the
    shared default from tune.DEFAULT_BLOCK."""
    if block_b is None:
        block_b = _tune.default_block("mod_lift")
    n = x.shape[-1]
    batch = x.shape[:-1]
    x2 = tiling.rows_view(jnp.asarray(x, dtype=jnp.uint32).reshape((-1, n)))
    b, rows = x2.shape[0], x2.shape[-2]
    l = qs.shape[0]
    call = _build(l, rows, min(block_b, b), interpret)
    out = call(jnp.asarray(qs, jnp.uint32), x2)
    return tiling.unrows(out, n).reshape(batch + (l, n))
