"""Lane-dense TPU tiling shared by the Pallas kernels.

Mosaic (the TPU Pallas compiler) wants the last two dimensions of every
block to be multiples of (8, 128) or the array's full extent.  A polynomial
row u32[N] is therefore viewed as u32[R, 128] — R = N / 128 sublane rows of
one 128-lane vector each — and the RNS limb axis of a u32[B, L, R, 128]
tensor is a SQUEEZED block dimension (`None`) indexed by the limb grid
coordinate, never a size-1 second-minor block dim.  Polynomials shorter
than one lane row (N < 128; only the small test contexts) are zero-padded
up to 128 lanes: every kernel is lane-local or pairs index i with i XOR d
for d < N, so the pad lanes never mix with the real ones and are sliced
off afterwards.

Per-limb scalars (q, -q^{-1}, Montgomery weights) ride scalar prefetch
into SMEM (pltpu.PrefetchScalarGridSpec) and are read with the limb grid
coordinate.  SMEM is 1 MiB on v5e, so callers keep a prefetched table to
at most `SMEM_WORDS` words.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SMEM_WORDS = 1 << 16


def n_rows(n: int) -> int:
    """Sublane rows of one length-n polynomial in the lane-dense view."""
    return max(n // LANES, 1)


def rows_view(x):
    """u32[..., N] -> u32[..., R, 128] (zero-padded lanes when N < 128)."""
    n = x.shape[-1]
    if n % LANES:
        if LANES % n:
            raise ValueError(f"polynomial length {n} is neither a multiple "
                             f"nor a divisor of {LANES}")
        pad = [(0, 0)] * (x.ndim - 1) + [(0, LANES - n)]
        x = jnp.pad(x, pad)
    return x.reshape(x.shape[:-1] + (n_rows(n), LANES))


def unrows(y, n: int):
    """Inverse of rows_view: u32[..., R, 128] -> u32[..., N]."""
    return y.reshape(y.shape[:-2] + (-1,))[..., :n]


def row_block(block_b: int, rows: int) -> pl.BlockSpec:
    """(block_b, <limb squeezed>, R, 128) tile of a u32[B, L, R, 128]
    array on the (limb, batch-block) grid."""
    return pl.BlockSpec((block_b, None, rows, LANES),
                        lambda li, bi, *_: (bi, li, 0, 0))
