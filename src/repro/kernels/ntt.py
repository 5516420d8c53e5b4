"""Pallas TPU kernels: negacyclic NTT / iNTT, limb-fused over all RNS limbs.

Target: TPU VPU (u32 lanes).  The grid is (L, ceil(B / block_b)): the RNS
limb is a *grid coordinate* (a squeezed block dimension), so one
`pallas_call` covers the whole u32[B, L, N] tensor.  Each polynomial is
held lane-dense as u32[R, 128] (kernels/tiling.py), and every butterfly
stage pairs flat index i with i XOR d:

  * d < 128   — the partner sits in the same sublane row; a lane rotate
                (`pltpu.roll` along lanes) brings it over;
  * d >= 128  — the partner is d / 128 rows away; a sublane rotate brings
                it over.

No reshape ever splits the (8, 128) tile, which is what Mosaic requires.
Each position selects its own half of the butterfly (CT forward: u + w v
or u - w v; GS inverse: u + v or (u - v) w) with the twiddle of its group,
so the arithmetic is the same mont_mul / mod_add / mod_sub on the same
operands as kernels/ref.py — the outputs are bit-identical.  The rotate
direction is read off a rotated iota instead of being assumed.

A transform is a static *plan*: a sequence of butterfly stages ("ct" /
"gs", distance d) and pointwise Montgomery multiplies ("mul").  Stages of
one kind on one axis whose distances halve (or double) run as one
fori_loop with a traced distance and rotation, so the kernel body holds a
handful of butterflies, not log2(N).  The per-position twiddle of each
step is gathered once per call from the LimbTables rows into a
u32[L, T, R, 128] table (block: one limb's T tables, resident in VMEM
across the batch axis of the grid):

  * flat NTT ("pallas"): log2(N) stages over psi_rev_mont, plus the N^-1
    multiply for the inverse;
  * 4-step NTT ("pallas4", DESIGN.md §10): N = n1 * n2 with
    j = j2 + n2 * j1 — log2(n1) stages at distances that are multiples of
    n2 (the length-n1 sub-NTT over j1, twiddles psi1), one multiply by the
    correction table, then log2(n2) stages at distances < n2 (the
    length-n2 sub-NTT over j2, twiddles psi2).  In this layout the
    4-step needs no transpose: its [br k1][br k2] output is already the
    flat bit-reversed order.

Constants come from the stacked u32[L] / u32[L, .] tables
(params.LimbTables); q and -q^{-1} are prefetched into SMEM and read with
the limb coordinate.  That is what lets the sharded engine
(core/ckks/sharded.py, DESIGN.md §8) turn the limb grid axis into the
`model` mesh axis with no collectives.

Validated in interpret mode against repro/kernels/ref.py with exact integer
equality (tests/test_kernels.py, tests/test_fused_engine.py,
tests/test_ntt4.py, tests/test_gold.py) and compiled for the TPU at N=8192
by tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels import tiling
from repro.kernels import tune as _tune

LANES = tiling.LANES


# ---------------------------------------------------------------------------
# plans: (kind, distance) steps + the static twiddle gather index
# ---------------------------------------------------------------------------


def _flat_plan(n: int, inverse: bool):
    """Flat LN NTT over a source row [psi (N) | n_inv (1, inverse only)]."""
    steps, idx = [], []
    i = np.arange(n)
    if not inverse:
        m = 1
        while m < n:
            t = n // (2 * m)
            steps.append(("ct", t))
            idx.append(m + i // (2 * t))
            m *= 2
        return steps, idx
    t = 1
    while t < n:
        h = n // (2 * t)
        steps.append(("gs", t))
        idx.append(h + i // (2 * t))
        t *= 2
    steps.append(("mul", 0))
    idx.append(np.full(n, n))
    return steps, idx


def _four_step_plan(n1: int, n2: int, inverse: bool):
    """4-step NTT over a source row [psi1 (n1) | psi2 (n2) | corr (N) |
    n_inv (1, inverse only)]."""
    n = n1 * n2
    i = np.arange(n)
    j1, j2 = i // n2, i % n2
    o2, oc = n1, n1 + n2

    def fwd_phase(length, stride, pos, off):
        out = []
        m = 1
        while m < length:
            t = length // (2 * m)
            out.append((("ct", t * stride), off + m + pos // (2 * t)))
            m *= 2
        return out

    def inv_phase(length, stride, pos, off):
        out = []
        t = 1
        while t < length:
            h = length // (2 * t)
            out.append((("gs", t * stride), off + h + pos // (2 * t)))
            t *= 2
        return out

    corr = [(("mul", 0), oc + i)]
    if not inverse:
        pairs = fwd_phase(n1, n2, j1, 0) + corr + fwd_phase(n2, 1, j2, o2)
    else:
        pairs = (inv_phase(n2, 1, j2, o2) + corr + inv_phase(n1, n2, j1, 0)
                 + [(("mul", 0), np.full(n, oc + n))])
    return [p[0] for p in pairs], [p[1] for p in pairs]


@functools.lru_cache(maxsize=64)
def _plan(kind: str, n: int, n1: int, n2: int):
    """(static steps, u32 gather index [T, R*128]) for one transform.  Pad
    lanes (N < 128) gather slot 0; they never mix with real lanes."""
    inverse = kind.endswith("inv")
    if kind.startswith("ntt4"):
        steps, idx = _four_step_plan(n1, n2, inverse)
    else:
        steps, idx = _flat_plan(n, inverse)
    width = tiling.n_rows(n) * LANES
    gather = np.zeros((len(idx), width), dtype=np.int32)
    gather[:, :n] = np.stack(idx)
    return tuple(steps), gather


def _twiddles(src, gather, rows: int):
    """src u32[L, S] -> per-position step tables u32[L, T, R, 128]."""
    tw = jnp.take(jnp.asarray(src, dtype=jnp.uint32), jnp.asarray(gather),
                  axis=1)
    return tw.reshape((tw.shape[0], gather.shape[0], rows, LANES))


# ---------------------------------------------------------------------------
# kernel body
# ---------------------------------------------------------------------------


def _runs(steps) -> tuple:
    """Group the plan into loops: consecutive butterfly stages of one kind
    on one axis (lanes: distance < 128, sublanes: >= 128) whose distances
    halve or double step by step run as one fori_loop with a traced
    distance, so the kernel holds one butterfly per run instead of one
    per stage.  -> ((kind, axis, first slot, count, d0, grows), ...)"""
    runs = []
    for slot, (kind, dist) in enumerate(steps):
        axis = 0 if dist >= LANES else 1
        if runs and kind != "mul":
            k, ax, s0, cnt, d0, grows = runs[-1]
            last = d0 << (cnt - 1) if grows else d0 >> (cnt - 1)
            if k == kind and ax == axis and (
                    (cnt == 1 and dist in (2 * d0, d0 // 2))
                    or dist == (2 * last if grows else last // 2)):
                runs[-1] = (k, ax, s0, cnt + 1, d0,
                            dist > d0 if cnt == 1 else grows)
                continue
        runs.append((kind, axis, slot, 1, dist, False))
    return tuple(runs)


def _butterfly(x, w, idx, d, shift, axis: int, ct: bool, q, qi):
    """One radix-2 stage pairing flat index i with i ^ d on a u32[R, 128]
    polynomial (d: traced u32 distance, `shift` its rotation along
    `axis`); w holds each position's group twiddle."""
    size = x.shape[axis]
    fwd = pltpu.roll(x, shift, axis)
    bwd = pltpu.roll(x, size - shift, axis)
    partner = jnp.where(pltpu.roll(idx, shift, axis) == (idx ^ d), fwd, bwd)
    lo = (idx & d) == np.uint32(0)
    u = jnp.where(lo, x, partner)
    v = jnp.where(lo, partner, x)
    if ct:
        wv = _ref.mont_mul(v, w, q, qi)
        return jnp.where(lo, _ref.mod_add(u, wv, q), _ref.mod_sub(u, wv, q))
    s = jnp.where(lo, _ref.mod_add(u, v, q), _ref.mod_sub(u, v, q))
    return jnp.where(lo, s, _ref.mont_mul(s, w, q, qi))


def _ntt_body(q_ref, qi_ref, x_ref, tw_ref, o_ref, *, runs, rows: int,
              block_b: int):
    li = pl.program_id(0)
    q = q_ref[li]
    qi = qi_ref[li]
    shape = (rows, LANES)
    idx = (jax.lax.broadcasted_iota(jnp.uint32, shape, 0) * np.uint32(LANES)
           + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))

    def run_loop(x, kind, axis, s0, count, d0, grows):
        def stage(k, x):
            ku = k.astype(jnp.uint32)
            d = (np.uint32(d0) << ku) if grows else (np.uint32(d0) >> ku)
            shift = (d >> np.uint32(7) if axis == 0 else d).astype(jnp.int32)
            return _butterfly(x, tw_ref[s0 + k], idx, d, shift, axis,
                              kind == "ct", q, qi)
        return jax.lax.fori_loop(0, count, stage, x)

    def one(r, carry):
        x = x_ref[r]
        for kind, axis, s0, count, d0, grows in runs:
            if kind == "mul":
                x = _ref.mont_mul(x, tw_ref[s0], q, qi)
            else:
                x = run_loop(x, kind, axis, s0, count, d0, grows)
        o_ref[r] = x
        return carry

    jax.lax.fori_loop(0, block_b, one, 0)


@functools.lru_cache(maxsize=128)
def _build(steps: tuple, n_tables: int, l: int, rows: int, block_b: int,
           interpret: bool):
    body = functools.partial(_ntt_body, runs=_runs(steps), rows=rows,
                             block_b=block_b)
    tile = tiling.row_block(block_b, rows)
    tables = pl.BlockSpec((None, n_tables, rows, LANES),
                          lambda li, bi, *_: (li, 0, 0, 0))

    @jax.jit
    def call(qs, qinv_negs, x, tw):
        b = x.shape[0]
        return pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(l, pl.cdiv(b, block_b)),
                in_specs=[tile, tables], out_specs=tile),
            out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
            interpret=interpret,
        )(qs, qinv_negs, x, tw)

    return call


def _run(kind: str, x, src, qs, qinv_negs, block_b, interpret: bool,
         n1: int = 0, n2: int = 0):
    if block_b is None:
        block_b = _tune.default_block(
            "ntt_inv" if kind.endswith("inv") else "ntt_fwd")
    l, n = x.shape[-2], x.shape[-1]
    batch = x.shape[:-2]
    x2 = tiling.rows_view(jnp.asarray(x, jnp.uint32).reshape((-1, l, n)))
    b, rows = x2.shape[0], x2.shape[-2]
    steps, gather = _plan(kind, n, n1, n2)
    call = _build(steps, len(steps), l, rows, min(block_b, b), interpret)
    out = call(jnp.asarray(qs, jnp.uint32), jnp.asarray(qinv_negs, jnp.uint32),
               x2, _twiddles(src, gather, rows))
    return tiling.unrows(out, n).reshape(batch + (l, n))


def _col(v):
    return jnp.asarray(v, jnp.uint32)[:, None]


def ntt_fwd_fused(x, psi_rev_mont, qs, qinv_negs, *, interpret: bool,
                  block_b: int | None = None):
    """x: u32[..., L, N] natural -> bit-reversed NTT domain, all limbs in one
    pallas_call.  psi_rev_mont: u32[L, N]; qs, qinv_negs: u32[L].

    block_b=None takes the shared default from tune.DEFAULT_BLOCK — the
    registry (kernels/ops.py) threads tuned values here instead."""
    return _run("ntt_fwd", x, psi_rev_mont, qs, qinv_negs, block_b,
                interpret)


def ntt_inv_fused(x, psi_inv_rev_mont, n_inv_monts, qs, qinv_negs, *,
                  interpret: bool, block_b: int | None = None):
    """x: u32[..., L, N] bit-reversed NTT domain -> natural order."""
    src = jnp.concatenate([jnp.asarray(psi_inv_rev_mont, jnp.uint32),
                           _col(n_inv_monts)], axis=1)
    return _run("ntt_inv", x, src, qs, qinv_negs, block_b, interpret)


def ntt4_fwd_fused(x, psi1_mont, psi2_mont, corr_mont, qs, qinv_negs, *,
                   interpret: bool, block_b: int | None = None):
    """4-step forward negacyclic NTT, bit-identical to ntt_fwd_fused.

    x: u32[..., L, N] natural -> bit-reversed NTT domain.  Tables come from
    params.LimbTables: psi1_mont u32[L, n1], psi2_mont u32[L, n2],
    corr_mont u32[L, N] (N = n1*n2; the split is read off the table shapes,
    so retabled variants from params.retable_ntt4 change it here)."""
    n1, n2 = psi1_mont.shape[-1], psi2_mont.shape[-1]
    if n1 * n2 != x.shape[-1]:
        raise ValueError(f"4-step split {n1} x {n2} != N={x.shape[-1]}")
    src = jnp.concatenate([jnp.asarray(t, jnp.uint32)
                           for t in (psi1_mont, psi2_mont, corr_mont)],
                          axis=1)
    return _run("ntt4_fwd", x, src, qs, qinv_negs, block_b, interpret,
                n1, n2)


def ntt4_inv_fused(x, psi1_inv_mont, psi2_inv_mont, corr_inv_mont,
                   n_inv_monts, qs, qinv_negs, *, interpret: bool,
                   block_b: int | None = None):
    """4-step inverse negacyclic NTT, bit-identical to ntt_inv_fused.

    x: u32[..., L, N] bit-reversed NTT domain -> natural order."""
    n1, n2 = psi1_inv_mont.shape[-1], psi2_inv_mont.shape[-1]
    if n1 * n2 != x.shape[-1]:
        raise ValueError(f"4-step split {n1} x {n2} != N={x.shape[-1]}")
    src = jnp.concatenate([jnp.asarray(t, jnp.uint32)
                           for t in (psi1_inv_mont, psi2_inv_mont,
                                     corr_inv_mont)] + [_col(n_inv_monts)],
                          axis=1)
    return _run("ntt4_inv", x, src, qs, qinv_negs, block_b, interpret,
                n1, n2)
