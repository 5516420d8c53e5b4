"""Pallas TPU kernel: fused encrypted FedAvg aggregation, limb-fused.

The server hot loop of the paper is  sum_i alpha_i * [[W_i]]  over client
ciphertexts.  Library implementations (PALISADE/TenSEAL wrappers) materialize
each weighted ciphertext in memory before the add; at HE's low arithmetic
intensity that doubles HBM traffic.  This kernel fuses weight-multiply +
modular accumulate: each ciphertext element is read exactly once, the
accumulator lives in VMEM.

Layout: cts u32[n_clients, B, L, N] (normal form, NTT domain), w_mont
u32[n_clients, L] Montgomery-form scalar weights (round(alpha_i * delta) * R
mod q_l).  The grid is (L, ceil(B / block_b)) over the lane-dense
u32[..., L, R, 128] view (kernels/tiling.py): the RNS limb is a grid
coordinate (a squeezed block dimension), its constants and the weights are
u32 tables prefetched into SMEM, and one `pallas_call` covers every limb —
kernel count is independent of limb depth.  The client loop is unrolled
inside the kernel.

VMEM: n_clients * block_b * N * 4B; for 16 clients, block_b=4, N=8192 ->
2 MiB in + 128 KiB out.
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


def _u32(a):
    return jnp.asarray(a, jnp.uint32)


def _agg_body(q_ref, qi_ref, w_ref, cts_ref, o_ref, *, n_clients: int,
              n_limbs: int):
    li = pl.program_id(0)
    q = q_ref[li]
    qi = qi_ref[li]
    acc = _ref.mont_mul(cts_ref[0], w_ref[li], q, qi)
    for i in range(1, n_clients):
        term = _ref.mont_mul(cts_ref[i], w_ref[i * n_limbs + li], q, qi)
        acc = _ref.mod_add(acc, term, q)
    o_ref[...] = acc


@functools.lru_cache(maxsize=128)
def _build(n_clients: int, l: int, rows: int, block_b: int,
           interpret: bool):
    body = functools.partial(_agg_body, n_clients=n_clients, n_limbs=l)
    cts_tile = pl.BlockSpec((n_clients, block_b, None, rows, tiling.LANES),
                            lambda li, bi, *_: (0, bi, li, 0, 0))
    tile = tiling.row_block(block_b, rows)

    @jax.jit
    def call(qs, qinv_negs, w, cts):
        b = cts.shape[1]
        return pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(l, pl.cdiv(b, block_b)),
                in_specs=[cts_tile], out_specs=tile),
            out_shape=jax.ShapeDtypeStruct(cts.shape[1:], jnp.uint32),
            interpret=interpret,
        )(qs, qinv_negs, w, cts)

    return call


def he_weighted_sum_fused(cts, w_mont, qs, qinv_negs, *, interpret: bool,
                          block_b: int | None = None):
    """sum_i w_i (*) ct_i mod q_l, all limbs in one pallas_call.

    cts: u32[C, ..., L, N]; w_mont: u32[C, L]; qs, qinv_negs: u32[L].
    block_b=None takes the shared default from tune.DEFAULT_BLOCK (4 here:
    the unrolled client loop holds n_clients tiles in VMEM at once, so the
    batch tile stays smaller than the single-input kernels')."""
    if block_b is None:
        block_b = _tune.default_block("weighted_sum")
    c = cts.shape[0]
    l, n = cts.shape[-2], cts.shape[-1]
    batch = cts.shape[1:-2]
    cts2 = tiling.rows_view(_u32(cts).reshape((c, -1, l, n)))
    b, rows = cts2.shape[1], cts2.shape[-2]
    call = _build(c, l, rows, min(block_b, b), interpret)
    out = call(_u32(qs), _u32(qinv_negs), _u32(w_mont).reshape(-1), cts2)
    return tiling.unrows(out, n).reshape(batch + (l, n))


# ---------------------------------------------------------------------------
# streaming variant: one client at a time into a running accumulator
# ---------------------------------------------------------------------------
#
# The batch kernel above needs all n_clients ciphertexts resident to fuse the
# client loop; at production scale ("millions of users") the server cannot
# materialize them.  The streaming kernel processes each arriving ciphertext
# as  acc' = acc + w (*) ct  — same fused multiply-accumulate, identical
# modular arithmetic (so the result is bit-for-bit equal to the batch path
# applied in arrival order), but server memory stays at one accumulator plus
# one in-flight ciphertext regardless of client count.


def _accum_body(q_ref, qi_ref, w_ref, ct_ref, acc_ref, o_ref):
    li = pl.program_id(0)
    q = q_ref[li]
    term = _ref.mont_mul(ct_ref[...], w_ref[li], q, qi_ref[li])
    o_ref[...] = _ref.mod_add(acc_ref[...], term, q)


@functools.lru_cache(maxsize=128)
def _build_accum(l: int, rows: int, block_b: int, interpret: bool):
    tile = tiling.row_block(block_b, rows)

    @jax.jit
    def call(qs, qinv_negs, w, ct, acc):
        b = ct.shape[0]
        return pl.pallas_call(
            _accum_body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(l, pl.cdiv(b, block_b)),
                in_specs=[tile, tile], out_specs=tile),
            out_shape=jax.ShapeDtypeStruct(ct.shape, jnp.uint32),
            interpret=interpret,
        )(qs, qinv_negs, w, ct, acc)

    return call


def he_weighted_accum_fused(acc, ct, w_mont, qs, qinv_negs, *,
                            interpret: bool, block_b: int | None = None):
    """acc + w (*) ct mod q_l, all limbs in one pallas_call.

    acc, ct: u32[..., L, N]; w_mont: u32[L] per-limb Montgomery weight."""
    if block_b is None:
        block_b = _tune.default_block("weighted_accum")
    l, n = ct.shape[-2], ct.shape[-1]
    batch = ct.shape[:-2]
    ct2 = tiling.rows_view(_u32(ct).reshape((-1, l, n)))
    acc2 = tiling.rows_view(_u32(jnp.broadcast_to(acc, ct.shape))
                            .reshape((-1, l, n)))
    b, rows = ct2.shape[0], ct2.shape[-2]
    call = _build_accum(l, rows, min(block_b, b), interpret)
    out = call(_u32(qs), _u32(qinv_negs), _u32(w_mont), ct2, acc2)
    return tiling.unrows(out, n).reshape(batch + (l, n))


# ---------------------------------------------------------------------------
# chunk-batched variant: the whole ready-chunk buffer in ONE launch
# ---------------------------------------------------------------------------
#
# The per-chunk accumulate above still costs one kernel launch per arriving
# ciphertext chunk — at n_chunks per update that makes the server's flush
# latency launch-bound, not bandwidth-bound.  This kernel folds a whole
# batch of ready chunks at once:  acc[k] += w[k] (*) ct[k]  with a PER-ROW
# weight table u32[K, L] (rows of one flush may belong to different
# clients), grid (L, ceil(K / block_k)).  The modular arithmetic per
# (row, limb, coefficient) is identical to the per-chunk kernel, so a
# flush stays bit-for-bit equal to folding its rows one at a time.  The
# weight table is prefetched into SMEM, so a flush larger than
# tiling.SMEM_WORDS weights runs as several launches of whole rows.


def _accum_chunks_body(q_ref, qi_ref, w_ref, ct_ref, acc_ref, o_ref, *,
                       n_limbs: int, block_k: int):
    li = pl.program_id(0)
    q = q_ref[li]
    qi = qi_ref[li]
    base = pl.program_id(1) * block_k

    def one(r, carry):
        w = w_ref[(base + r) * n_limbs + li]
        term = _ref.mont_mul(ct_ref[r], w, q, qi)
        o_ref[r] = _ref.mod_add(acc_ref[r], term, q)
        return carry

    jax.lax.fori_loop(0, block_k, one, 0)


@functools.lru_cache(maxsize=128)
def _build_accum_chunks(l: int, mid: int, rows: int, block_k: int,
                        interpret: bool):
    body = functools.partial(_accum_chunks_body, n_limbs=l, block_k=block_k)
    # [K, mid, L, R, 128]: the limb axis stays where the caller's layout
    # has it (squeezed block dim), so no transpose precedes the launch
    tile = pl.BlockSpec((block_k, mid, None, rows, tiling.LANES),
                        lambda li, ki, *_: (ki, 0, li, 0, 0))

    @jax.jit
    def call(qs, qinv_negs, w, ct, acc):
        k = ct.shape[0]
        return pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(l, pl.cdiv(k, block_k)),
                in_specs=[tile, tile], out_specs=tile),
            out_shape=jax.ShapeDtypeStruct(ct.shape, jnp.uint32),
            interpret=interpret,
        )(qs, qinv_negs, w, ct, acc)

    return call


def he_weighted_accum_chunks_fused(acc, cts, w_mont, qs, qinv_negs, *,
                                   interpret: bool,
                                   block_k: int | None = None):
    """acc[k] + w[k] (*) ct[k] mod q_l for every row k, one pallas_call
    (several when the weight table exceeds tiling.SMEM_WORDS).

    acc, cts: u32[K, ..., L, N]; w_mont: u32[K, L] per-row Montgomery
    weights broadcast over the middle (...) dims; qs, qinv_negs: u32[L].
    """
    if block_k is None:
        block_k = _tune.default_block("weighted_accum_chunks")
    k, l, n = cts.shape[0], cts.shape[-2], cts.shape[-1]
    shape = cts.shape

    def view(a):          # [K, ..., L, N] -> [K, mid, L, R, 128]
        return tiling.rows_view(_u32(a).reshape((k, -1, l, n)))

    ct2 = view(cts)
    acc2 = view(jnp.broadcast_to(acc, shape))
    block_k = min(block_k, k)
    call = _build_accum_chunks(l, ct2.shape[1], ct2.shape[-2], block_k,
                               interpret)
    step = max(tiling.SMEM_WORDS // (l * block_k), 1) * block_k
    w = _u32(w_mont)
    qs, qinv_negs = _u32(qs), _u32(qinv_negs)
    outs = []
    for s in range(0, k, step):
        ws = w[s:s + step]
        # whole blocks of weights, so the last block never reads SMEM
        # past the table
        ws = jnp.pad(ws, ((0, (-ws.shape[0]) % block_k), (0, 0)))
        outs.append(call(qs, qinv_negs, ws.reshape(-1), ct2[s:s + step],
                         acc2[s:s + step]))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    return tiling.unrows(out, n).reshape(shape)
