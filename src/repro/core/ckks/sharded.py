"""Multi-chip sharded HE engine: the limb-fused execution model mapped onto
a device mesh (DESIGN.md §8).

PR 2 made RNS limbs a grid/batch axis so every op is one kernel launch;
this module makes that grid axis a MESH axis.  A `(data, model)` mesh
shards

  * the limb axis L of every `u32[..., L, 2, N]` ciphertext tensor — and of
    the stacked constant tables (`CkksContext.tables`) — along ``model``;
  * the ciphertext chunk/batch axis along ``data``.

Every graph is a single `shard_map` dispatch whose body routes through the
backend registry (`kernels.ops.apply`), so each shard runs the same fused
jnp graph or per-shard Pallas launch as the single-device engine, just on
its local `(B/n_data, L/n_model)` block.  HE aggregation is pointwise per
(limb, coefficient): keygen / encrypt / weighted_sum / weighted_accum need
NO cross-chip communication; the only collective in the whole round is the
gather of limb shards at the final decrypt (CRT decode needs every limb).

Bit-identity contract (asserted in tests/test_sharded.py): every op here is
bit-for-bit equal to the single-device fused engine for any mesh shape.
For the samplers this relies on draw shapes being shard-invariant — see
cipher.py's sampler docstrings; keygen's uniform `a` (whose draw shape
includes L) is drawn in full on every model shard and sliced locally.

Sharding rules:
  * ``ctx.n_limbs`` (or the ciphertext's limb count) must be divisible by
    the ``model`` axis size — `launch.mesh.make_he_mesh` picks a legal
    factorization automatically.
  * batch axes are zero-padded up to a multiple of the ``data`` axis size
    inside the graph and sliced back after (zeros are inert under the
    modular ops and the padded rows are discarded).
  * every batched graph — including encrypt and seeded encrypt — shards
    BOTH axes.  Encrypt sampling stays shard-invariant because every draw
    is per chunk, keyed on fold_in(key, global_chunk_id) (DESIGN.md §9):
    a shard re-derives exactly its own rows' keys from its row offset.
    Only keygen replicates over ``data`` (its tensors have no batch axis).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.ckks import encoding
from repro.core.ckks.cipher import (DERIVE_FOLD_CHUNK, Ciphertext,
                                    _gaussian_residues, _ternary_residues,
                                    _uniform_residues, derive_chunk_keys,
                                    split_key)
from repro.core.ckks.params import CkksContext, LimbTables
from repro.kernels import ops, ref as _ref

_TABLE_FIELDS = ("qs", "qinv_negs", "r2s", "one_monts", "n_inv_monts",
                 "psi_rev_mont", "psi_inv_rev_mont",
                 "ntt4_psi1_mont", "ntt4_psi1_inv_mont",
                 "ntt4_psi2_mont", "ntt4_psi2_inv_mont",
                 "ntt4_corr_mont", "ntt4_corr_inv_mont")


def table_arrays(t: LimbTables) -> tuple:
    """LimbTables -> flat tuple of jnp arrays, in _TABLE_FIELDS order —
    the positional form `shard_map` bodies receive tables in.  Public:
    launch/fl_step.py builds its own sharded graphs from these."""
    return tuple(jnp.asarray(getattr(t, f)) for f in _TABLE_FIELDS)


def table_specs(model: str) -> tuple:
    """PartitionSpecs matching table_arrays: u32[L] fields shard along
    `model`, u32[L, .] twiddle/correction tables shard the limb row axis
    (the six ntt4_* 4-step tables included — limb-sharding covers the
    4-step NTT backend with zero new collectives)."""
    v, m = P(model), P(model, None)
    return (v, v, v, v, v, m, m, m, m, m, m, m, m)


def local_tables(tabs) -> LimbTables:
    """Rebuild a LimbTables view from per-shard (traced) arrays — the ops
    registry consumes it exactly like the host-numpy constant tables."""
    return LimbTables(**dict(zip(_TABLE_FIELDS, tabs)))


def _col(v):
    return v[:, None]


@dataclasses.dataclass(frozen=True)
class ShardedHe:
    """Sharded counterpart of the cipher-level API, bound to (ctx, mesh).

    Hashable (frozen dataclass over a hashable ctx and Mesh), so it is the
    static jit key of every sharded graph: a new mesh or context retraces.

    Attributes:
        ctx: CkksContext whose tables are sharded along `model_axis`.
        mesh: jax Mesh with at least (`data_axis`, `model_axis`) axes.
        data_axis: mesh axis name for ciphertext chunk/batch sharding.
        model_axis: mesh axis name for RNS-limb sharding.
    """

    ctx: CkksContext
    mesh: Any
    data_axis: str = "data"
    model_axis: str = "model"

    @property
    def n_data(self) -> int:
        return int(self.mesh.shape[self.data_axis])

    @property
    def n_model(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    def _check_limbs(self, l: int) -> None:
        if l % self.n_model:
            raise ValueError(
                f"limb count {l} is not divisible by model-axis size "
                f"{self.n_model}; build the mesh with "
                "launch.mesh.make_he_mesh(n_limbs, ...) so the limb grid "
                "axis maps onto whole shards")

    # -- placement helpers ---------------------------------------------------

    def ct_sharding(self, with_batch: bool = True) -> NamedSharding:
        """NamedSharding for u32[B, L, 2, N] ciphertext data (chunks ->
        data axis, limbs -> model axis)."""
        return NamedSharding(
            self.mesh,
            P(self.data_axis if with_batch else None, self.model_axis,
              None, None))

    def put_ciphertext(self, ct: Ciphertext,
                       with_batch: bool = True) -> Ciphertext:
        """Place ciphertext data onto the mesh (no-op if B or L do not
        divide; the graphs re-shard on entry anyway)."""
        b, l = ct.data.shape[0], ct.n_limbs
        if l % self.n_model or (with_batch and b % self.n_data):
            return ct
        return Ciphertext(
            data=jax.device_put(ct.data, self.ct_sharding(with_batch)),
            scale=ct.scale)

    # -- public sharded ops --------------------------------------------------

    def keygen(self, key) -> tuple[dict, dict]:
        """Sharded keygen; bit-identical keys to cipher.keygen(ctx, key).

        Returns (sk, pk) with every u32[L, N] component sharded along
        `model_axis`.  No collectives: the ternary/gaussian draws are
        shard-invariant and the uniform `a` is drawn in full per shard,
        sliced to local limbs.
        """
        self._check_limbs(self.ctx.n_limbs)
        token = ops.backend_token()
        with obs.kernel_launch("sharded.keygen", token) as kl:
            s_mont, pk0_mont, pk1_mont = kl.done(
                _keygen_graph(self, token, key))
        return ({"s_mont": s_mont},
                {"pk0_mont": pk0_mont, "pk1_mont": pk1_mont})

    def encrypt_values(self, pk: dict, values, key) -> Ciphertext:
        """f32[B, slots] -> fresh ciphertext, encode FFT + encrypt in ONE
        sharded dispatch with no collective.

        Limbs shard over `model_axis` AND the chunk/batch axis shards over
        `data_axis`: every (u, e0, e1) draw is per chunk, keyed on
        fold_in(key, global_chunk_id), so each shard re-derives exactly
        the rows it owns and the result is bit-identical to
        cipher.encrypt_values on one device for ANY mesh shape (the
        shard-invariance contract, DESIGN.md §9.1; asserted in
        tests/test_sharded.py).  Batches that do not divide the data axis
        are zero-padded in-graph and sliced back."""
        self._check_limbs(self.ctx.n_limbs)
        token = ops.backend_token()
        with obs.kernel_launch("sharded.encrypt_values", token,
                               rows=int(values.shape[0])) as kl:
            data = kl.done(_encrypt_values_graph(self, token,
                                                 pk["pk0_mont"],
                                                 pk["pk1_mont"], values,
                                                 key))
        return Ciphertext(data=data, scale=float(self.ctx.delta))

    def encrypt_coeffs(self, pk: dict, m_coeff, key,
                       scale: float | None = None) -> Ciphertext:
        """u32[B, L, N] encoded residues -> ciphertext; same sharding and
        bit-identity contract as encrypt_values (chunks -> `data_axis`,
        limbs -> `model_axis`, per-chunk key derivation)."""
        self._check_limbs(m_coeff.shape[-2])
        scale = float(scale if scale is not None else self.ctx.delta)
        token = ops.backend_token()
        with obs.kernel_launch("sharded.encrypt_coeffs", token,
                               rows=int(m_coeff.shape[0])) as kl:
            data = kl.done(_encrypt_coeffs_graph(self, token,
                                                 pk["pk0_mont"],
                                                 pk["pk1_mont"], m_coeff,
                                                 key))
        return Ciphertext(data=data, scale=scale)

    def encrypt_values_seeded(self, sk: dict, values, key, a_seed: int,
                              derive: int = DERIVE_FOLD_CHUNK
                              ) -> Ciphertext:
        """f32[B, slots] -> seeded secret-key ciphertext (uplink path) in
        ONE sharded dispatch with no collective.

        Same wire convention as cipher.encrypt_values_seeded: chunk b's
        c1 row is PRG-expanded per the wire-v2 `derive` algorithm
        (cipher.DERIVE_KEYFNS, DESIGN.md §9.2), so the wire layer ships
        (a_seed, c0) at ~0.5x fresh-ciphertext bytes and a streaming
        server regenerates each chunk independently.  Chunks shard over
        `data_axis`, limbs over `model_axis`; the result is bit-identical
        to the single-device path for any mesh shape — the noise stream is
        per chunk, and the public `a` stream (whose draw shape includes L)
        is drawn full-table per model shard and sliced, like keygen's `a`.
        `a_seed` must be unique per (client, round); reuse leaks m1 - m2.
        """
        self._check_limbs(self.ctx.n_limbs)
        a_base = jax.random.PRNGKey(int(a_seed))
        token = ops.backend_token()
        with obs.kernel_launch("sharded.encrypt_values_seeded", token,
                               rows=int(values.shape[0])) as kl:
            data = kl.done(_encrypt_seeded_values_graph(self, token,
                                                        sk["s_mont"],
                                                        values, key,
                                                        a_base,
                                                        int(derive)))
        return Ciphertext(data=data, scale=float(self.ctx.delta))

    def encrypt_coeffs_seeded(self, sk: dict, m_coeff, key, a_seed: int,
                              scale: float | None = None,
                              derive: int = DERIVE_FOLD_CHUNK
                              ) -> Ciphertext:
        """u32[B, L, N] encoded residues -> seeded ciphertext; sharding,
        derivation, and uniqueness contract as encrypt_values_seeded."""
        self._check_limbs(m_coeff.shape[-2])
        scale = float(scale if scale is not None else self.ctx.delta)
        a_base = jax.random.PRNGKey(int(a_seed))
        token = ops.backend_token()
        with obs.kernel_launch("sharded.encrypt_coeffs_seeded", token,
                               rows=int(m_coeff.shape[0])) as kl:
            data = kl.done(_encrypt_seeded_coeffs_graph(self, token,
                                                        sk["s_mont"],
                                                        m_coeff, key,
                                                        a_base,
                                                        int(derive)))
        return Ciphertext(data=data, scale=scale)

    def decrypt_to_coeffs(self, sk: dict, ct: Ciphertext):
        """Sharded decrypt -> u32[B, L, N] coefficient residues.

        mul_add + iNTT are limb-local; the gather of limb shards implied
        by reading the (replicated-spec) output is the ONLY collective of
        the whole aggregation round — CRT decode needs every limb.
        """
        self._check_limbs(ct.n_limbs)
        s = sk["s_mont"][: ct.n_limbs]
        token = ops.backend_token()
        with obs.kernel_launch("sharded.decrypt", token) as kl:
            return kl.done(_decrypt_graph(self, token, s, ct.data))

    def decrypt_values(self, sk: dict, ct: Ciphertext):
        """-> f32[B, slots] via the jnp decode path (2-limb)."""
        return encoding.decode_jnp(self.decrypt_to_coeffs(sk, ct),
                                   self.ctx, ct.scale)

    def weighted_sum(self, cts: Ciphertext, weights) -> Ciphertext:
        """Fused FedAvg aggregation, sharded: chunks over `data_axis`,
        limbs over `model_axis`, zero collectives.

        Args:
            cts: Ciphertext with data u32[C, B, L, 2, N] (clients leading).
            weights: python floats, len C.

        Returns:
            Ciphertext u32[B, L, 2, N], bit-identical to
            cipher.weighted_sum on one device.
        """
        self._check_limbs(cts.data.shape[-3])
        w_mont = jnp.asarray(encoding.encode_weights_mont(weights, self.ctx))
        token = ops.backend_token()
        with obs.kernel_launch("sharded.weighted_sum", token,
                               n_clients=int(cts.data.shape[0])) as kl:
            data = kl.done(_weighted_sum_graph(self, token, cts.data,
                                               w_mont))
        return Ciphertext(data=data, scale=cts.scale * self.ctx.delta)

    def weighted_accum(self, acc: Ciphertext, ct: Ciphertext,
                       weight: float) -> Ciphertext:
        """Streaming fold acc + w (*) ct, sharded like weighted_sum."""
        self._check_limbs(ct.n_limbs)
        w_mont = jnp.asarray(
            encoding.encode_scalar_residues(float(weight), self.ctx))
        token = ops.backend_token()
        with obs.kernel_launch("sharded.weighted_accum", token) as kl:
            data = kl.done(_weighted_accum_graph(self, token, acc.data,
                                                 ct.data, w_mont))
        return Ciphertext(data=data, scale=acc.scale)

    def weighted_accum_chunks(self, accs, cts, w_mont):
        """Batched flush on the ops-level layout: accs, cts u32[K, ..., L, N]
        (limbs at axis -2), w_mont u32[K, L].  Ready-chunk rows shard over
        `data_axis`, limbs over `model_axis`; used by wire.stream when a
        ShardedHe is attached."""
        self._check_limbs(cts.shape[-2])
        token = ops.backend_token()
        with obs.kernel_launch("sharded.weighted_accum_chunks", token,
                               rows=int(cts.shape[0])) as kl:
            return kl.done(_weighted_accum_chunks_graph(self, token, accs,
                                                        cts, w_mont))


# ---------------------------------------------------------------------------
# sharded graphs (module-level, cached by jit on the hashable engine)
# ---------------------------------------------------------------------------


def _pad_rows(x, mult: int, axis: int = 0):
    """Zero-pad `axis` up to a multiple of `mult` (static shapes)."""
    r = x.shape[axis]
    pad = (-r) % mult
    if not pad:
        return x, r
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), r


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _weighted_sum_graph(eng: ShardedHe, token, data, w_mont):
    ctx, da, ma = eng.ctx, eng.data_axis, eng.model_axis
    c, n = data.shape[0], data.shape[-1]
    l = data.shape[-3]
    t = ctx.tables.take(l)
    # [C, B..., L, 2, N] -> limbs at -2, flatten (B..., 2) into rows
    x = jnp.moveaxis(data, -3, -2)
    mid = x.shape[1:-2]
    x = x.reshape((c, -1, l, n))
    x, r = _pad_rows(x, eng.n_data, axis=1)

    def body(x, w, *tabs):
        return ops.apply("weighted_sum", local_tables(tabs), x, w)

    f = jax.shard_map(body, mesh=eng.mesh,
                  in_specs=(P(None, da, ma, None), P(None, ma))
                  + table_specs(ma),
                  out_specs=P(da, ma, None), check_vma=False)
    out = f(x, w_mont[:, :l], *table_arrays(t))[:r]
    return jnp.moveaxis(out.reshape(mid + (l, n)), -2, -3)


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _weighted_accum_graph(eng: ShardedHe, token, acc, ct, w_mont):
    ctx, da, ma = eng.ctx, eng.data_axis, eng.model_axis
    n = ct.shape[-1]
    l = ct.shape[-3]
    t = ctx.tables.take(l)
    x = jnp.moveaxis(ct, -3, -2)
    a = jnp.moveaxis(jnp.broadcast_to(acc, ct.shape), -3, -2)
    mid = x.shape[:-2]
    x = x.reshape((-1, l, n))
    a = a.reshape((-1, l, n))
    x, r = _pad_rows(x, eng.n_data)
    a, _ = _pad_rows(a, eng.n_data)

    def body(a, x, w, *tabs):
        return ops.apply("weighted_accum", local_tables(tabs), a, x, w)

    f = jax.shard_map(body, mesh=eng.mesh,
                  in_specs=(P(da, ma, None), P(da, ma, None), P(ma))
                  + table_specs(ma),
                  out_specs=P(da, ma, None), check_vma=False)
    out = f(a, x, w_mont[:l], *table_arrays(t))[:r]
    return jnp.moveaxis(out.reshape(mid + (l, n)), -2, -3)


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _weighted_accum_chunks_graph(eng: ShardedHe, token, accs, cts, w_mont):
    ctx, da, ma = eng.ctx, eng.data_axis, eng.model_axis
    k, n = cts.shape[0], cts.shape[-1]
    l = cts.shape[-2]
    t = ctx.tables.take(l)
    accs = jnp.broadcast_to(accs, cts.shape)
    mid = cts.shape[1:-2]
    x = cts.reshape((k, -1, l, n))
    a = accs.reshape((k, -1, l, n))
    x, r = _pad_rows(x, eng.n_data)
    a, _ = _pad_rows(a, eng.n_data)
    w, _ = _pad_rows(w_mont[:, :l], eng.n_data)

    def body(a, x, w, *tabs):
        return ops.apply("weighted_accum_chunks", local_tables(tabs), a, x,
                         w)

    f = jax.shard_map(body, mesh=eng.mesh,
                  in_specs=(P(da, None, ma, None), P(da, None, ma, None),
                            P(da, ma)) + table_specs(ma),
                  out_specs=P(da, None, ma, None), check_vma=False)
    out = f(a, x, w, *table_arrays(t))[:r]
    return out.reshape((r,) + mid + (l, n))


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _keygen_graph(eng: ShardedHe, token, key):
    ctx, ma = eng.ctx, eng.model_axis
    n = ctx.n_poly
    l_loc = ctx.n_limbs // eng.n_model
    qs_full = np.asarray(ctx.tables.qs)
    sigma = float(ctx.error_sigma)

    def body(key, *tabs):
        t = local_tables(tabs)
        q, qi = _col(t.qs), _col(t.qinv_negs)
        k_s, k_a, k_e = split_key(key, 3)
        s = ops.apply("ntt_fwd", t, _ternary_residues(k_s, (n,), t.qs))
        s_mont = _ref.mont_mul(s, jnp.broadcast_to(_col(t.r2s), s.shape),
                               q, qi)
        # the uniform draw's shape includes L: draw the FULL table on every
        # shard (replicated constant qs_full) and slice local limbs so the
        # stream matches the single-device graph bit-for-bit
        a_full = _uniform_residues(k_a, (n,), qs_full)
        li = jax.lax.axis_index(ma)
        a = jax.lax.dynamic_slice_in_dim(a_full, li * l_loc, l_loc, axis=0)
        e = ops.apply("ntt_fwd", t,
                      _gaussian_residues(k_e, (n,), t.qs, sigma))
        a_s = _ref.mont_mul(a, s_mont, q, qi)
        pk0 = _ref.mod_add(_ref.mod_neg(a_s, q), e, q)
        to_mont = lambda x: _ref.mont_mul(
            x, jnp.broadcast_to(_col(t.r2s), x.shape), q, qi)
        return s_mont, to_mont(pk0), to_mont(a)

    f = jax.shard_map(body, mesh=eng.mesh,
                  in_specs=(P(None),) + table_specs(ma),
                  out_specs=(P(ma, None),) * 3, check_vma=False)
    return f(key, *table_arrays(ctx.tables))


def _local_chunk_keys(eng: ShardedHe, key, b_loc: int,
                      derive: int = DERIVE_FOLD_CHUNK):
    """Keys for this data-shard's chunk rows, derived from GLOBAL chunk ids.

    Shard d of the data axis owns the contiguous rows
    [d * b_loc, (d + 1) * b_loc); derive_chunk_keys(key, global_offset, ..)
    re-derives exactly the keys the single-device trace would use for those
    rows — the whole shard-count-invariance argument in one line
    (DESIGN.md §9.1).  Every registered derive algorithm keys on the global
    chunk index, so the invariance holds per id."""
    start = jax.lax.axis_index(eng.data_axis) * b_loc
    return derive_chunk_keys(key, start, b_loc, derive)


def _encrypt_body_sharded(eng: ShardedHe, pk0, pk1, m_coeff, key, tabs):
    """Per-shard encrypt body: same op sequence and per-chunk key
    derivation as cipher._encrypt_body, limb constants from the local table
    shard, chunk keys from the shard's global row offset."""
    ctx = eng.ctx
    b_loc, n = m_coeff.shape[0], ctx.n_poly
    sigma = float(ctx.error_sigma)
    t = local_tables(tabs)
    q = _col(t.qs)
    k3 = jax.vmap(lambda k: split_key(k, 3))(
        _local_chunk_keys(eng, key, b_loc))
    m = ops.apply("ntt_fwd", t, m_coeff)
    u = ops.apply("ntt_fwd", t, jax.vmap(
        lambda k: _ternary_residues(k, (n,), t.qs))(k3[:, 0]))
    e0 = ops.apply("ntt_fwd", t, jax.vmap(
        lambda k: _gaussian_residues(k, (n,), t.qs, sigma))(k3[:, 1]))
    e1 = ops.apply("ntt_fwd", t, jax.vmap(
        lambda k: _gaussian_residues(k, (n,), t.qs, sigma))(k3[:, 2]))
    c0 = ops.apply("mul_add", t, u, pk0[None], _ref.mod_add(e0, m, q))
    c1 = ops.apply("mul_add", t, u, pk1[None], e1)
    return jnp.stack([c0, c1], axis=-2)


def _local_encode(eng: ShardedHe, values):
    """Encode this data shard's rows f32[b_loc, slots] and keep the model
    shard's limbs.  The encode FFT runs inside the shard_map body, on the
    shard's own rows — outside it, XLA all-gathers the chunk axis to run
    every chunk's FFT on every shard."""
    l_loc = eng.ctx.n_limbs // eng.n_model
    full = encoding.encode_jnp(values, eng.ctx)            # [b, L, N]
    li = jax.lax.axis_index(eng.model_axis)
    return jax.lax.dynamic_slice_in_dim(full, li * l_loc, l_loc, axis=1)


def _encrypt_shard_map(eng: ShardedHe, encode: bool = False):
    """Sharded pk encrypt; with `encode` the third operand is f32 values
    (rows on `data`, replicated over `model`) encoded in the body."""
    da, ma = eng.data_axis, eng.model_axis

    def body(pk0, pk1, m, key, *tabs):
        if encode:
            m = _local_encode(eng, m)
        return _encrypt_body_sharded(eng, pk0, pk1, m, key, tabs)

    m_spec = P(da, None) if encode else P(da, ma, None)
    return jax.shard_map(
        body, mesh=eng.mesh,
        in_specs=(P(ma, None), P(ma, None), m_spec, P(None))
        + table_specs(ma),
        out_specs=P(da, ma, None, None), check_vma=False)


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _encrypt_coeffs_graph(eng: ShardedHe, token, pk0, pk1, m_coeff, key):
    l = m_coeff.shape[-2]
    t = eng.ctx.tables.take(l)
    x, r = _pad_rows(m_coeff, eng.n_data)
    out = _encrypt_shard_map(eng)(pk0[:l], pk1[:l], x, key,
                                  *table_arrays(t))
    return out[:r]


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _encrypt_values_graph(eng: ShardedHe, token, pk0, pk1, values, key):
    t = eng.ctx.tables
    x, r = _pad_rows(values, eng.n_data)
    out = _encrypt_shard_map(eng, encode=True)(pk0, pk1, x, key,
                                               *table_arrays(t))
    return out[:r]


def _encrypt_seeded_body_sharded(eng: ShardedHe, s_mont, m_coeff, key,
                                 a_base, tabs,
                                 derive: int = DERIVE_FOLD_CHUNK):
    """Per-shard seeded (secret-key) encrypt body.

    The public c1 = a stream must match the server-side expand_a_rows
    regeneration bit for bit — for the SAME wire-negotiated derive id —
    and its draw shape includes L: so, like keygen's uniform `a`, every
    model shard draws the FULL limb table per chunk and slices its local
    limbs.  The secret noise draw is (N,) per chunk and limb-free (always
    fold_in — never wire-negotiated), so it broadcasts against the local
    primes."""
    ctx = eng.ctx
    b_loc, n = m_coeff.shape[0], ctx.n_poly
    sigma = float(ctx.error_sigma)
    t = local_tables(tabs)
    q = _col(t.qs)
    l_loc = ctx.n_limbs // eng.n_model
    qs_full = np.asarray(ctx.tables.qs)
    m = ops.apply("ntt_fwd", t, m_coeff)
    a_full = jax.vmap(lambda k: _uniform_residues(k, (n,), qs_full))(
        _local_chunk_keys(eng, a_base, b_loc, derive))  # [b_loc, L_full, N]
    li = jax.lax.axis_index(eng.model_axis)
    a = jax.lax.dynamic_slice_in_dim(a_full, li * l_loc, l_loc, axis=1)
    e = ops.apply("ntt_fwd", t, jax.vmap(
        lambda k: _gaussian_residues(k, (n,), t.qs, sigma))(
            _local_chunk_keys(eng, key, b_loc)))
    a_s = _ref.mont_mul(a, s_mont[None], q, _col(t.qinv_negs))
    c0 = _ref.mod_add(_ref.mod_neg(a_s, q), _ref.mod_add(e, m, q), q)
    return jnp.stack([c0, a], axis=-2)


def _encrypt_seeded_shard_map(eng: ShardedHe,
                              derive: int = DERIVE_FOLD_CHUNK,
                              encode: bool = False):
    """Sharded seeded encrypt; `encode` as in _encrypt_shard_map."""
    da, ma = eng.data_axis, eng.model_axis

    def body(s_mont, m, key, a_base, *tabs):
        if encode:
            m = _local_encode(eng, m)
        return _encrypt_seeded_body_sharded(eng, s_mont, m, key, a_base,
                                            tabs, derive)

    m_spec = P(da, None) if encode else P(da, ma, None)
    return jax.shard_map(
        body, mesh=eng.mesh,
        in_specs=(P(ma, None), m_spec, P(None), P(None)) + table_specs(ma),
        out_specs=P(da, ma, None, None), check_vma=False)


@functools.partial(jax.jit, static_argnames=("eng", "token", "derive"))
def _encrypt_seeded_coeffs_graph(eng: ShardedHe, token, s_mont, m_coeff,
                                 key, a_base,
                                 derive: int = DERIVE_FOLD_CHUNK):
    t = eng.ctx.tables
    x, r = _pad_rows(m_coeff, eng.n_data)
    out = _encrypt_seeded_shard_map(eng, derive)(s_mont, x, key, a_base,
                                                 *table_arrays(t))
    return out[:r]


@functools.partial(jax.jit, static_argnames=("eng", "token", "derive"))
def _encrypt_seeded_values_graph(eng: ShardedHe, token, s_mont, values, key,
                                 a_base, derive: int = DERIVE_FOLD_CHUNK):
    t = eng.ctx.tables
    x, r = _pad_rows(values, eng.n_data)
    out = _encrypt_seeded_shard_map(eng, derive, encode=True)(
        s_mont, x, key, a_base, *table_arrays(t))
    return out[:r]


@functools.partial(jax.jit, static_argnames=("eng", "token"))
def _decrypt_graph(eng: ShardedHe, token, s_mont, data):
    ctx, da, ma = eng.ctx, eng.data_axis, eng.model_axis
    l, n = data.shape[-3], data.shape[-1]
    t = ctx.tables.take(l)
    x, b = _pad_rows(data, eng.n_data)

    def body(s, x, *tabs):
        t = local_tables(tabs)
        c0 = x[..., 0, :]
        c1 = x[..., 1, :]
        phase = ops.apply("mul_add", t, c1, s[None], c0)
        return ops.apply("ntt_inv", t, phase)

    f = jax.shard_map(body, mesh=eng.mesh,
                  in_specs=(P(ma, None), P(da, ma, None, None))
                  + table_specs(ma),
                  out_specs=P(da, ma, None), check_vma=False)
    return f(s_mont, x, *table_arrays(t))[:b]
