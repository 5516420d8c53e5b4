"""Distributed HE secure-aggregation step (the paper's server hot loop,
mapped onto the production mesh).

Two sharding regimes (DESIGN.md §8):

  * limb-sharded — when the mesh's ``model`` axis size divides the RNS
    limb count, the step routes through the sharded engine layout: limbs
    shard along ``model``, ciphertext chunks along every other axis, and
    the fused weighted-sum runs as one `shard_map` dispatch with zero
    collectives (HE aggregation is pointwise per (limb, coefficient)).
  * chunk-only (fallback) — otherwise the [n_chunks] axis shards across
    every mesh axis (production meshes have model=16 > L); still zero
    collectives, memory-bound.

The plaintext remainder aggregates the same way in both regimes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.ckks import encoding
from repro.core.ckks.params import CkksContext, make_context
from repro.core.ckks.sharded import local_tables, table_arrays, table_specs
from repro.kernels import ops


@dataclasses.dataclass(frozen=True)
class HeAggSpec:
    """Static description of one aggregation round's tensors."""

    n_clients: int
    n_chunks: int            # ciphertexts per client (padded to mesh size)
    n_plain: int             # plaintext parameters (padded to mesh size)
    ctx: CkksContext

    @staticmethod
    def for_model(n_params: int, p_ratio: float, n_clients: int,
                  mesh_size: int, ctx: CkksContext | None = None):
        ctx = ctx or make_context()
        n_enc = int(round(n_params * p_ratio))
        chunks = max(1, -(-n_enc // ctx.slots))
        chunks = -(-chunks // mesh_size) * mesh_size
        n_plain = n_params - n_enc
        n_plain = -(-n_plain // mesh_size) * mesh_size
        return HeAggSpec(n_clients=n_clients, n_chunks=chunks,
                         n_plain=n_plain, ctx=ctx)

    def input_specs(self):
        sds = jax.ShapeDtypeStruct
        c, l, n = self.n_clients, self.ctx.n_limbs, self.ctx.n_poly
        return {
            "cts": sds((c, self.n_chunks, l, 2, n), jnp.uint32),
            "plain": sds((c, self.n_plain), jnp.float32),
        }

    def limb_sharded(self, mesh) -> bool:
        """True when the mesh's model axis can host whole limb shards."""
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        m = sizes.get("model", 0)
        return m > 0 and self.ctx.n_limbs % m == 0 \
            and self.n_chunks % (mesh.size // m) == 0

    def shardings(self, mesh):
        """NamedShardings for the step inputs.

        Limb-sharded regime: cts [C, chunks, L, 2, N] put chunks on the
        non-model axes and limbs on ``model``.  Fallback: chunks across
        every axis, limbs replicated.
        """
        axes = tuple(mesh.axis_names)
        if self.limb_sharded(mesh):
            data_axes = tuple(a for a in axes if a != "model")
            return {
                "cts": NamedSharding(
                    mesh, P(None, data_axes, "model", None, None)),
                "plain": NamedSharding(mesh, P(None, data_axes)),
            }
        return {
            "cts": NamedSharding(mesh, P(None, axes, None, None, None)),
            "plain": NamedSharding(mesh, P(None, axes)),
        }

    def wire_bytes_per_client(self) -> int:
        return self.n_chunks * self.ctx.ciphertext_bytes(packed=False) \
            + 4 * self.n_plain


def make_he_agg_step(spec: HeAggSpec, weights: list[float], mesh=None):
    """Server aggregation: sum_i w_i (*) ct_i (HE) + sum_i w_i plain_i.

    With a mesh whose model axis divides the limb count, the HE part is an
    explicit `shard_map` over (chunks -> data axes, limbs -> model); the
    body dispatches through the backend registry per shard.  Without a
    mesh (or when limbs don't divide) the single-device fused op is used
    and any sharding comes from jit's in_shardings alone.
    """
    ctx = spec.ctx
    w_mont = jnp.asarray(
        encoding.encode_weights_mont(weights, ctx))        # [C, L]
    w_plain = jnp.asarray(np.asarray(weights, np.float32))
    limb_sharded = mesh is not None and spec.limb_sharded(mesh)

    if limb_sharded:
        data_axes = tuple(a for a in mesh.axis_names if a != "model")
        tabs = table_arrays(ctx.tables)

        def he_body(x, w, *tabs):
            return ops.apply("weighted_sum", local_tables(tabs), x, w)

        he = jax.shard_map(
            he_body, mesh=mesh,
            in_specs=(P(None, data_axes, None, "model", None),
                      P(None, "model")) + table_specs("model"),
            out_specs=P(data_axes, None, "model", None), check_vma=False)

        def step(cts, plain):
            # [C, chunks, L, 2, N] -> limbs at axis -2 for the kernels
            x = jnp.moveaxis(cts, -3, -2)
            enc = jnp.moveaxis(he(x, w_mont, *tabs), -2, -3)
            pt = jnp.einsum("c,cp->p", w_plain, plain)
            return enc, pt

        return step

    def step(cts, plain):
        x = jnp.moveaxis(cts, -3, -2)
        enc = ops.weighted_sum(x, w_mont, ctx)
        enc = jnp.moveaxis(enc, -2, -3)
        pt = jnp.einsum("c,cp->p", w_plain, plain)
        return enc, pt

    return step


def jit_he_agg_step(spec: HeAggSpec, mesh, weights: list[float]):
    sh = spec.shardings(mesh)
    return jax.jit(
        make_he_agg_step(spec, weights, mesh=mesh),
        in_shardings=(sh["cts"], sh["plain"]),
        out_shardings=(None, None),
    )
