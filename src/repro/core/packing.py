"""Flatten/partition/pack model parameters for selective HE.

The FL/HE boundary works on a single flat f32 vector per model (the paper's
``flatten``/``reshape`` APIs, Table 3).  Selection masks are *static* per FL
task (the paper fixes M after round 1), so the mask partition is realized as
constant index arrays -> jit-friendly gathers/scatters with static shapes.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class FlatSpec:
    """Shape bookkeeping for pytree <-> flat-vector roundtrips."""

    treedef: object
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[object, ...]
    sizes: tuple[int, ...]
    offsets: tuple[int, ...]   # start offset of each leaf in the flat vector

    @property
    def total(self) -> int:
        return self.offsets[-1] + self.sizes[-1] if self.sizes else 0


def make_flat_spec(params) -> FlatSpec:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    offsets = tuple(int(o) for o in np.concatenate([[0], np.cumsum(sizes)[:-1]]))
    return FlatSpec(treedef=treedef, shapes=shapes, dtypes=dtypes, sizes=sizes,
                    offsets=offsets)


def flatten_params(params):
    """pytree -> (f32[P], FlatSpec)."""
    spec = make_flat_spec(params)
    leaves = jax.tree_util.tree_leaves(params)
    vec = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    return vec, spec


def unflatten_params(vec, spec: FlatSpec):
    """f32[P] -> pytree with spec's shapes/dtypes."""
    leaves = []
    for off, size, shape, dt in zip(spec.offsets, spec.sizes, spec.shapes,
                                    spec.dtypes):
        leaves.append(jax.lax.dynamic_slice_in_dim(vec, off, size)
                      .reshape(shape).astype(dt))
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# mask partition (static indices)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MaskPartition:
    """Static index arrays splitting a flat vector by a boolean mask.

    ``enc_idx``/``plain_idx`` are host numpy int32 arrays (constants baked
    into the jitted round step).  ``n_enc_padded`` pads the encrypted segment
    to a whole number of CKKS slot blocks.
    """

    n_total: int
    enc_idx: np.ndarray
    plain_idx: np.ndarray
    slots: int

    @property
    def n_enc(self) -> int:
        return int(self.enc_idx.size)

    @property
    def n_plain(self) -> int:
        return int(self.plain_idx.size)

    @property
    def n_chunks(self) -> int:
        return max(1, -(-self.n_enc // self.slots))

    @property
    def n_enc_padded(self) -> int:
        return self.n_chunks * self.slots

    @property
    def ratio(self) -> float:
        return self.n_enc / max(1, self.n_total)


def make_partition(mask: np.ndarray, slots: int) -> MaskPartition:
    mask = np.asarray(mask, dtype=bool)
    return MaskPartition(
        n_total=int(mask.size),
        enc_idx=np.where(mask)[0].astype(np.int32),
        plain_idx=np.where(~mask)[0].astype(np.int32),
        slots=int(slots),
    )


def split_by_mask(vec, part: MaskPartition):
    """f32[P] -> (enc f32[n_chunks, slots] zero-padded, plain f32[n_plain])."""
    enc = vec[jnp.asarray(part.enc_idx)]
    pad = part.n_enc_padded - part.n_enc
    enc = jnp.pad(enc, (0, pad)).reshape(part.n_chunks, part.slots)
    plain = vec[jnp.asarray(part.plain_idx)]
    return enc, plain


def merge_by_mask(enc_chunks, plain, part: MaskPartition):
    """Inverse of split_by_mask -> f32[P].

    A pure placement of values, done on the host: on the device the two
    scatters and their index arrays take ~4x the model's size (over 10 GB
    at qwen1.5-0.5b width), beside the model being replaced."""
    out = np.zeros((part.n_total,), dtype=np.float32)
    out[part.enc_idx] = np.asarray(enc_chunks, np.float32).reshape(-1)[
        : part.n_enc]
    out[part.plain_idx] = np.asarray(plain, np.float32)
    return jnp.asarray(out)
