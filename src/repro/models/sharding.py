"""Sharding policy: parameter/batch/cache PartitionSpecs for the production
mesh (see DESIGN.md §4).

Policy summary (axes: optional 'pod', 'data', 'model'):
  * 2-D weights [in, out]          -> P('data', 'model')    (ZeRO-FSDP x TP)
  * embed [V, d]                   -> P('model', None)      (vocab-sharded)
  * unembed [d, V]                 -> P('data', 'model')
  * MoE expert weights [E, in, out]-> P(None, None, 'model') (EP-free baseline;
                                      dispatch runs under shard_map over dp)
  * 1-D params (norms, biases, A_log, dt_bias, D) -> replicated
  * conv kernels [w, ch]           -> replicated
  * batch dims                     -> ('pod', 'data') when divisible
  * decode KV caches               -> batch over dp, seq over 'model'
                                      (B==1: seq over ('data','model'))

Stacked layer dims (leading L) are never sharded.  All rules check
divisibility and fall back to replication, so reduced smoke configs on one
CPU device lower with fully-replicated specs.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class AxisEnv:
    """Names + sizes of the mesh axes in play ((1,)-sized axes => no mesh)."""

    data: tuple[str, ...] = ("data",)   # FSDP / batch axes ('pod','data')
    model: str = "model"
    data_size: int = 1
    model_size: int = 1
    mesh: object = dataclasses.field(default=None, compare=False, hash=False)

    @property
    def dp(self):
        return self.data if self.data_size > 1 else None

    def mp(self, dim: int):
        return self.model if self.model_size > 1 and dim % self.model_size == 0 \
            else None

    def fsdp(self, dim: int):
        if self.data_size > 1 and dim % self.data_size == 0:
            return self.data if len(self.data) > 1 else self.data[0]
        return None

    def flat(self, dim: int):
        """All mesh axes as one flattened TP axis (weight-stationary
        serving); falls back to 'model' then replication."""
        total = self.data_size * self.model_size
        if total > 1 and dim % total == 0:
            return (*self.data, self.model)
        return self.mp(dim)


def axis_env_from_mesh(mesh) -> AxisEnv:
    names = mesh.axis_names
    data = tuple(n for n in names if n in ("pod", "data"))
    data_size = int(np.prod([mesh.shape[n] for n in data])) if data else 1
    model_size = int(mesh.shape["model"]) if "model" in names else 1
    return AxisEnv(data=data or ("data",), model="model",
                   data_size=data_size, model_size=model_size, mesh=mesh)


CPU_ENV = AxisEnv()  # sizes 1 -> every spec collapses to replicated


# ---------------------------------------------------------------------------
# parameter specs by path
# ---------------------------------------------------------------------------

_REPLICATED_2D = re.compile(r"conv_|router")


def _leaf_spec(path: str, shape, ax: AxisEnv):
    nd = len(shape)
    if nd <= 1:
        return P()
    if "unembed" in path:                       # must precede the embed rule
        return P(ax.fsdp(shape[0]), ax.mp(shape[1]))
    if "embed" in path and "patch" not in path and "frame" not in path:
        # [V, d] vocab-sharded
        return P(ax.mp(shape[0]), None)
    if _REPLICATED_2D.search(path):
        return P(*([None] * nd))
    if nd == 2:
        return P(ax.fsdp(shape[0]), ax.mp(shape[1]))
    if nd == 3:
        # stacked per-layer [L, in, out] or expert [E, in, out]
        if "expert" in path:
            return P(None, None, ax.mp(shape[2]))
        return P(None, ax.fsdp(shape[1]), ax.mp(shape[2]))
    if nd == 4:
        # stacked experts [L, E, in, out]
        return P(None, None, None, ax.mp(shape[3]))
    return P(*([None] * nd))


def _leaf_spec_serve_tp(path: str, shape, ax: AxisEnv):
    """Weight-stationary serving: shard every weight's OUT dim over the
    flattened mesh (pure TP) so decode never all-gathers weights; the
    per-matmul psum moves only [B, d]-sized partials."""
    nd = len(shape)
    if nd == 1:
        return P(ax.flat(shape[0]))
    if nd == 0:
        return P()
    if "embed" in path and "patch" not in path and "frame" not in path:
        return P(ax.flat(shape[0]), None)
    lead = [None] * (nd - 2)
    return P(*lead, None, ax.flat(shape[-1]))


def param_specs(params_abstract, ax: AxisEnv, mode: str = "train"):
    """pytree of ShapeDtypeStruct -> pytree of PartitionSpec.

    mode='train': 2-D ZeRO-FSDP x TP (the baseline everywhere).
    mode='serve_tp': flattened-mesh weight-stationary TP (decode
    hillclimb — see EXPERIMENTS.md §Perf).
    """
    fn = _leaf_spec if mode == "train" else _leaf_spec_serve_tp

    def visit(path, leaf):
        name = jax.tree_util.keystr(path)
        return fn(name, leaf.shape, ax)
    return jax.tree_util.tree_map_with_path(visit, params_abstract)


# ---------------------------------------------------------------------------
# activation constraints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------


def _have_mesh() -> bool:
    m = jax.sharding.get_abstract_mesh()
    return m is not None and not m.empty and m.shape_tuple


def constrain(x, *spec):
    """with_sharding_constraint that degrades to identity on 1-device runs."""
    if not _have_mesh():
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------


def batch_spec(ax: AxisEnv, batch_size: int, extra_dims: int = 1):
    """P over the leading batch dim; replicate when indivisible."""
    dp = ax.dp if (ax.dp and batch_size % ax.data_size == 0) else None
    return P(dp, *([None] * extra_dims))


def kv_cache_spec(ax: AxisEnv, batch_size: int):
    """[B, S, KH, hd]: batch over dp, seq over model; B==1 -> seq over
    (data..., model)."""
    if batch_size == 1:
        seq = (*ax.data, ax.model) if ax.data_size > 1 else ax.model
        return P(None, seq if ax.model_size > 1 else None, None, None)
    dp = ax.dp if batch_size % ax.data_size == 0 else None
    mp = ax.model if ax.model_size > 1 else None
    return P(dp, mp, None, None)


def ssm_state_spec(ax: AxisEnv, batch_size: int, n_heads: int):
    """[B, nh, hd, state]: batch over dp, heads over model."""
    dp = ax.dp if (batch_size % ax.data_size == 0 and batch_size > 1) else None
    return P(dp, ax.mp(n_heads), None, None)


def conv_state_spec(ax: AxisEnv, batch_size: int, ch: int):
    """[B, w-1, ch]."""
    dp = ax.dp if (batch_size % ax.data_size == 0 and batch_size > 1) else None
    return P(dp, None, ax.mp(ch))
