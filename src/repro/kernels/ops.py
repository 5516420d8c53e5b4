"""Public wrappers over the limb-fused HE kernels, with a backend registry.

Execution model
---------------
RNS limbs are a batch/grid dimension, never a Python loop: every op consumes
the full u32[..., L, N] tensor in ONE call — a single fused jnp graph on the
`ref` backend, a single `pallas_call` with the limb index in the grid on the
`pallas` backend.  Per-limb constants (q, -q^{-1}, R^2, N^{-1}R, twiddle
tables) come pre-stacked as u32[L] / u32[L, N] arrays from
`CkksContext.tables` (params.LimbTables) and are sliced to the input's limb
count, so limb-dropped ciphertexts work transparently.

Backend registry
----------------
Each op is an entry in an op-table mapping backend name -> implementation:

  * "ref"    — pure-jnp oracle (repro/kernels/ref.py). Default on CPU: fast,
               exact, and what the FL examples/benchmarks run.
  * "pallas" — pl.pallas_call kernels. On CPU they run in interpret mode
               (kernel body executed in Python) for validation; on TPU they
               compile natively.
  * "pallas4" — like "pallas", but ntt_fwd/ntt_inv dispatch to the 4-step
               NTT kernels (kernels/ntt.py, DESIGN.md §10).  Every non-NTT
               op shares the "pallas" kernels.  All concrete backends are
               bit-identical (tests/test_gold.py).
  * "auto"   — per-op, per-SHAPE resolution through the kernels/tune.py
               tuning cache (DESIGN.md §12): a cache hit runs the measured
               winner (concrete backend + launch config — block_b, ntt4
               split), a miss runs the platform fallback
               with the shared defaults.  Resolution happens at trace
               time (shapes are static under jit) and the tuner's cache
               generation is folded into `backend_token()`, so cached
               graphs retrace when the cache (re)loads.

Selection is per-op: `set_backend("pallas")` flips every op,
`set_backend("pallas", op="weighted_sum")` flips one.  The interpret/compile
decision is made once (first use) from the JAX platform.  `backend_token()`
returns a hashable snapshot of the whole assignment for use as a static jit
key — the jitted encrypt/decrypt/aggregate graphs in core/ckks/cipher.py
retrace when the registry changes.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as _obs
from repro.kernels import he_agg as _he_agg
from repro.kernels import lift as _lift
from repro.kernels import ntt as _ntt
from repro.kernels import pointwise as _pointwise
from repro.kernels import ref as _ref
from repro.kernels import tune as _tune

OPS = ("ntt_fwd", "ntt_inv", "mul_add", "mod_lift", "weighted_sum",
       "weighted_accum", "weighted_accum_chunks")
BACKENDS = ("ref", "pallas", "pallas4", "auto")


def _env_backend() -> str:
    """Read + validate REPRO_HE_BACKEND at import time.  An unknown value
    used to land in the assignment unchecked and surface much later as a
    bare KeyError at first dispatch; fail at import with the fix instead."""
    name = os.environ.get("REPRO_HE_BACKEND", "ref")
    if name not in BACKENDS:
        raise ValueError(
            f"REPRO_HE_BACKEND={name!r} is not a known backend; expected one "
            f"of {'/'.join(BACKENDS)} — see the 'Environment variables & "
            "flags' table in README.md")
    return name


_ASSIGN: dict[str, str] = {op: _env_backend() for op in OPS}
_INTERPRET: bool | None = None


def _interpret() -> bool:
    """Interpret vs native-compile, decided once per process at first build."""
    global _INTERPRET
    if _INTERPRET is None:
        _INTERPRET = jax.default_backend() == "cpu"
    return _INTERPRET


def set_backend(name: str, op: str | None = None) -> None:
    """Select the backend for every op (op=None) or one op."""
    assert name in BACKENDS, name
    if op is None:
        for o in OPS:
            _ASSIGN[o] = name
    else:
        assert op in OPS, op
        _ASSIGN[op] = name


def get_backend(op: str | None = None) -> str:
    """Backend for `op`; with op=None, the common backend ("mixed" if the
    per-op assignments diverge)."""
    if op is not None:
        return _ASSIGN[op]
    names = set(_ASSIGN.values())
    return names.pop() if len(names) == 1 else "mixed"


def backend_token() -> tuple:
    """Hashable snapshot of (per-op assignment, interpret flag) — the static
    jit key that makes cached graphs retrace on registry changes.  With any
    op on `auto` the tuner's cache generation is part of the token: a cache
    (re)load may change what a dispatch resolves to, so graphs that embedded
    the old resolution must retrace (tests/test_tune.py pins this)."""
    tok = tuple(sorted(_ASSIGN.items())) + (("interpret", _interpret()),)
    if "auto" in _ASSIGN.values():
        tok += (("tune", _tune.generation()),)
    return tok


@functools.lru_cache(maxsize=256)
def _tables(ctx, l: int):
    """ctx's stacked constant tables sliced to the first l limbs."""
    return ctx.tables.take(l)


def _qcol(t):
    return t.qs[:, None]


# ---------------------------------------------------------------------------
# op-table: one fused implementation per (op, backend)
# ---------------------------------------------------------------------------


# Every implementation takes a trailing `cfg` kwarg (tune.KernelConfig or
# None).  cfg=None means "kernel defaults" — byte-identical to the
# pre-autotuner call, which is what explicit ref/pallas/pallas4 backend
# selections always pass.  The ref oracle has no launch geometry, so it
# ignores cfg entirely.


def _blk(cfg):
    return cfg.block_b if cfg is not None else None


def _ntt_fwd_ref(t, x, cfg=None):
    return _ref.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs)


def _ntt_fwd_pallas(t, x, cfg=None):
    return _ntt.ntt_fwd_fused(x, t.psi_rev_mont, t.qs, t.qinv_negs,
                              block_b=_blk(cfg), interpret=_interpret())


def _ntt_inv_ref(t, x, cfg=None):
    return _ref.ntt_inv_fused(x, t.psi_inv_rev_mont, t.n_inv_monts, t.qs,
                              t.qinv_negs)


def _ntt_inv_pallas(t, x, cfg=None):
    return _ntt.ntt_inv_fused(x, t.psi_inv_rev_mont, t.n_inv_monts, t.qs,
                              t.qinv_negs, block_b=_blk(cfg),
                              interpret=_interpret())


def _ntt_fwd_pallas4(t, x, cfg=None):
    return _ntt.ntt4_fwd_fused(x, t.ntt4_psi1_mont, t.ntt4_psi2_mont,
                               t.ntt4_corr_mont, t.qs, t.qinv_negs,
                               block_b=_blk(cfg), interpret=_interpret())


def _ntt_inv_pallas4(t, x, cfg=None):
    return _ntt.ntt4_inv_fused(x, t.ntt4_psi1_inv_mont,
                               t.ntt4_psi2_inv_mont, t.ntt4_corr_inv_mont,
                               t.n_inv_monts, t.qs, t.qinv_negs,
                               block_b=_blk(cfg), interpret=_interpret())


def _mul_add_ref(t, x, y_mont, z, cfg=None):
    return _ref.mul_add_fused(x, jnp.broadcast_to(y_mont, x.shape),
                              jnp.broadcast_to(z, x.shape), t.qs, t.qinv_negs)


def _mul_add_pallas(t, x, y_mont, z, cfg=None):
    return _pointwise.mul_add_fused(x, y_mont, z, t.qs, t.qinv_negs,
                                    block_b=_blk(cfg),
                                    interpret=_interpret())


def _mod_lift_ref(t, x, cfg=None):
    return _ref.mod_lift_fused(x, t.qs)


def _mod_lift_pallas(t, x, cfg=None):
    return _lift.mod_lift_fused(x, t.qs, block_b=_blk(cfg),
                                interpret=_interpret())


def _weighted_sum_ref(t, cts, w_mont, cfg=None):
    return _ref.he_weighted_sum_fused(cts, w_mont, t.qs, t.qinv_negs)


def _weighted_sum_pallas(t, cts, w_mont, cfg=None):
    return _he_agg.he_weighted_sum_fused(cts, w_mont, t.qs, t.qinv_negs,
                                         block_b=_blk(cfg),
                                         interpret=_interpret())


def _weighted_accum_ref(t, acc, ct, w_mont, cfg=None):
    return _ref.he_weighted_accum_fused(acc, ct, w_mont, t.qs, t.qinv_negs)


def _weighted_accum_pallas(t, acc, ct, w_mont, cfg=None):
    return _he_agg.he_weighted_accum_fused(acc, ct, w_mont, t.qs,
                                           t.qinv_negs, block_b=_blk(cfg),
                                           interpret=_interpret())


def _weighted_accum_chunks_ref(t, acc, cts, w_mont, cfg=None):
    return _ref.he_weighted_accum_chunks_fused(acc, cts, w_mont, t.qs,
                                               t.qinv_negs)


def _weighted_accum_chunks_pallas(t, acc, cts, w_mont, cfg=None):
    return _he_agg.he_weighted_accum_chunks_fused(acc, cts, w_mont, t.qs,
                                                  t.qinv_negs,
                                                  block_k=_blk(cfg),
                                                  interpret=_interpret())


_IMPL = {
    "ntt_fwd": {"ref": _ntt_fwd_ref, "pallas": _ntt_fwd_pallas,
                "pallas4": _ntt_fwd_pallas4},
    "ntt_inv": {"ref": _ntt_inv_ref, "pallas": _ntt_inv_pallas,
                "pallas4": _ntt_inv_pallas4},
    # pallas4 differs only in the NTT family; every other op shares the
    # limb-grid pallas kernel so REPRO_HE_BACKEND=pallas4 stays a full
    # backend assignment (same env canon as ref/pallas).
    "mul_add": {"ref": _mul_add_ref, "pallas": _mul_add_pallas,
                "pallas4": _mul_add_pallas},
    "mod_lift": {"ref": _mod_lift_ref, "pallas": _mod_lift_pallas,
                 "pallas4": _mod_lift_pallas},
    "weighted_sum": {"ref": _weighted_sum_ref,
                     "pallas": _weighted_sum_pallas,
                     "pallas4": _weighted_sum_pallas},
    "weighted_accum": {"ref": _weighted_accum_ref,
                       "pallas": _weighted_accum_pallas,
                       "pallas4": _weighted_accum_pallas},
    "weighted_accum_chunks": {"ref": _weighted_accum_chunks_ref,
                              "pallas": _weighted_accum_chunks_pallas,
                              "pallas4": _weighted_accum_chunks_pallas},
}


# shape-key extraction: which positional arg carries the [..., L, N] tensor
# whose batch size keys the tuning cache, and how its batch is counted.
# Shapes are static under jit, so `auto` resolution is a trace-time
# decision — the resolved (backend, config) is baked into the graph and
# `backend_token()` carries the tuner generation to force retraces.
_SHAPE_ARG = {"ntt_fwd": 0, "ntt_inv": 0, "mul_add": 0, "mod_lift": 0,
              "weighted_sum": 0, "weighted_accum": 1,
              "weighted_accum_chunks": 1}


def _shape_dims(op, tables, args):
    """(N, L, B) of one dispatch — B is the flattened batch the kernel
    wrappers grid over (leading-axis rows for the chunk kernel)."""
    x = args[_SHAPE_ARG[op]]
    if op == "mod_lift":
        # the lift input is u32[..., N] with NO limb axis (the client never
        # touched RNS); L is the table depth the dispatch was sliced to.
        return x.shape[-1], int(tables.qs.shape[0]), \
            int(math.prod(x.shape[:-1]))
    n, l = x.shape[-1], x.shape[-2]
    if op == "weighted_sum":
        b = math.prod(x.shape[1:-2])      # leading axis is the client count
    elif op == "weighted_accum_chunks":
        b = x.shape[0]                    # grid rows = chunk rows K
    else:
        b = math.prod(x.shape[:-2])
    return n, l, int(b)


def _variant_tables(tables, split):
    """tables with the ntt4_* fields rebuilt for a non-default split.

    Only the host-numpy constant-embedding path can be retabled; traced or
    sharded tables (core/ckks/sharded.py passes per-shard slices inside
    shard_map) keep their default split — the tuner's split choice simply
    doesn't apply there."""
    if not isinstance(tables.qs, np.ndarray):
        return tables
    from repro.core.ckks import params as _params

    return _params.retable_ntt4(tables, split[0], split[1])


def _resolve(op, tables, args):
    """(concrete backend, config|None) for one dispatch.  Explicit
    assignments keep cfg=None — byte-identical to the pre-autotuner call."""
    backend = _ASSIGN[op]
    if backend != "auto":
        return backend, None
    n, l, b = _shape_dims(op, tables, args)
    return _tune.resolve(op, n, l, b, _interpret())


def _dispatch(op, tables, *args):
    """Registry dispatch point for every op invocation.

    With REPRO_OBS unset this is exactly the raw implementation call —
    same jitted graph keys, same dispatch count (tests/test_obs.py pins
    it).  With REPRO_OBS=1 the call routes through obs.timed_kernel:
    eager invocations get blocked per-op wall timing under a
    jax.profiler.TraceAnnotation; invocations inside a jit/shard_map
    trace get a jax.named_scope so device profiles carry op names, plus a
    retrace counter — all recorded per backend so flat/pallas/pallas4
    runs are distinguishable (DESIGN.md §11).  `auto` resolves through
    the tuning cache first and stamps the resolved config into the span.
    """
    backend, cfg = _resolve(op, tables, args)
    if (cfg is not None and cfg.ntt4_split is not None
            and backend == "pallas4" and op in _tune.NTT_OPS):
        tables = _variant_tables(tables, cfg.ntt4_split)
    impl = _IMPL[op][backend]
    if not _obs.kernel_hooks_enabled():
        return impl(tables, *args, cfg=cfg)
    return _obs.timed_kernel(op, backend, backend_token(),
                             functools.partial(impl, cfg=cfg), tables,
                             *args, config=cfg)


def run_config(op, backend, cfg, tables, *args):
    """Run one op under an explicit (concrete backend, KernelConfig),
    bypassing the registry assignment — the tuner's measurement entry
    (tune._candidate_fn) and a debugging hook.  Applies the config's
    ntt4_split variant tables exactly like `_dispatch`."""
    assert backend in ("ref", "pallas", "pallas4"), backend
    if (cfg is not None and cfg.ntt4_split is not None
            and backend == "pallas4" and op in _tune.NTT_OPS):
        tables = _variant_tables(tables, cfg.ntt4_split)
    return _IMPL[op][backend](tables, *args, cfg=cfg)


def apply(op, tables, *args):
    """Dispatch `op` through the registry with explicit constant tables.

    Args:
        op: one of OPS.
        tables: a `params.LimbTables` — may hold host numpy arrays (the
            normal constant-embedding path) OR traced/sharded jnp arrays.
            The sharded engine (core/ckks/sharded.py) builds per-shard
            tables inside `shard_map` and routes every kernel through here,
            so per-op backend selection applies unchanged across chips.
        *args: the op's positional tensor arguments (see the public
            wrappers below for each op's layout contract).

    Returns:
        The op's result with the same layout as the public wrapper.
    """
    return _dispatch(op, tables, *args)


# ---------------------------------------------------------------------------
# public fused ops (ciphertext-limb layout: u32[..., L, N])
# ---------------------------------------------------------------------------


def ntt_fwd(x, ctx):
    """Forward negacyclic NTT over every limb in one launch.

    Args:
        x: u32[..., L, N] coefficient-domain residues, natural order.
        ctx: CkksContext (tables sliced to x's limb count).

    Returns:
        u32[..., L, N] in bit-reversed NTT domain.
    """
    return _dispatch("ntt_fwd", _tables(ctx, x.shape[-2]), x)


def ntt_inv(x, ctx):
    """Inverse negacyclic NTT over every limb in one launch.

    Args:
        x: u32[..., L, N] bit-reversed NTT-domain residues.
        ctx: CkksContext.

    Returns:
        u32[..., L, N] coefficient-domain residues, natural order.
    """
    return _dispatch("ntt_inv", _tables(ctx, x.shape[-2]), x)


def mul_add(x, y_mont, z, ctx):
    """Fused x (*) y_mont + z — the encrypt/decrypt workhorse.

    Args:
        x: u32[..., L, N] normal-form residues.
        y_mont: u32[..., L, N] Montgomery-form operand (broadcastable to x).
        z: u32[..., L, N] normal-form addend (broadcastable to x).
        ctx: CkksContext.

    Returns:
        u32[..., L, N] normal-form result, one fused call over all limbs.
    """
    return _dispatch("mul_add", _tables(ctx, x.shape[-2]), x, y_mont, z)


def mod_lift(x, n_limbs, ctx):
    """Per-limb modular lift: residues of raw u32 rows across the limb grid.

    Args:
        x: u32[..., N] full-range 32-bit words with NO limb axis —
            transcipher-masked coefficients or keystream pads
            (DESIGN.md §15).
        n_limbs: limb count L of the target ciphertext level.
        ctx: CkksContext.

    Returns:
        u32[..., L, N] with out[..., l, :] = x mod q_l — the transcipher
        server unmask's first step, feeding ntt_fwd.
    """
    return _dispatch("mod_lift", _tables(ctx, n_limbs), x)


def weighted_sum(cts, w_mont, ctx):
    """Batch FedAvg aggregation: sum_i w_i (*) ct_i over the leading axis.

    Args:
        cts: u32[C, ..., L, N] client ciphertext residues (NTT domain).
        w_mont: u32[C, L] Montgomery-form scalar weights per (client, limb).
        ctx: CkksContext.

    Returns:
        u32[..., L, N] aggregate; each element read once, accumulator in
        VMEM on the pallas backend.
    """
    l = cts.shape[-2]
    return _dispatch("weighted_sum", _tables(ctx, l), cts, w_mont[:, :l])


def weighted_accum(acc, ct, w_mont, ctx):
    """Streaming aggregation step: acc + w (*) ct.

    Args:
        acc: u32[..., L, N] running modular accumulator.
        ct: u32[..., L, N] one arriving ciphertext.
        w_mont: u32[L] Montgomery scalar weight.
        ctx: CkksContext.

    Returns:
        u32[..., L, N] updated accumulator.  One client folded into the
        running sum — the O(1)-memory server path (repro.wire.stream);
        bit-identical to weighted_sum applied in arrival order.
    """
    l = ct.shape[-2]
    return _dispatch("weighted_accum", _tables(ctx, l), acc, ct, w_mont[:l])


def weighted_accum_chunks(acc, cts, w_mont, ctx):
    """Batched streaming flush: acc[k] + w[k] (*) ct[k] for every ready
    chunk row k in ONE launch.

    Args:
        acc: u32[K, ..., L, N] per-row accumulators (zeros for fresh rows).
        cts: u32[K, ..., L, N] ready ciphertext chunks; rows may belong to
            different clients and different chunk indices.
        w_mont: u32[K, L] per-row Montgomery scalar weights.
        ctx: CkksContext.

    Returns:
        u32[K, ..., L, N] updated accumulators.  Bit-identical to calling
        weighted_accum row by row — the wire/stream flush invariant.
    """
    l = cts.shape[-2]
    return _dispatch("weighted_accum_chunks", _tables(ctx, l), acc, cts,
                     w_mont[:, :l])


# limb-wise helpers with no dedicated kernel (cheap, always ref) ------------


def mod_add(a, b, ctx):
    t = _tables(ctx, a.shape[-2])
    return _ref.mod_add(a, jnp.broadcast_to(b, a.shape), _qcol(t))


def mod_sub(a, b, ctx):
    t = _tables(ctx, a.shape[-2])
    return _ref.mod_sub(a, jnp.broadcast_to(b, a.shape), _qcol(t))


def mod_neg(a, ctx):
    return _ref.mod_neg(a, _qcol(_tables(ctx, a.shape[-2])))


def to_mont(a, ctx):
    t = _tables(ctx, a.shape[-2])
    return _ref.mont_mul(a, jnp.broadcast_to(t.r2s[:, None], a.shape),
                         _qcol(t), t.qinv_negs[:, None])


def from_mont(a, ctx):
    t = _tables(ctx, a.shape[-2])
    return _ref.mont_mul(a, jnp.ones_like(a), _qcol(t), t.qinv_negs[:, None])


def mont_mul(a, b_mont, ctx):
    t = _tables(ctx, a.shape[-2])
    return _ref.mont_mul(a, jnp.broadcast_to(b_mont, a.shape), _qcol(t),
                         t.qinv_negs[:, None])
