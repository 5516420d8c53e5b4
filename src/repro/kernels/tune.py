"""Shape-keyed kernel autotuner: swept launch configs, cached winners,
`auto` backend resolution (DESIGN.md §12).

Every hot HE op bottoms out in a handful of Pallas launch parameters that
used to be frozen at guesses: a per-kernel `block_b`, the sqrt heuristic
for `params.ntt4_split`, and a process-wide env var for the flat-vs-4-step
NTT choice.  This module makes all of them a *measured, per-shape*
decision:

  * **config** — `KernelConfig(block_b, ntt4_split)` is the full
    launch geometry of one kernel invocation.  `DEFAULT_BLOCK` is the one
    table every kernel default routes through (kernels/{ntt,pointwise,
    he_agg}.py take `block_b=None` and ask here), so block sizes live in
    exactly one place.
  * **sweep** — `sweep_op()` measures every candidate
    (backend x block_b x ntt4_split) for one `(op, N, L, B)`
    point with `block_until_ready` wall time, pruning candidates whose
    roofline-model estimate (memory traffic / HBM bandwidth + per-grid-
    step launch overhead, peaks from kernels/peaks.py) is
    hopeless before ever running them.  The default config is always a
    candidate, so the winner is never slower than the default at
    measurement time.
  * **cache** — winners persist as JSON keyed by
    `op|N<n>|L<l>|B<b>|<platform>` with a meta block recording
    `ops.backend_token()`, platform, and device count at tune time.
    `REPRO_HE_TUNE_CACHE` names the file (README env table); entries for
    a different platform, unknown ops, or malformed configs are stale and
    ignored.
  * **auto** — `REPRO_HE_BACKEND=auto` (kernels/ops.py) resolves every
    dispatch through `resolve()`: cache hit -> the measured winner
    (backend + config), miss -> `DEFAULT_BLOCK` on the platform fallback
    backend.  `generation()` is folded into `ops.backend_token()` so
    jitted graphs retrace whenever the cache (re)loads and the resolved
    config may have changed.

Correctness invariant: a config only changes LAUNCH GEOMETRY — block
sizes, sub-NTT factorization — never the modular
arithmetic, so every candidate reproduces the gold KATs bit-exactly
(tests/test_tune.py sweeps the full grid against tests/golden/).

Module-level imports stay stdlib-only: the kernel modules import this one
for their defaults, so jax/kernels are imported lazily inside functions.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings

# ---------------------------------------------------------------------------
# launch-config defaults: the ONE table kernel block sizes route through
# ---------------------------------------------------------------------------

# Per-op default tile height (batch rows per grid step).  weighted_sum and
# weighted_accum_chunks hold n_clients / block_k ciphertext tiles resident
# at once, so their default tile is half the pointwise ops' (the VMEM
# budget note in kernels/he_agg.py) — previously an uncommented magic "4"
# in one signature and "8" in the rest.
DEFAULT_BLOCK = {
    "ntt_fwd": 8,
    "ntt_inv": 8,
    "mul_add": 8,
    "mod_lift": 8,
    "weighted_sum": 4,
    "weighted_accum": 8,
    "weighted_accum_chunks": 4,
}

BLOCK_CANDIDATES = (1, 2, 4, 8, 16)
NTT_OPS = ("ntt_fwd", "ntt_inv")

CACHE_VERSION = 1
CACHE_ENV = "REPRO_HE_TUNE_CACHE"

# roofline pruning rule (DESIGN.md §12.3): a candidate whose modelled time
# exceeds PRUNE_RATIO x the best modelled candidate is skipped unmeasured.
PRUNE_RATIO = 3.0
# per-grid-step dispatch overhead for the launch term of the model; the
# exact value only shifts where the memory and launch terms cross, and the
# rule is a >=3x filter, so order of magnitude is enough.
LAUNCH_OVERHEAD_S = 2e-6


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """Launch geometry of one kernel invocation — never arithmetic.

    block_b: batch rows per grid step (block_k for the chunk kernel).
    ntt4_split: (n1, n2) sub-NTT factorization, None = params.ntt4_split's
        sqrt heuristic (4-step NTT ops only).
    """

    block_b: int
    ntt4_split: tuple[int, int] | None = None

    def to_json(self) -> dict:
        return {"block_b": self.block_b,
                "ntt4_split": list(self.ntt4_split)
                if self.ntt4_split else None}

    @classmethod
    def from_json(cls, doc: dict) -> "KernelConfig":
        split = doc.get("ntt4_split")
        return cls(block_b=int(doc["block_b"]),
                   ntt4_split=tuple(int(x) for x in split) if split
                   else None)


def default_config(op: str) -> KernelConfig:
    """The config a dispatch uses with no cache entry: the DEFAULT_BLOCK
    tile and the sqrt split — exactly the pre-autotuner behaviour."""
    return KernelConfig(block_b=DEFAULT_BLOCK[op])


def default_block(op: str) -> int:
    """Kernel-signature fallback: kernels/{ntt,pointwise,he_agg}.py call
    this when their `block_b`/`block_k` kwarg is left None."""
    return DEFAULT_BLOCK[op]


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------


def shape_key(op: str, n: int, l: int, b: int, platform: str) -> str:
    """Cache key for one tuned point.  Shape-exact: a different batch or
    limb count is a different entry (no interpolation)."""
    return f"{op}|N{n}|L{l}|B{b}|{platform}"


@dataclasses.dataclass
class _CacheEntry:
    backend: str
    config: KernelConfig
    tuned_ms: float = float("nan")
    default_ms: float = float("nan")


_ENTRIES: dict[str, _CacheEntry] = {}
_GENERATION = 0          # bumped on every load/clear/put -> backend_token
_LOADED_PATH: str | None = None
_LOAD_ATTEMPTED = False


def cache_path() -> str | None:
    """The JSON tuning-cache path (REPRO_HE_TUNE_CACHE), None if unset."""
    return os.environ.get(CACHE_ENV) or None


def generation() -> int:
    """Monotonic cache state counter, folded into `ops.backend_token()`
    when any op is assigned `auto`: a (re)load or edit retraces every
    jitted graph that embedded a resolved config."""
    return _GENERATION


def clear_cache() -> None:
    """Drop every in-memory entry (resolution falls back to defaults)."""
    global _GENERATION, _LOADED_PATH, _LOAD_ATTEMPTED
    _ENTRIES.clear()
    _LOADED_PATH = None
    _LOAD_ATTEMPTED = True      # an explicit clear pins "empty", no reload
    _GENERATION += 1


def put(op: str, n: int, l: int, b: int, platform: str, backend: str,
        config: KernelConfig, tuned_ms: float = float("nan"),
        default_ms: float = float("nan")) -> None:
    """Insert/overwrite one resolved winner (sweep_op and tests)."""
    global _GENERATION
    _ENTRIES[shape_key(op, n, l, b, platform)] = _CacheEntry(
        backend=backend, config=config, tuned_ms=tuned_ms,
        default_ms=default_ms)
    _GENERATION += 1


def load_cache(path: str | None = None) -> int:
    """Load a JSON tuning cache, REPLACING the in-memory entries.

    Returns the number of entries accepted.  Stale entries — unknown op
    names, malformed configs, keys whose platform tag differs from the
    running platform — are skipped one by one, so a cache tuned on TPU
    degrades to defaults on CPU instead of mis-steering it; a missing or
    unreadable file loads as empty.  Always bumps `generation()`.
    """
    global _GENERATION, _LOADED_PATH, _LOAD_ATTEMPTED
    import jax

    platform = jax.default_backend()
    path = path if path is not None else cache_path()
    _ENTRIES.clear()
    _LOAD_ATTEMPTED = True
    _LOADED_PATH = path
    _GENERATION += 1
    if not path:
        return 0
    try:
        with open(path) as f:
            doc = json.load(f)
        raw = doc.get("entries", {})
    except FileNotFoundError:
        # a named-but-not-yet-written cache is the normal first-run state
        return 0
    except (OSError, json.JSONDecodeError, AttributeError) as e:
        # A cache that EXISTS but cannot be read (permissions, truncation,
        # corruption, non-dict JSON) silently disabling tuning is the bug
        # this guards: surface it once, visibly, and count it.
        from repro import obs
        obs.counter("tune_cache_load_errors_total").inc()
        warnings.warn(
            f"tuning cache {path!r} (from {CACHE_ENV}) could not be loaded"
            f" ({e!r}); autotuned configs are disabled and every `auto`"
            f" dispatch falls back to defaults — fix or delete the file",
            RuntimeWarning, stacklevel=2)
        return 0
    accepted = 0
    for key, e in raw.items():
        try:
            op, _, _, _, key_platform = key.split("|")
            if op not in DEFAULT_BLOCK or key_platform != platform:
                continue
            backend = e["backend"]
            if backend not in ("ref", "pallas", "pallas4"):
                continue
            _ENTRIES[key] = _CacheEntry(
                backend=backend,
                config=KernelConfig.from_json(e["config"]),
                tuned_ms=float(e.get("tuned_ms", float("nan"))),
                default_ms=float(e.get("default_ms", float("nan"))))
            accepted += 1
        except (KeyError, ValueError, TypeError):
            continue
    return accepted


def save_cache(path: str) -> None:
    """Persist the in-memory entries (plus tune-time provenance meta)."""
    import jax

    from repro.kernels import ops as _ops

    doc = {
        "version": CACHE_VERSION,
        "meta": {
            "platform": jax.default_backend(),
            "device_count": len(jax.devices()),
            "backend_token": str(_ops.backend_token()),
        },
        "entries": {
            key: {"backend": e.backend, "config": e.config.to_json(),
                  "tuned_ms": e.tuned_ms, "default_ms": e.default_ms}
            for key, e in sorted(_ENTRIES.items())
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def _ensure_loaded() -> None:
    if not _LOAD_ATTEMPTED:
        load_cache()


def n_entries() -> int:
    _ensure_loaded()
    return len(_ENTRIES)


def loaded_path() -> str | None:
    _ensure_loaded()
    return _LOADED_PATH


def fallback_backend(interpret: bool) -> str:
    """Concrete backend for an `auto` dispatch with no cache entry: the
    jnp oracle where Pallas would run in interpret mode (CPU), the Pallas
    kernels where they compile natively."""
    return "ref" if interpret else "pallas"


def resolve(op: str, n: int, l: int, b: int,
            interpret: bool) -> tuple[str, KernelConfig]:
    """(backend, config) for one `auto` dispatch.  Cache hit -> the
    measured winner; miss -> defaults.  Never returns "auto"."""
    _ensure_loaded()
    import jax

    e = _ENTRIES.get(shape_key(op, n, l, b, jax.default_backend()))
    if e is not None:
        return e.backend, e.config
    return fallback_backend(interpret), default_config(op)


def provenance() -> dict:
    """Tuner state stamped into `obs.provenance()` / BENCH artifacts."""
    return {"generation": generation(), "cache_path": loaded_path(),
            "entries": n_entries()}


# ---------------------------------------------------------------------------
# candidate enumeration + roofline pruning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Candidate:
    backend: str            # "ref" | "pallas" | "pallas4"
    config: KernelConfig

    @property
    def is_default(self) -> bool:
        return self.config.ntt4_split is None


def candidates(op: str, n: int, l: int, b: int,
               interpret: bool) -> list[Candidate]:
    """The full swept space for one point:

      * every op: the jnp-oracle `ref` (one candidate — block_b is
        meaningless there) and the `pallas` kernel at each
        BLOCK_CANDIDATES tile <= B;
      * NTT ops additionally: `pallas4` at every
        `params.ntt4_split_candidates(N)` x block.

    The default config (DEFAULT_BLOCK, sqrt split) on the
    platform fallback backend is always present, so a sweep can only ever
    match or beat it.
    """
    from repro.core.ckks import params as ckks_params

    blocks = [blk for blk in BLOCK_CANDIDATES if blk <= max(b, 1)]
    if not blocks:
        blocks = [1]
    out = [Candidate("ref", default_config(op))]
    for blk in blocks:
        out.append(Candidate("pallas", KernelConfig(block_b=blk)))
    if op in NTT_OPS:
        for n1, n2 in ckks_params.ntt4_split_candidates(n):
            for blk in blocks:
                out.append(Candidate("pallas4", KernelConfig(
                    block_b=blk, ntt4_split=(n1, n2))))
    fb = fallback_backend(interpret)
    dflt = Candidate(fb, default_config(op))
    if dflt not in out:
        out.insert(0, dflt)
    return out


def _model_time_s(op: str, n: int, l: int, b: int, cand: Candidate,
                  interpret: bool) -> float:
    """Roofline estimate for one candidate: HBM traffic / bandwidth plus
    per-grid-step launch overhead (DESIGN.md §12.3).

    Memory term: each kernel reads/writes its u32[B, L, N] operands once
    (the fused kernels' whole point), so traffic is a config-independent
    ~3 x B x L x N x 4 bytes; NTT stage count scales the in-VMEM work:
    log2(N) butterfly stages for both NTT kernels, plus the correction
    multiply of the 4-step.  Launch term: grid steps x LAUNCH_OVERHEAD_S —
    what small block_b actually costs.  The model only needs to be
    *ordinally* right: anything >= PRUNE_RATIO x the best estimate is
    skipped unmeasured.  In interpret mode no chip is modelled: the
    memory term is left out and only launch counts rank the candidates.
    """
    import math

    mem_s = 0.0
    if not interpret:
        import jax

        from repro.kernels.peaks import peaks
        hbm = peaks(jax.devices()[0].device_kind).hbm_bytes_per_s
        mem_s = 3 * b * l * n * 4 / hbm
        if op in NTT_OPS:
            extra = 1.0 if cand.backend == "pallas4" else 0.0
            mem_s *= 1.0 + (math.log2(n) + extra) / 8.0
    if cand.backend == "ref":
        # whole-tensor jnp graph: no grid, one fused dispatch
        return mem_s + LAUNCH_OVERHEAD_S
    grid_steps = l * -(-b // cand.config.block_b)
    return mem_s + grid_steps * LAUNCH_OVERHEAD_S


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------


def _timeit(fn, *args, reps: int = 3) -> float:
    """Mean wall seconds after one warmup, blocked on every output leaf
    (the same discipline as benchmarks/run.py and obs.timed_kernel — async
    dispatch cannot fake a win)."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _make_inputs(op: str, ctx, b: int, seed: int = 0):
    """Deterministic op inputs at the sweep point's shapes."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref

    rng = np.random.RandomState(seed)
    l = ctx.n_limbs

    def rand(shape):
        return jnp.asarray(ref.rand_limbed_np(rng, ctx, shape))

    w_row = jnp.asarray(
        rng.randint(1, np.asarray(ctx.tables.qs).min(),
                    size=(b, l)).astype(np.uint32))
    if op in ("ntt_fwd", "ntt_inv"):
        return (rand((b,)),)
    if op == "mod_lift":
        return (jnp.asarray(rng.randint(
            0, 1 << 32, size=(b, ctx.n_poly)).astype(np.uint32)),)
    if op == "mul_add":
        return (rand((b,)), rand((b,)), rand((b,)))
    if op == "weighted_sum":
        return (rand((4, b)), w_row[:4])
    if op == "weighted_accum":
        return (rand((b,)), rand((b,)), w_row[0])
    if op == "weighted_accum_chunks":
        return (rand((b,)), rand((b,)), w_row)
    raise ValueError(op)


def _candidate_fn(op: str, cand: Candidate, ctx, interpret: bool):
    """A jitted callable running `op` under one candidate's exact launch
    geometry, bypassing the registry (the sweep must not mutate global
    backend state)."""
    import jax

    from repro.kernels import ops as _ops

    tables = ctx.tables.take(ctx.n_limbs)
    if cand.backend == "pallas4" and op in NTT_OPS \
            and cand.config.ntt4_split is not None:
        from repro.core.ckks import params as ckks_params
        tables = ckks_params.retable_ntt4(tables, *cand.config.ntt4_split)

    def fn(*args):
        return _ops.run_config(op, cand.backend, cand.config, tables,
                               *args)

    return jax.jit(fn)


@dataclasses.dataclass
class SweepResult:
    op: str
    n: int
    l: int
    b: int
    platform: str
    winner: Candidate
    tuned_ms: float
    default_ms: float
    n_candidates: int
    n_pruned: int

    @property
    def speedup(self) -> float:
        return self.default_ms / self.tuned_ms if self.tuned_ms else 1.0

    def to_row(self) -> dict:
        return {"op": self.op, "n": self.n, "l": self.l, "b": self.b,
                "platform": self.platform,
                "backend": self.winner.backend,
                "config": self.winner.config.to_json(),
                "default_ms": self.default_ms, "tuned_ms": self.tuned_ms,
                "speedup": self.speedup,
                "candidates": self.n_candidates, "pruned": self.n_pruned}


def sweep_op(op: str, ctx, b: int, reps: int = 3,
             use_roofline: bool = True) -> SweepResult:
    """Measure every (unpruned) candidate for one point and record the
    winner in the in-memory cache.

    Winner selection includes the default config, so `tuned_ms <=
    default_ms` by construction — a tuned cache can only match or beat
    the hardcoded defaults it replaces.
    """
    import jax

    from repro import obs
    from repro.kernels import ops as _ops

    interpret = _ops._interpret()
    platform = jax.default_backend()
    n, l = ctx.n_poly, ctx.n_limbs
    args = _make_inputs(op, ctx, b)
    cands = candidates(op, n, l, b, interpret)
    est = {c: _model_time_s(op, n, l, b, c, interpret) for c in cands}
    floor = min(est.values())
    default = Candidate(fallback_backend(interpret), default_config(op))
    measured: dict[Candidate, float] = {}
    pruned = 0
    for cand in cands:
        if use_roofline and cand != default \
                and est[cand] > PRUNE_RATIO * floor:
            pruned += 1
            continue
        fn = _candidate_fn(op, cand, ctx, interpret)
        dt = _timeit(fn, *args, reps=reps)
        measured[cand] = dt
        obs.histogram("tune_candidate_seconds", op=op,
                      backend=cand.backend).observe(dt)
    default_s = measured[default]
    winner = min(measured, key=measured.get)
    tuned_s = measured[winner]
    put(op, n, l, b, platform, winner.backend, winner.config,
        tuned_ms=tuned_s * 1e3, default_ms=default_s * 1e3)
    obs.counter("tune_sweeps_total", op=op).inc()
    return SweepResult(op=op, n=n, l=l, b=b, platform=platform,
                       winner=winner, tuned_ms=tuned_s * 1e3,
                       default_ms=default_s * 1e3,
                       n_candidates=len(cands), n_pruned=pruned)
