"""Kernel-launch timing hooks for the `kernels/ops.py` backend registry.

Two measurement points, both opt-in via REPRO_OBS=1 (DESIGN.md §11.3):

  * `timed_kernel` — wraps every registry dispatch.  Called EAGERLY
    (tests, benchmarks, ad-hoc use) it times the op wall-to-wall with
    `jax.block_until_ready` under a `jax.profiler.TraceAnnotation`, so
    host traces and device profiles both carry the op name.  Called under
    a jit/shard_map TRACE (the normal production path — cipher graphs,
    the streaming flush, sharded bodies) real timing is impossible, so it
    wraps the op in `jax.named_scope` instead: the compiled HLO carries
    `he.<op>.<backend>` metadata for device profilers, and a
    `kernel_op_traces_total` counter records the retrace.
  * `kernel_launch` — a span for the CALL SITE of a jitted HE graph
    (stream flush, ShardedHe dispatches): wall time of one launch,
    blocked on completion, keyed by op name and the full
    `ops.backend_token()` so flat/pallas/pallas4 runs are distinguishable
    in one trace.

With REPRO_OBS=0 every hook short-circuits to the raw implementation:
no block, no named_scope, no counter — jitted graph keys and dispatch
counts are bit-for-bit those of a build without this module
(tests/test_obs.py asserts it).
"""
from __future__ import annotations

import time

from repro.obs import metrics as _metrics
from repro.obs import trace as _trace


def kernel_hooks_enabled() -> bool:
    """Gate for the registry dispatch hook (same switch as spans)."""
    return _trace.enabled()


def _any_tracer(args) -> bool:
    """True when any leaf is a jax Tracer — i.e. we are inside a jit /
    shard_map trace and wall-timing would measure tracing, not compute."""
    import jax

    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(args))


def timed_kernel(op: str, backend: str, token, impl, *args, config=None):
    """Dispatch one registry op with timing (see module docstring).

    `config` is the resolved tune.KernelConfig of an `auto` dispatch (None
    for explicit backends); it is stamped into the span args so a trace
    shows the launch geometry that actually ran (DESIGN.md §12.5)."""
    import jax

    if _any_tracer(args):
        _metrics.REGISTRY.counter("kernel_op_traces_total", op=op,
                                  backend=backend).inc()
        with jax.named_scope(f"he.{op}.{backend}"):
            return impl(*args)
    tracer = _trace.get_tracer()
    ts0 = tracer.now_us()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"he.{op}"):
        out = jax.block_until_ready(impl(*args))
    dt = time.perf_counter() - t0
    span_args = {"op": op, "backend": backend, "token": str(token),
                 "eager": True}
    if config is not None:
        span_args["config"] = config.to_json()
    tracer.emit_complete(f"he.{op}", ts0, dt * 1e6, cat="kernel",
                         args=span_args)
    return out


class _KernelLaunch:
    """Kernel event around one jitted-graph launch (blocks on exit)."""

    __slots__ = ("op", "token", "args", "_ts0", "_t0", "_out")

    def __init__(self, op: str, token, args: dict):
        self.op = op
        self.token = token
        self.args = args
        self._out = None

    def done(self, out):
        """Hand the launch its outputs so __exit__ can block on them."""
        self._out = out
        return out

    def __enter__(self) -> "_KernelLaunch":
        self._ts0 = _trace.get_tracer().now_us()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        import jax

        if self._out is not None and exc_type is None:
            jax.block_until_ready(self._out)
        dt = time.perf_counter() - self._t0
        _trace.get_tracer().emit_complete(
            f"he.{self.op}", self._ts0, dt * 1e6, cat="kernel",
            args={"op": self.op, "token": str(self.token), **self.args})


class _NullLaunch:
    __slots__ = ()

    def done(self, out):
        return out

    def __enter__(self) -> "_NullLaunch":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NULL_LAUNCH = _NullLaunch()


def kernel_launch(op: str, token, **args):
    """Context manager timing one jitted HE-graph launch.

    Usage::

        with obs.kernel_launch("weighted_accum_chunks", token, rows=k) as kl:
            out = kl.done(jitted_graph(...))

    `kl.done(out)` registers the outputs; exit blocks on them and records
    the wall time as a cat="kernel" trace event keyed by the backend token.
    No-op when obs is disabled.
    """
    if not _trace.enabled():
        return _NULL_LAUNCH
    return _KernelLaunch(op, token, dict(args))


def maybe_block(x):
    """block_until_ready(x) when obs is enabled and x is concrete — makes
    span durations mean 'work finished', not 'dispatch returned'."""
    if not _trace.enabled():
        return x
    import jax

    if _any_tracer(x):
        return x
    return jax.block_until_ready(x)
