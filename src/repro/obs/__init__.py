"""repro.obs — unified telemetry: metrics registry, trace spans, kernel
timing hooks, exporters.

The measurement substrate under every performance claim this repo makes
(DESIGN.md §11).  Three layers, one switch:

  * **metrics** (`repro/obs/metrics.py`) — process-wide registry of
    counters / gauges / histograms with labels.  Always on: the legacy
    one-off counters (`StreamIngest.accum_launches`,
    `peak_chunk_buffers`, the `wire/budget.py` byte ledger) now resolve
    here behind compatible properties.
  * **trace spans** (`repro/obs/trace.py`) — nestable `span()` context
    managers wired through the FL round loop, the wire ingest/flush and
    pack paths, and the serving loop.  Each is always a `repro.<name>`
    profiler annotation, so a profiler session (the chip benchmark's
    traced runs) sees it on its own clock; with REPRO_OBS=1 it also
    emits Chrome-trace-event JSONL loadable in Perfetto.
  * **kernel hooks** (`repro/obs/hooks.py`) — per-op wall time +
    `jax.profiler.TraceAnnotation` / `jax.named_scope` in the
    `kernels/ops.py` registry, and `kernel_launch` timing for jitted HE
    graphs, keyed by `ops.backend_token()`.  Gated on REPRO_OBS=1.

Exporters: the trace JSONL sink itself, `prometheus_text()` /
`dump_metrics()`, and `tools/round_report.py` (per-round
phase/bytes/launches table from a trace file).  `provenance()` stamps
BENCH_*.json entries with backend token / device kind / obs version.

Environment (canonical table: README.md):
  REPRO_OBS=1           enable the JSONL span file + kernel hooks
                        (default off; profiler annotations are always on).
  REPRO_OBS_TRACE=path  trace sink (default ./obs_trace.jsonl).
"""
from __future__ import annotations

from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.trace import (OBS_VERSION, Span, Tracer, configure, enabled,
                             event, flush, get_tracer, span, trace_path)
from repro.obs.hooks import (kernel_hooks_enabled, kernel_launch,
                             maybe_block, timed_kernel)

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "OBS_VERSION", "Span", "Tracer",
    "configure", "enabled", "event", "flush", "get_tracer", "span",
    "trace_path",
    "kernel_hooks_enabled", "kernel_launch", "maybe_block", "timed_kernel",
    "counter", "gauge", "histogram", "prometheus_text", "dump_metrics",
    "provenance",
]


def counter(name: str, **labels) -> Counter:
    """Get-or-create a counter in the process registry."""
    return REGISTRY.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    """Get-or-create a gauge in the process registry."""
    return REGISTRY.gauge(name, **labels)


def histogram(name: str, **labels) -> Histogram:
    """Get-or-create a histogram in the process registry."""
    return REGISTRY.histogram(name, **labels)


def prometheus_text() -> str:
    """Prometheus-style text dump of the process registry."""
    return REGISTRY.prometheus_text()


def dump_metrics(path: str) -> None:
    """Write the Prometheus-style registry dump to `path`."""
    with open(path, "w") as f:
        f.write(REGISTRY.prometheus_text())


def provenance() -> dict:
    """Measurement provenance stamped into BENCH_*.json entries: obs
    schema version, backend registry snapshot, and device identity —
    enough to know what a checked-in number was measured on."""
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    out = {
        "obs_version": OBS_VERSION,
        "backend": ops.get_backend(),
        "backend_token": str(ops.backend_token()),
        "platform": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "unknown",
        "device_count": len(devs),
    }
    if "auto" in {ops.get_backend(op) for op in ops.OPS}:
        from repro.kernels import tune

        out["tune"] = tune.provenance()
    return out
