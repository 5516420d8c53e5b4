"""Nestable trace spans: profiler annotations, and Chrome-trace-event JSONL.

`span("round", round=3)` is a context manager with two outputs:

  * always, a `jax.profiler.TraceAnnotation("repro.round")` — an inactive
    TraceMe unless a profiler session records, in which case the span lands
    in the profiler trace on the profiler's clock beside the device events.
    While a session records, the span also adds its seconds to the registry
    histogram `obs_span_seconds{span=<name>}`, the same split read in
    process without parsing the trace;
  * with REPRO_OBS=1, one Chrome trace "complete" event (`ph: "X"`) with
    microsecond `ts`/`dur`, written on exit.

Spans nest by wall-time containment on the emitting thread — exactly the
model Perfetto / chrome://tracing render — so the per-round span tree in
`fl/orchestrator.py` (round > client > local_train/encrypt, round >
aggregate > wire.ingest > wire.flush, ...; taxonomy table in DESIGN.md
§11) needs no explicit parent ids.

File format: one JSON event per line.  The first line is ``[`` and every
event line ends with ``,`` — the Chrome trace-event array format with the
optional closing bracket omitted, which both Perfetto and chrome://tracing
load directly, while staying trivially parseable line-by-line
(tools/round_report.py).  Events are appended as they close, so a crash
mid-run loses at most the open spans.

Gating: `enabled()` is False unless REPRO_OBS=1 (or `configure()` flips
it).  Disabled, a span writes no JSONL event and blocks on nothing; its
whole cost is one inactive TraceMe (overhead policy, DESIGN.md §11.3).
jax is imported on the first span, so jax-free readers of this package
(tools/round_report.py) never load it.  The default sink is
$REPRO_OBS_TRACE (default ./obs_trace.jsonl), opened lazily on the first
event.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time

from repro.obs import metrics as _metrics

#: schema version stamped into trace metadata and BENCH provenance
OBS_VERSION = 1

_ENV_ENABLED = os.environ.get("REPRO_OBS", "") not in ("", "0")
_ENV_TRACE_PATH = os.environ.get("REPRO_OBS_TRACE", "obs_trace.jsonl")

_enabled = _ENV_ENABLED
_tracer: "Tracer | None" = None
_lock = threading.Lock()
# jax.profiler.TraceAnnotation, imported on the first span
_Annotation = None


def enabled() -> bool:
    """True when span/trace recording is on (REPRO_OBS=1 or configure())."""
    return _enabled


def configure(enabled: bool | None = None, trace_path: str | None = "KEEP",
              reset: bool = False) -> None:
    """Programmatic override of the env-var gate (tests, notebooks).

    Args:
        enabled: flip span/kernel-hook recording on or off (None = keep).
        trace_path: file sink for a fresh tracer; None = in-memory only,
            "KEEP" (default) = leave the current sink setting alone.
        reset: drop the current tracer (and its buffered events) so the
            next event starts a fresh trace.
    """
    global _enabled, _tracer
    with _lock:
        if reset and _tracer is not None:
            _tracer.close()
            _tracer = None
        if enabled is not None:
            _enabled = bool(enabled)
        if trace_path != "KEEP":
            if _tracer is not None:
                _tracer.close()
            _tracer = Tracer(path=trace_path)


def get_tracer() -> "Tracer":
    """The process tracer (created on first use; sink from REPRO_OBS_TRACE
    when REPRO_OBS=1, else in-memory)."""
    global _tracer
    with _lock:
        if _tracer is None:
            _tracer = Tracer(path=_ENV_TRACE_PATH if _ENV_ENABLED else None)
        return _tracer


class Tracer:
    """Event buffer + optional JSONL file sink, one per process."""

    def __init__(self, path: str | None = None):
        self.path = path
        self.events: list[dict] = []
        self._fh = None
        self._flock = threading.Lock()
        self._t0_ns = time.perf_counter_ns()
        self._local = threading.local()

    # -- time / stack --------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since tracer start (perf_counter clock — durations,
        never wall-clock timestamps)."""
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def depth(self) -> int:
        """Current span nesting depth on this thread."""
        return len(self._stack())

    def current_span(self) -> "Span | None":
        st = self._stack()
        return st[-1] if st else None

    # -- emission ------------------------------------------------------------

    def emit(self, ev: dict) -> None:
        with self._flock:
            self.events.append(ev)
            if self.path:
                if self._fh is None:
                    self._fh = open(self.path, "w")
                    self._fh.write("[\n")
                    self._fh.write(json.dumps(self._meta_event(),
                                              separators=(",", ":")) + ",\n")
                self._fh.write(json.dumps(ev, separators=(",", ":")) + ",\n")

    def _meta_event(self) -> dict:
        return {"name": "process_name", "ph": "M", "pid": os.getpid(),
                "tid": threading.get_native_id(),
                "args": {"name": "repro", "obs_version": OBS_VERSION,
                         "wall_time": time.time()}}

    def emit_complete(self, name: str, ts_us: float, dur_us: float,
                      cat: str = "phase", args: dict | None = None) -> None:
        """One Chrome 'X' complete event (ts/dur in microseconds)."""
        self.emit({"name": name, "cat": cat, "ph": "X",
                   "ts": round(ts_us, 3), "dur": round(dur_us, 3),
                   "pid": os.getpid(), "tid": threading.get_native_id(),
                   "args": args or {}})

    def emit_instant(self, name: str, cat: str = "event",
                     args: dict | None = None) -> None:
        """One Chrome 'i' instant event at the current time."""
        self.emit({"name": name, "cat": cat, "ph": "i",
                   "ts": round(self.now_us(), 3), "s": "t",
                   "pid": os.getpid(), "tid": threading.get_native_id(),
                   "args": args or {}})

    def flush(self) -> None:
        with self._flock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        with self._flock:
            if self._fh is not None:
                self._fh.flush()
                self._fh.close()
                self._fh = None


def _annotation(name: str, args: dict):
    """The profiler annotation `repro.<name>` carrying the span's entry
    args."""
    global _Annotation
    if _Annotation is None:
        import jax.profiler

        _Annotation = jax.profiler.TraceAnnotation
    return _Annotation("repro." + name, **args)


class Span:
    """One nestable span: a profiler annotation, and with a tracer a
    complete JSONL event recorded on __exit__."""

    __slots__ = ("tracer", "name", "cat", "args", "_ann", "_ts0", "_t0")

    def __init__(self, tracer: Tracer | None, name: str, cat: str,
                 args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = _annotation(name, args)
        self._ts0 = 0.0
        self._t0 = None

    def set(self, **kw) -> None:
        """Attach/overwrite args after the span opened (e.g. byte counts
        known only at the end of the phase); they reach the JSONL event
        only, the annotation having taken its args at entry."""
        self.args.update(kw)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        if _Annotation.is_enabled():      # a profiler session records
            self._t0 = time.perf_counter()
        if self.tracer is not None:
            self.tracer._stack().append(self)
            self._ts0 = self.tracer.now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.tracer is not None:
            dur = self.tracer.now_us() - self._ts0
            st = self.tracer._stack()
            if st and st[-1] is self:
                st.pop()
            if exc_type is not None:
                self.args["error"] = exc_type.__name__
            self.tracer.emit_complete(self.name, self._ts0, dur,
                                      cat=self.cat, args=self.args)
        if self._t0 is not None:
            _metrics.REGISTRY.histogram("obs_span_seconds",
                                        span=self.name).observe(
                time.perf_counter() - self._t0)
        self._ann.__exit__(exc_type, exc, tb)


def span(name: str, cat: str = "phase", **args):
    """Open a nestable span: a `repro.<name>` profiler annotation always,
    a JSONL event too when obs is enabled.

    Usage::

        with obs.span("round", round=rnd) as sp:
            ...
            sp.set(bytes_up=ledger.total(UPLINK, rnd))

    Pass as entry args only values that are cheap and known at entry
    (they go into the annotation); spans opened per chunk take none.
    """
    return Span(get_tracer() if _enabled else None, name, cat, args)


def event(name: str, cat: str = "event", **args) -> None:
    """Record an instant event (no-op unless obs is enabled)."""
    if _enabled:
        get_tracer().emit_instant(name, cat=cat, args=dict(args))


def flush() -> None:
    """Flush the trace sink (atexit does this too; call before reading the
    file in-process)."""
    if _tracer is not None:
        _tracer.flush()


def trace_path() -> str | None:
    """The active trace file path, or None (disabled / in-memory)."""
    if not _enabled:
        return None
    return get_tracer().path


@atexit.register
def _atexit_flush() -> None:  # pragma: no cover - exit path
    if _tracer is not None:
        _tracer.close()
