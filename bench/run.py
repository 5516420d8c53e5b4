"""The benchmark's one command.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chip this process finds: set-up
(inputs and weights from the seed, every program compiled and warmed up),
then units of the cell's traffic back to back until the first unit boundary
at or after --seconds, then the comparison with the plain reference.  The
last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with --trace 1 its
per-layer metrics read from the profiler trace and the harness's spans),
`device`, with --trace 1 `breakdown`, and last `checks`: each compared
number beside its limit.  The same numbers end standard error.

There is no fallback: without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""

    setup_s: float
    window_s: float
    work: dict             # counts of the window's work (ct, updates, ...)
    shapes: dict           # sizes the counts functions need
    spans: dict            # harness span name -> seconds, summed
    trace: object          # trace.Summary, or None without --trace 1
    memory_peak_bytes: int | None
    hbm_in_use_bytes: int | None   # largest sampled by the role in the window
    peaks: object          # peaks.Peaks of the device


def _device(want: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (devices[0] is "
                         f"{devs[0].platform!r}); the benchmark runs on the "
                         "chip only")
    if len(devs) < want:
        raise SystemExit(f"bench: the cell needs {want} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": want}


def _memory_peak(n: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:n]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _profile_options():
    import jax

    opt = jax.profiler.ProfileOptions()
    opt.python_tracer_level = 0     # Python call tracing would slow the host
    opt.host_tracer_level = 2       # keeps the harness's annotations
    return opt


class _Compiles:
    """Programs compiled and read from the persistent cache, and the
    seconds spent compiling, since the last `take()`."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self, phase: str) -> None:
        print(f"bench: {phase}: {self.n} programs compiled "
              f"({self.secs:.1f} s), {self.hits} read from the cache",
              file=sys.stderr, flush=True)
        self.n = self.hits = 0
        self.secs = 0.0


def _window(role, seconds: float, spans, trace_dir: str | None):
    import jax

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    units = 0
    with spans("window"):
        t0 = time.perf_counter()
        while True:
            role.unit()
            units += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    if trace_dir is not None:
        jax.profiler.stop_trace()
    return units, window_s


def run_cell(cell, seed: int, seconds: float, tracing: bool,
             device: dict, t_start: float = START) -> dict:
    """Set-up, window, comparison and metrics of one run of `cell` on
    `device`; returns the result object.  Checks and metrics also go to
    standard error."""
    import jax

    import check
    import peaks as peaks_mod
    import roles
    import spec
    import trace as trace_mod
    from repro.kernels import ops

    pk = peaks_mod.peaks(device["kind"])
    print(f"bench: {cell.name} on {device['count']} x {device['kind']}, "
          f"jax {jax.__version__}, HE backend {ops.get_backend()}, "
          f"seed {seed}", file=sys.stderr, flush=True)
    compiles = _Compiles()
    spans = roles.Spans(tracing=tracing)
    role = roles.ROLES[cell.traffic["role"]](cell, seed, spans)
    role.setup()
    setup_s = time.perf_counter() - t_start
    compiles.take(f"set-up {setup_s:.1f} s")
    print("bench: set-up spans (s): " + ", ".join(
        f"{k} {sum(v):.1f}" for k, v in spans.seconds.items()),
        file=sys.stderr, flush=True)
    spans.seconds.clear()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if tracing else None
    try:
        units, window_s = _window(role, seconds, spans, trace_dir)
        summary = (trace_mod.summarize(trace_mod.xplane_path(trace_dir),
                                       device["count"])
                   if trace_dir else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    compiles.take("window")
    memory_peak = _memory_peak(device["count"])
    window_spans = {k: sum(v) for k, v in spans.seconds.items()}
    t_check = time.perf_counter()
    numbers = role.check()
    compiles.take(f"comparison {time.perf_counter() - t_check:.1f} s")
    correct, checks = check.judge(numbers, cell.limits)

    run = Run(setup_s=setup_s, window_s=window_s, work=dict(role.work),
              shapes=role.shapes_for_counts(),
              spans=window_spans,
              trace=summary, memory_peak_bytes=memory_peak,
              hbm_in_use_bytes=max(role.hbm_in_use, default=0) or None,
              peaks=pk)
    metrics = {}
    for m in (cell.per_layer if tracing else cell.end_to_end):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=memory_peak)
    result = {"correct": correct, "attempted": units,
              "failed": 0 if correct else units, "metrics": metrics,
              "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.top_programs(10),
                               "idle_gaps": summary.gaps[:10]}
    result["checks"] = checks
    print(f"bench: {units} units in {window_s:.3f} s, set-up "
          f"{setup_s:.3f} s, numbers {numbers}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "fl", "client.py")):
        raise SystemExit("bench: the program (src/repro) is not in this "
                         "checkout")
    sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the tuning cache is an untracked file the benchmark does not read
    os.environ.pop("REPRO_HE_TUNE_CACHE", None)
    from repro.launch import compile_cache
    compile_cache.enable()

    import jax
    # every program of the cell in the persistent cache, the small ones too,
    # so that a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    import spec
    cell = spec.cell(args.workload, ROOT)
    device = _device(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
