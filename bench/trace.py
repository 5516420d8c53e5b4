"""Reduces the profiler trace of a traced window to device busy time, the
idle share, device time per program by stable name, and the longest idle
gaps, each named by what the harness was doing on the host then.

The traced window is the host annotation `bench.window`.  Busy time is
the union, inside the window, of the events of each device plane's
`XLA Ops` line (one event per operation run on the chip), averaged over
the chips used.  Device time by stable name is read from the `XLA Modules`
line: one event per run of a compiled program, named by the jitted
function (`jit_<name>(<fingerprint>)`; the fingerprint is dropped).  The
operations' own names on a TPU are HLO instruction texts with no source
metadata, so a program is the finest stable name the trace gives.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_MIN_GAP_S = 1e-3           # idle stretches shorter than this are no gap


def xplane_path(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                  # mean over the chips used
    programs: dict                 # program name -> device seconds (mean)
    runs: dict                     # program name -> runs (all chips)
    gaps: list                     # [[host span, seconds], ...] longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, pattern: str) -> tuple[float, int]:
        """(device seconds, runs) of every program whose name matches the
        regular expression `pattern`."""
        rx = re.compile(pattern)
        hits = [k for k in self.programs if rx.search(k)]
        return (sum(self.programs[k] for k in hits),
                sum(self.runs[k] for k in hits))

    def top_programs(self, n: int) -> list:
        return [[k, v] for k, v in
                sorted(self.programs.items(), key=lambda kv: -kv[1])[:n]]


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(path: str, n_devices: int) -> Summary:
    from jax.profiler import ProfileData

    return summarize_data(ProfileData.from_file(path), n_devices)


def summarize_data(pd, n_devices: int) -> Summary:
    host_spans = []
    devices = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host_spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW} annotation in the trace, "
                           f"found {len(windows)}")
    w0, w1 = windows[0]
    devices = sorted(devices, key=lambda p: p.name)[:n_devices]
    if not devices:
        raise RuntimeError("the trace holds no TPU device plane")
    programs, runs, busy, first_idle = {}, {}, 0.0, None
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in (_OPS_LINE, _MODULES_LINE):
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                if line.name == _OPS_LINE:
                    intervals.append((s, e))
                    continue
                key = _FINGERPRINT.sub("", ev.name)
                programs[key] = programs.get(key, 0.0) \
                    + (e - s) * 1e-9 / len(devices)
                runs[key] = runs.get(key, 0) + 1
        merged = _union(intervals)
        busy += sum(e - s for s, e in merged) * 1e-9 / len(devices)
        if first_idle is None:
            first_idle = merged
    # idle gaps of the first chip, each named by the innermost harness span
    # that covers its middle
    gaps, prev = [], w0
    for s, e in (first_idle or []) + [[w1, w1]]:
        if (s - prev) * 1e-9 >= _MIN_GAP_S:
            mid = (s + prev) / 2
            inside = [(hs, he, n) for hs, he, n in host_spans
                      if hs <= mid <= he and n != WINDOW]
            name = (min(inside, key=lambda t: t[1] - t[0])[2] if inside
                    else "host: outside the harness's spans")
            gaps.append([name, (s - prev) * 1e-9])
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy, programs=programs,
                   runs=runs, gaps=gaps)
