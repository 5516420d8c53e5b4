"""The reduction from a profiler trace to busy time, idle share, device
time per operation and named idle gaps."""
from __future__ import annotations

import os

import pytest

import smoke  # noqa: F401  (puts bench/ on the path)
import trace

HERE = os.path.dirname(os.path.abspath(__file__))

# one chip, a window of 10 ms on the host clock (1 ms .. 11 ms); ops at
# 2..4 ms and 3..5 ms overlap (busy 2..5) in one run of the program
# _accum_chunks_graph (2..5 ms), one at 8..9 ms in jit_other, and one of
# jit_other outside the window; host spans bench.ingest over 1..6 ms and
# bench.finalize over 6..10 ms
_TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 8000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 3000000000 }
    events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 1000000000 }
    events { metadata_id: 4 offset_ps: 20000000000 duration_ps: 1000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u32[8]" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.2 = u32[8]" } }
  event_metadata { key: 3
                   value { id: 3 name: "jit__accum_chunks_graph(1234)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(99)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 10000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 4000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.ingest" } }
  event_metadata { key: 3 value { id: 3 name: "bench.finalize" } }
}
"""


def test_union_idle_ops_and_gaps():
    from jax.profiler import ProfileData

    s = trace.summarize_data(ProfileData.from_text_proto(_TEXT), 1)
    assert s.window_s == pytest.approx(10e-3)
    assert s.busy_s == pytest.approx(4e-3)          # 2..5 and 8..9 ms
    assert s.idle_share == pytest.approx(0.6)
    assert s.program_seconds(r"_accum_chunks_graph") == (
        pytest.approx(3e-3), 1)
    assert s.program_seconds(r"^jit_other$") == (pytest.approx(1e-3), 1)
    assert s.top_programs(1) == [["jit__accum_chunks_graph",
                                  pytest.approx(3e-3)]]
    # gaps 1..2 (ingest), 5..8 (ingest to 6, finalize after: middle 6.5),
    # 9..11 (finalize to 10, middle 10: finalize)
    assert [g[0] for g in s.gaps] == ["bench.finalize", "bench.finalize",
                                      "bench.ingest"]
    assert [g[1] for g in s.gaps] == [pytest.approx(x)
                                      for x in (3e-3, 2e-3, 1e-3)]


def test_window_annotation_is_required():
    from jax.profiler import ProfileData

    text = _TEXT.replace('name: "bench.window"', 'name: "bench.other"')
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.summarize_data(ProfileData.from_text_proto(text), 1)


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite: three runs of one jitted program,
    each under a bench.unit annotation, inside the bench.window."""
    s = trace.summarize(os.path.join(HERE, "data", "v5e.xplane.pb"), 1)
    assert s.window_s == pytest.approx(33.605578e-3)
    # three runs of ~11.2 us of one fused op each, ~11 ms apart
    assert s.runs == {"jit__lambda": 3}
    assert s.programs["jit__lambda"] == pytest.approx(33.6e-6, rel=0.01)
    assert s.busy_s == pytest.approx(33.59e-6, rel=0.01)
    assert 0.99 < s.idle_share < 1.0
    assert [g[0] for g in s.gaps[:2]] == ["host: outside the harness's spans"] * 2
