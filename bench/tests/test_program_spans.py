"""The readers of the program's spans: seconds per update of each span
that ran while a profiler session recorded, and None where the span did
not run, where the run was not traced, or where no update completed."""
from __future__ import annotations

import json
import os
import types

import pytest

import smoke  # noqa: F401  (puts bench/ and src/ on the path)
import program_spans
import spec

_SPANS = {  # metric -> the program's span it reads
    "parse_s_per_update.fold": "wire.ingest.parse",
    "expand_s_per_update.fold": "wire.ingest.expand",
    "plain_decode_s_per_update.fold": "wire.ingest.plain_decode",
    "plain_fold_s_per_update.fold": "wire.ingest.plain_fold",
    "flush_s_per_update.fold": "wire.flush",
    "pack_fetch_s.client": "wire.pack.fetch",
    "pack_ct_frames_s.client": "wire.pack.ct_frames",
    "pack_plain_s.client": "wire.pack.plain",
    "pack_join_s.client": "wire.pack.join",
}


def _run(updates, traced=True):
    return types.SimpleNamespace(work={"updates": updates},
                                 trace=object() if traced else None)


def test_every_program_span_metric_has_its_reader():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]
                 if m["source"] == "program_span"}
    assert names == set(_SPANS)


def test_readers_take_profiled_spans_per_update(tmp_path):
    import jax

    from repro import obs

    name = "wire.pack.join"
    with obs.span(name):          # outside any profiler session: not read
        pass
    reader = spec.reader("pack_join_s.client")
    assert reader(_run(2)) is None
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with obs.span(name):
                pass
    h = obs.REGISTRY.get("obs_span_seconds", span=name)
    assert h.count == 3
    assert reader(_run(2)) == pytest.approx(h.sum / 2)
    assert program_spans.per_update(_run(2), name) == pytest.approx(
        h.sum / 2)
    # untraced, no update, or a span that never ran: nothing to read
    assert reader(_run(2, traced=False)) is None
    assert reader(_run(0)) is None
    assert spec.reader("parse_s_per_update.fold")(_run(2)) is None
