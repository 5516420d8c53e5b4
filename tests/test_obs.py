"""Telemetry layer (repro.obs): registry semantics, span nesting, the
trace-JSONL round trip through tools/round_report.py, legacy-counter
parity on the streaming ingest, the REPRO_OBS=0 do-no-harm contract
(disabled obs leaves backend tokens, dispatch behaviour, and gold-KAT
outputs untouched), and the wire spans in a profiler trace with obs
off."""
import glob
import json
import os
import struct
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core.ckks import cipher
from repro.core.ckks import params as ckks_params
from repro.core.secure_agg import AggregatorConfig, SelectiveHEAggregator
from repro.kernels import ops, ref
from repro.obs.metrics import MetricsRegistry
from repro.wire import compress as wc
from repro.wire import format as wf
from repro.wire import stream as ws

import gold

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(ROOT, "tools"))
import round_report  # noqa: E402  (tools/ has no package)

CTX = ckks_params.make_test_context(n_poly=256, n_limbs=2, delta_bits=20)
SK, PK = cipher.keygen(CTX, jax.random.PRNGKey(0))


@pytest.fixture
def obs_memory():
    """Enable obs with an in-memory tracer; restore disabled on exit."""
    obs.configure(enabled=True, trace_path=None, reset=True)
    yield obs.get_tracer()
    obs.configure(enabled=False, trace_path=None, reset=True)


def small_model(seed=1):
    r = np.random.RandomState(seed)
    return {"w1": jnp.asarray(r.randn(40, 10), jnp.float32),
            "b1": jnp.asarray(r.randn(50), jnp.float32)}


def make_agg(p=0.4, seed=3):
    m = small_model()
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(m))
    sens = np.abs(np.random.RandomState(seed).randn(n))
    return SelectiveHEAggregator.build(CTX, m, sens,
                                       AggregatorConfig(p_ratio=p)), m


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs", route="a")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    # distinct label sets are distinct series; same labels share one
    assert reg.counter("reqs", route="b") is not c
    assert reg.counter("reqs", route="a") is c
    assert reg.total("reqs") == 5
    g = reg.gauge("resident")
    g.set(3)
    g.add(2)
    g.set_max(4)            # below current -> unchanged
    assert g.value == 5
    g.set_max(9)
    assert g.value == 9
    # one name cannot be two instrument types
    with pytest.raises(TypeError):
        reg.gauge("reqs", route="a")
    assert reg.get("nope") is None


def test_histogram_percentiles_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    with pytest.raises(ValueError):
        h.percentile(50)
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100
    assert h.mean == pytest.approx(50.5)
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 100.0
    # linear interpolation over the sorted samples (numpy's definition)
    assert h.percentile(50) == pytest.approx(
        np.percentile(np.arange(1, 101), 50))
    assert h.percentile(99) == pytest.approx(
        np.percentile(np.arange(1, 101), 99))


def test_prometheus_text_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("ops_total", op="ntt", backend="ref").inc(7)
    reg.histogram("secs", op="ntt").observe(0.5)
    text = reg.prometheus_text()
    assert "# TYPE ops_total counter" in text
    assert 'ops_total{backend="ref",op="ntt"} 7' in text
    assert 'secs{op="ntt",quantile="0.5"}' in text
    assert 'secs_count{op="ntt"} 1' in text
    snap = reg.snapshot()
    assert snap["ops_total"][0]["value"] == 7
    assert snap["secs"][0]["count"] == 1


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering(obs_memory):
    tr = obs_memory
    with obs.span("round", round=0) as r:
        with obs.span("client", cid=1):
            assert tr.depth() == 2
        with obs.span("aggregate"):
            pass
        r.set(bytes_up=7)
    assert tr.depth() == 0
    names = [e["name"] for e in tr.events]
    # spans are emitted as they CLOSE: children before the parent
    assert names == ["client", "aggregate", "round"]
    rd = tr.events[-1]
    assert rd["ph"] == "X" and rd["args"]["bytes_up"] == 7
    # wall-time containment — the tree structure Perfetto reconstructs
    for child in tr.events[:2]:
        assert rd["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= rd["ts"] + rd["dur"] + 1e-3
    # the two children are disjoint and in order
    c0, c1 = tr.events[0], tr.events[1]
    assert c0["ts"] + c0["dur"] <= c1["ts"] + 1e-3


def test_span_records_exception(obs_memory):
    tr = obs_memory
    with pytest.raises(RuntimeError):
        with obs.span("boom"):
            raise RuntimeError("x")
    assert tr.events[-1]["args"]["error"] == "RuntimeError"
    assert tr.depth() == 0


def test_disabled_span_is_shared_noop(tmp_path):
    # disabled, a span is a profiler annotation only: it writes no JSONL
    # event, opens no trace file, and tallies nothing with no profiler on
    path = tmp_path / "never.jsonl"
    obs.configure(enabled=False, trace_path=str(path), reset=True)
    try:
        sp = obs.span("anything", k=1)
        assert sp.tracer is None
        with sp as s:
            s.set(ignored=True)      # must not raise
        obs.event("nothing")
        assert obs.trace_path() is None
        assert not obs.get_tracer().events
        obs.flush()
        assert not path.exists()
        assert obs.REGISTRY.get("obs_span_seconds", span="anything") is None
    finally:
        obs.configure(enabled=False, trace_path=None, reset=True)


# ---------------------------------------------------------------------------
# trace file -> round_report round trip
# ---------------------------------------------------------------------------


def _synthetic_round(tr):
    """Emit a deterministic round tree: 1000us round fully covered by
    client(400) + aggregate(600); one kernel launch inside aggregate that
    nests a second kernel event (the sharded-dispatch double-measure)."""
    tok = "('ntt_fwd','ref')"
    tr.emit_complete("local_train", 10, 380, cat="phase", args={"cid": 0})
    tr.emit_complete("client", 0, 400, cat="phase", args={"cid": 0})
    tr.emit_complete("he.weighted_accum_chunks", 460, 50, cat="kernel",
                     args={"op": "weighted_accum_chunks", "token": tok})
    tr.emit_complete("he.weighted_accum_chunks", 450, 100, cat="kernel",
                     args={"op": "weighted_accum_chunks", "token": tok})
    tr.emit_complete("aggregate", 400, 600, cat="phase", args={})
    tr.emit_complete("round", 0, 1000, cat="phase",
                     args={"round": 3, "bytes_up": 111, "bytes_down": 222,
                           "launches": 1})


def test_round_report_roundtrip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.configure(enabled=True, trace_path=path, reset=True)
    try:
        _synthetic_round(obs.get_tracer())
        obs.get_tracer().close()
    finally:
        obs.configure(enabled=False, trace_path=None, reset=True)
    # the file is line-parseable AND a valid Chrome trace array once the
    # optional ']' is appended
    with open(path) as f:
        raw = f.read()
    json.loads(raw.rstrip().rstrip(",") + "]")

    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "round_report.py"), path,
         "--json", "--min-coverage", "0.9"],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(proc.stdout)
    (rnd,) = rep["rounds"]
    assert rnd["round"] == 3
    assert rnd["wall_ms"] == pytest.approx(1.0)
    assert rnd["client"] == pytest.approx(0.4)
    assert rnd["aggregate"] == pytest.approx(0.6)
    assert rnd["bytes_up"] == 111 and rnd["bytes_down"] == 222
    assert rnd["launches"] == 1
    assert rnd["coverage"] == pytest.approx(1.0)
    # the nested kernel event is the same launch measured twice: only the
    # top-level one is counted
    (k,) = rep["kernels"]
    assert k["op"] == "weighted_accum_chunks" and k["count"] == 1
    assert k["total_ms"] == pytest.approx(0.1)


def test_round_report_rejects_low_coverage(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    obs.configure(enabled=True, trace_path=path, reset=True)
    try:
        tr = obs.get_tracer()
        tr.emit_complete("client", 0, 100, cat="phase", args={})
        tr.emit_complete("round", 0, 1000, cat="phase", args={"round": 0})
        tr.close()
    finally:
        obs.configure(enabled=False, trace_path=None, reset=True)
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "round_report.py"), path,
         "--min-coverage", "0.9"],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 1
    assert "below coverage" in proc.stderr


def test_round_report_empty_trace_fails(tmp_path):
    path = tmp_path / "empty.jsonl"
    path.write_text("[\n")
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "round_report.py"),
         str(path)],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 1


# ---------------------------------------------------------------------------
# legacy counters == registry series (streaming ingest)
# ---------------------------------------------------------------------------


def test_stream_counters_are_registry_backed():
    agg, m = make_agg()
    n = 4
    blobs = []
    for i in range(n):
        c = jax.tree_util.tree_map(lambda x, i=i: x + 0.05 * i, m)
        upd = agg.client_protect_seeded(c, SK, jax.random.PRNGKey(30 + i),
                                        a_seed=700 + i)
        sct = wc.seed_compress(upd.ct, 700 + i)
        blobs.append(ws.pack_update_frames(upd, cid=i, n_samples=2, rnd=0,
                                           seeded=sct))
    ing = ws.StreamIngest(CTX)
    for b in blobs:
        ing.ingest(b, 1.0 / n)
    ing.finalize()
    # legacy invariants still hold through the property layer
    assert ing.clients_ingested == n
    assert ing.accum_launches == n
    assert ing.peak_chunk_buffers == agg.part.n_chunks
    assert ing.bytes_ingested == sum(len(b) for b in blobs)
    # and each property IS the labeled registry series, not a shadow copy
    lab = {"ingest": ing.ingest_id}
    assert obs.REGISTRY.get("wire_ingest_accum_launches",
                            **lab).value == ing.accum_launches
    assert obs.REGISTRY.get("wire_ingest_clients",
                            **lab).value == ing.clients_ingested
    assert obs.REGISTRY.get("wire_ingest_bytes",
                            **lab).value == ing.bytes_ingested
    assert obs.REGISTRY.get("wire_ingest_peak_chunk_buffers",
                            **lab).value == ing.peak_chunk_buffers
    # properties are read-only: the legacy `ing.clients_ingested += 1`
    # write pattern is gone for good
    with pytest.raises(AttributeError):
        ing.clients_ingested = 0


# ---------------------------------------------------------------------------
# REPRO_OBS=0 do-no-harm; hooks record when enabled
# ---------------------------------------------------------------------------


def test_disabled_obs_leaves_dispatch_untouched():
    obs.configure(enabled=False, trace_path=None, reset=True)
    token_before = ops.backend_token()
    obs.configure(enabled=True, trace_path=None, reset=True)
    try:
        # the jit static key is independent of the obs switch: flipping
        # telemetry can never retrace or recompile an HE graph
        assert ops.backend_token() == token_before
    finally:
        obs.configure(enabled=False, trace_path=None, reset=True)
    assert ops.backend_token() == token_before

    # disabled dispatch records nothing and emits nothing
    before = {k for k in obs.REGISTRY.snapshot() if k.startswith("kernel")}
    x = jnp.asarray(ref.rand_limbed_np(np.random.RandomState(0), CTX, (1,)))
    ops.ntt_fwd(x, CTX)
    after = {k for k in obs.REGISTRY.snapshot() if k.startswith("kernel")}
    assert before == after
    assert not obs.get_tracer().events


def test_gold_kats_bitexact_with_obs_disabled():
    obs.configure(enabled=False, trace_path=None, reset=True)
    golden = gold.load_kats()
    got = gold.compute_kats()
    for name in sorted(golden):
        np.testing.assert_array_equal(got[name], golden[name],
                                      err_msg=f"obs-disabled drift: {name}")


def test_enabled_eager_dispatch_records(obs_memory):
    x = jnp.asarray(ref.rand_limbed_np(np.random.RandomState(0), CTX, (1,)))
    y_ref = np.asarray(ops.ntt_fwd(x, CTX))
    evs = [e for e in obs_memory.events if e.get("cat") == "kernel"
           and e["args"].get("op") == "ntt_fwd"]
    assert evs
    ev = evs[-1]
    assert ev["name"] == "he.ntt_fwd" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["args"]["eager"] is True
    assert ev["args"]["backend"] == ops.get_backend("ntt_fwd")
    assert ev["args"]["token"] == str(ops.backend_token())
    # and the instrumented result is the raw result
    obs.configure(enabled=False)
    np.testing.assert_array_equal(y_ref, np.asarray(ops.ntt_fwd(x, CTX)))


def test_kernel_launch_context_manager(obs_memory):
    with obs.kernel_launch("fake_op", ops.backend_token(), rows=3) as kl:
        out = kl.done(jnp.ones((2, 2)))
    assert float(out.sum()) == 4.0
    evs = [e for e in obs_memory.events if e.get("cat") == "kernel"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["name"] == "he.fake_op" and ev["ph"] == "X" and ev["dur"] >= 0
    assert ev["args"]["op"] == "fake_op" and ev["args"]["rows"] == 3
    assert ev["args"]["token"] == str(ops.backend_token())


# ---------------------------------------------------------------------------
# orchestrator round span tree
# ---------------------------------------------------------------------------


def test_orchestrator_round_span_coverage(obs_memory):
    from test_fl import tiny_task
    task = tiny_task()
    task.run()
    roots = round_report.build_tree(list(obs_memory.events))
    rows = round_report.round_rows(roots)
    assert len(rows) == 2                       # one row per round
    for r in rows:
        # the span tree explains where round wall time went
        assert r["coverage"] >= 0.8, r
        assert r["client"] > 0 and r["aggregate"] >= 0
    names = {e["name"] for e in obs_memory.events}
    assert {"round", "client", "local_train", "aggregate",
            "recover"} <= names


# ---------------------------------------------------------------------------
# spans in the profiler trace, with REPRO_OBS off
# ---------------------------------------------------------------------------


def _profiled(fn, tmp_path):
    """fn() under a profiler session -> (its result, {repro.* name:
    [(start_ns, end_ns), ...]} of the host events in the trace)."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.setdefault(ev.name, []).append((ev.start_ns,
                                                          ev.end_ns))
    return out, spans


def _inside(inner, outer) -> bool:
    return all(any(os_ <= s and e <= oe for os_, oe in outer)
               for s, e in inner)


def _tally(name: str):
    h = obs.REGISTRY.get("obs_span_seconds", span=name)
    return (0, 0.0) if h is None else (h.count, h.sum)


def _seeded_update(agg, m, cid=0):
    c = jax.tree_util.tree_map(lambda x: x + 0.05 * (cid + 1), m)
    upd = agg.client_protect_seeded(c, SK, jax.random.PRNGKey(40 + cid),
                                    a_seed=900 + cid)
    return upd, wc.seed_compress(upd.ct, 900 + cid)


def test_ingest_spans_reach_profiler_with_obs_off(tmp_path):
    obs.configure(enabled=False, trace_path=None, reset=True)
    agg, m = make_agg()
    upd, sct = _seeded_update(agg, m)
    blob = ws.pack_update_frames(upd, cid=0, n_samples=3, rnd=0,
                                 seeded=sct)
    n = agg.part.n_chunks
    names = ("wire.ingest.parse", "wire.ingest.expand",
             "wire.ingest.plain_decode", "wire.ingest.plain_fold",
             "wire.flush")
    before = {k: _tally(k) for k in names}
    ing = ws.StreamIngest(CTX)
    _, spans = _profiled(lambda: ing.ingest(blob, 1.0), tmp_path)
    (outer,) = spans["repro.wire.ingest"]
    for name in names:
        assert _inside(spans["repro." + name], [outer]), name
    # one parse per frame (UPDATE_BEGIN, each CT_CHUNK, PLAIN_SEGMENT,
    # UPDATE_END), one expand per chunk, one of each of the rest
    counts = {k: len(spans["repro." + k]) for k in names}
    assert counts == {"wire.ingest.parse": n + 3, "wire.ingest.expand": n,
                      "wire.ingest.plain_decode": 1,
                      "wire.ingest.plain_fold": 1, "wire.flush": 1}
    assert "repro.wire.ingest.unmask" not in spans
    # the in-process tally counts the same spans while the profiler records
    for k in names:
        c, sec = _tally(k)
        assert c - before[k][0] == counts[k]
        assert 0.0 < sec - before[k][1] < (outer[1] - outer[0]) * 1e-9
    # and nothing of the JSONL layer ran
    assert not obs.get_tracer().events
    assert obs.trace_path() is None
    assert ing.clients_ingested == 1 and ing.accum_launches == 1


def _frames_reference(upd, *, cid, n_samples, rnd, seeded, plain_codec):
    """The update's frames laid out from the wire primitives alone: what
    pack_update_frames must emit, byte for byte."""
    n = int(upd.ct.data.shape[0])
    kind = ws.CT_SEEDED if seeded is not None else ws.CT_FULL
    out = [wf.frame(wf.T_UPDATE_BEGIN, struct.pack("<IIIIB", cid, n_samples,
                                                   rnd, n, kind))]
    host = np.asarray(seeded.c0 if seeded is not None else upd.ct.data)
    for b in range(n):
        if seeded is not None:
            inner = wf.serialize_seeded_ciphertext(wc.SeededCiphertext(
                c0=host[b:b + 1], seed=seeded.seed, scale=seeded.scale,
                chunk_offset=b, derive=seeded.derive))
        else:
            inner = wf.serialize_ciphertext(cipher.Ciphertext(
                data=host[b:b + 1], scale=upd.ct.scale))
        out.append(wf.frame(wf.T_CT_CHUNK, struct.pack("<I", b) + inner))
    arr, q = wc.quantize_plain(np.asarray(upd.plain), plain_codec)
    out.append(wf.serialize_plain_segment(arr, plain_codec, q))
    out.append(wf.frame(wf.T_UPDATE_END, b""))
    return b"".join(out)


@pytest.mark.parametrize("seeded,codec", [(True, "f32"), (False, "f16"),
                                          (True, "i8")])
def test_pack_spans_and_bytes_unchanged(tmp_path, seeded, codec):
    obs.configure(enabled=False, trace_path=None, reset=True)
    agg, m = make_agg()
    upd, sct = _seeded_update(agg, m, cid=1)
    sct = sct if seeded else None
    kw = dict(cid=1, n_samples=5, rnd=2, seeded=sct, plain_codec=codec)
    blob, spans = _profiled(lambda: ws.pack_update_frames(upd, **kw),
                            tmp_path)
    assert blob == _frames_reference(upd, **kw)
    outer = spans["repro.wire.pack"]
    assert len(outer) == 1
    for k in ("fetch", "ct_frames", "plain", "join"):
        got = spans["repro.wire.pack." + k]
        assert len(got) == 1 and _inside(got, outer), k
    assert not obs.get_tracer().events


def test_protect_split_span_once_per_protect(tmp_path):
    obs.configure(enabled=False, trace_path=None, reset=True)
    agg, m = make_agg()
    before = _tally("protect.split")

    def protect_twice():
        return [_seeded_update(agg, m, cid=c)[0] for c in range(2)]

    upds, spans = _profiled(protect_twice, tmp_path)
    assert len(spans["repro.protect.split"]) == 2
    c, sec = _tally("protect.split")
    assert c - before[0] == 2 and sec > before[1]
    # the split's plaintext partition reaches the pack on the host
    assert all(type(u.plain) is np.ndarray for u in upds)


def test_span_tallies_only_while_profiling(tmp_path):
    obs.configure(enabled=False, trace_path=None, reset=True)
    name = "test.tally"
    with obs.span(name):
        pass
    assert _tally(name) == (0, 0.0)

    def two():
        for _ in range(2):
            with obs.span(name, cid=7):
                pass

    _, spans = _profiled(two, tmp_path)
    assert len(spans["repro." + name]) == 2
    c, sec = _tally(name)
    assert c == 2 and sec > 0.0
    with obs.span(name):
        pass
    assert _tally(name)[0] == 2
