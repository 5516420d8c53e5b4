"""repro.wire: serialization round-trips, seed-expanded uplink compression,
quantized plain partition, streaming O(1) server ingest, bandwidth ledger,
SelectiveHEAggregator.overhead_report coverage, and decoder fuzzing (every
mutated/truncated input raises WireError — deterministic sweeps always run;
hypothesis widens the search when installed)."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # property tests skip cleanly
    from _hyp import given, settings, st

from repro.core import packing
from repro.core.ckks import cipher, encoding
from repro.core.ckks import params as ckks_params
from repro.core.secure_agg import (AggregatorConfig, ProtectedUpdate,
                                   SelectiveHEAggregator)
from repro import wire
from repro.wire import budget as wb
from repro.wire import compress as wc
from repro.wire import format as wf
from repro.wire import stream as ws

CTX = ckks_params.make_test_context(n_poly=256, n_limbs=2, delta_bits=20)
SK, PK = cipher.keygen(CTX, jax.random.PRNGKey(0))


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


def fresh_ct(b=2, seed=0):
    v = np.random.RandomState(seed).randn(b, CTX.slots).astype(np.float32)
    return v, cipher.encrypt_values(CTX, PK, jnp.asarray(v),
                                    jax.random.PRNGKey(seed + 1))


# ---------------------------------------------------------------------------
# format: lossless round-trips
# ---------------------------------------------------------------------------


def test_ciphertext_roundtrip_bitexact():
    _, ct = fresh_ct()
    out, off = wf.deserialize(wf.serialize_ciphertext(ct))
    assert off == len(wf.serialize_ciphertext(ct))
    np.testing.assert_array_equal(np.asarray(ct.data, dtype=np.uint32),
                                  out.data)
    assert out.scale == ct.scale
    # decrypts identically to the in-memory path
    a = cipher.decrypt_values(CTX, SK, ct)
    b = cipher.decrypt_values(CTX, SK, wire.deserialize(
        wire.serialize_ciphertext(ct))[0])
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_keyset_roundtrip_bitexact():
    for keys in (PK, SK):
        out, _ = wf.deserialize(wf.serialize_keyset(keys))
        assert sorted(out) == sorted(keys)
        for k in keys:
            np.testing.assert_array_equal(np.asarray(keys[k]), out[k])


def test_partition_roundtrip():
    agg, _ = make_agg()
    out, _ = wf.deserialize(wf.serialize_partition(agg.part))
    assert out.n_total == agg.part.n_total and out.slots == agg.part.slots
    np.testing.assert_array_equal(out.enc_idx, agg.part.enc_idx)
    np.testing.assert_array_equal(out.plain_idx, agg.part.plain_idx)


def test_protected_update_roundtrip_bitexact():
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(5))
    out, _ = wf.deserialize(wf.serialize_update(upd), CTX)
    np.testing.assert_array_equal(np.asarray(upd.ct.data, np.uint32),
                                  out.ct.data)
    np.testing.assert_allclose(np.asarray(upd.plain), np.asarray(out.plain),
                               rtol=0, atol=0)
    # serialized -> deserialized -> decrypt equals the in-memory path
    a = agg.client_recover(upd, SK)
    b = agg.client_recover(out, SK)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bad_magic_and_truncation_rejected():
    blob = bytearray(wf.serialize_ciphertext(fresh_ct()[1]))
    with pytest.raises(wf.NeedMoreData):
        wf.parse_frame(blob[:-1], 0)
    blob[0] = 0
    with pytest.raises(wf.WireError):
        wf.parse_frame(bytes(blob), 0)


def test_frame_reader_incremental():
    _, ct = fresh_ct()
    blob = wf.serialize_ciphertext(ct) + wf.serialize_keyset(PK)
    r = wf.FrameReader()
    got = []
    for i in range(0, len(blob), 97):       # arbitrary slicing
        r.feed(blob[i:i + 97])
        got.extend(r)
    assert [t for t, _, _ in got] == [wf.T_CIPHERTEXT, wf.T_KEYSET]


# ---------------------------------------------------------------------------
# versioning: v1 frames decode forever, unknown versions reject loudly
# ---------------------------------------------------------------------------


def _seeded_ct(b=2, seed=1, a_seed=77):
    v = np.random.RandomState(seed).randn(b, CTX.slots).astype(np.float32)
    coeffs = encoding.encode_jnp(jnp.asarray(v), CTX)
    return cipher.encrypt_coeffs_seeded(CTX, SK, coeffs,
                                        jax.random.PRNGKey(seed), a_seed)


def _seeded_ct_derive(derive, b=2, seed=1, a_seed=77):
    v = np.random.RandomState(seed).randn(b, CTX.slots).astype(np.float32)
    coeffs = encoding.encode_jnp(jnp.asarray(v), CTX)
    return cipher.encrypt_coeffs_seeded(CTX, SK, coeffs,
                                        jax.random.PRNGKey(seed), a_seed,
                                        derive=derive)


def _provisioned(a_seed=19, n_chunks=1, seed=6):
    from repro.core.ckks import transcipher as tc
    return tc.provision(CTX, SK, jax.random.PRNGKey(seed), a_seed, n_chunks)


def _masked_chunk(cm, seed=6):
    from repro.core.ckks import transcipher as tc
    v = np.random.RandomState(seed).randn(cm.n_chunks,
                                          CTX.slots).astype(np.float32)
    return wc.MaskedChunk(masked=tc.mask_values(CTX, cm, v),
                          a_seed=cm.a_seed, scale=cm.scale,
                          derive=cm.derive)


def test_v1_frames_roundtrip_through_v2_decoder_bitexact():
    """Every artifact emitted in the legacy v1 layout decodes bit-exactly
    on the current (v2-default) decoder."""
    _, ct = fresh_ct()
    out, _ = wf.deserialize(wf.serialize_ciphertext(ct, version=1))
    np.testing.assert_array_equal(np.asarray(ct.data, np.uint32), out.data)
    assert out.scale == ct.scale

    sct = wc.seed_compress(_seeded_ct(), 77)
    blob = wf.serialize_seeded_ciphertext(sct, version=1)
    # v1 seeded payload really has NO derive byte: header + <dQI> + array
    assert len(blob) + 1 == len(wf.serialize_seeded_ciphertext(sct))
    out, _ = wf.deserialize(blob)
    assert out.derive == wc.DERIVE_FOLD_CHUNK      # implied by v1
    np.testing.assert_array_equal(np.asarray(sct.c0, np.uint32), out.c0)
    np.testing.assert_array_equal(np.asarray(out.expand(CTX).data),
                                  np.asarray(_seeded_ct().data))

    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(5))
    out, _ = wf.deserialize(wf.serialize_update(upd, version=1), CTX)
    np.testing.assert_array_equal(np.asarray(upd.ct.data, np.uint32),
                                  out.ct.data)


def test_v1_update_stream_ingests_bit_identical_to_v2():
    agg, m = make_agg()
    upd = agg.client_protect_seeded(m, SK, jax.random.PRNGKey(6), a_seed=21)
    sct = wc.seed_compress(upd.ct, 21)
    blob_v1 = ws.pack_update_frames(upd, cid=0, n_samples=1, seeded=sct,
                                    version=1)
    blob_v2 = ws.pack_update_frames(upd, cid=0, n_samples=1, seeded=sct,
                                    version=2)
    assert blob_v1 != blob_v2          # layouts differ on the wire...
    outs = []
    for blob in (blob_v1, blob_v2):
        ing = ws.StreamIngest(CTX)
        ing.ingest(blob, 1.0)
        outs.append(ing.finalize())
    # ...but the decoded aggregate is bit-identical
    np.testing.assert_array_equal(np.asarray(outs[0].ct.data),
                                  np.asarray(outs[1].ct.data))


def test_unknown_wire_version_rejected_actionably():
    """A v3 frame must raise WireError, and the message must tell the
    operator which knob to flip (README section / REPRO_WIRE_VERSION)."""
    blob = bytearray(wf.serialize_ciphertext(fresh_ct()[1]))
    blob[4] = 3                        # version byte in the envelope
    with pytest.raises(wf.WireError, match="REPRO_WIRE_VERSION"):
        wf.deserialize(bytes(blob))
    with pytest.raises(wf.WireError, match="README"):
        wf.parse_frame(bytes(blob), 0)
    # emission is pinned to the supported set too
    with pytest.raises(wf.WireError, match="cannot emit"):
        wf.frame(wf.T_UPDATE_END, b"", version=3)


def test_v2_seeded_frame_carries_and_validates_derive():
    import dataclasses

    sct = wc.seed_compress(_seeded_ct(), 77)
    out, _ = wf.deserialize(wf.serialize_seeded_ciphertext(sct, version=2))
    assert out.derive == wc.DERIVE_FOLD_CHUNK
    # an unknown derive id on the wire is rejected at parse time
    bad = dataclasses.replace(sct, derive=9)
    blob = wf.serialize_seeded_ciphertext(bad, version=2)
    with pytest.raises(wf.WireError, match="derivation"):
        wf.deserialize(blob)
    # ...and cannot be down-serialized to v1 (which cannot express it)
    with pytest.raises(wf.WireError, match="not expressible"):
        wf.serialize_seeded_ciphertext(bad, version=1)


def test_derive_registry_consistent_across_layers():
    """One registry (core/ckks/cipher.py), re-exported unchanged by the
    wire layers — the negotiation tables can never drift apart."""
    assert cipher.DERIVES == wc.DERIVES == wf.DERIVES == (1, 2)
    assert wc.DERIVE_FOLD_CHUNK == cipher.DERIVE_FOLD_CHUNK == 1
    assert wc.DERIVE_CTR == cipher.DERIVE_CTR == 2


def test_v2_seeded_frame_roundtrips_derive_ctr_bitexact():
    """DERIVE_CTR negotiation end to end at the frame level: the v2 frame
    carries the id, the receiver's expand regenerates the exact ciphertext,
    and the two derive families really produce different bits."""
    ct = _seeded_ct_derive(wc.DERIVE_CTR, b=2, seed=2, a_seed=55)
    sct = wc.seed_compress(ct, 55, derive=wc.DERIVE_CTR)
    out, _ = wf.deserialize(wf.serialize_seeded_ciphertext(sct))
    assert out.derive == wc.DERIVE_CTR
    np.testing.assert_array_equal(np.asarray(out.expand(CTX).data),
                                  np.asarray(ct.data))
    ct_fold = _seeded_ct_derive(wc.DERIVE_FOLD_CHUNK, b=2, seed=2, a_seed=55)
    assert not np.array_equal(np.asarray(ct.data), np.asarray(ct_fold.data))
    # a v1 peer cannot be sent this stream — refuse, don't reinterpret
    with pytest.raises(wf.WireError, match="not expressible"):
        wf.serialize_seeded_ciphertext(sct, version=1)


def test_derive_ctr_seeded_stream_recovers_fedavg():
    """The negotiation matrix end to end: clients protect with
    derive=DERIVE_CTR, the packed v2 stream round-trips through
    StreamIngest, and FedAvg recovers; packing the same update for a v1
    peer refuses."""
    agg, m = make_agg()
    n = 3
    clients = [jax.tree_util.tree_map(lambda x, i=i: x + 0.1 * i, m)
               for i in range(n)]
    ing = ws.StreamIngest(CTX)
    for i, c in enumerate(clients):
        upd = agg.client_protect_seeded(c, SK, jax.random.PRNGKey(70 + i),
                                        a_seed=900 + i,
                                        derive=wc.DERIVE_CTR)
        sct = wc.seed_compress(upd.ct, 900 + i, derive=wc.DERIVE_CTR)
        blob = ws.pack_update_frames(upd, cid=i, n_samples=4, rnd=0,
                                     seeded=sct)
        with pytest.raises(wf.WireError, match="not expressible"):
            ws.pack_update_frames(upd, cid=i, n_samples=4, rnd=0,
                                  seeded=sct, version=1)
        ing.ingest(blob, 1.0 / n)
    rec = agg.client_recover_params(ing.finalize(), SK)
    expect = jax.tree_util.tree_map(lambda *xs: sum(xs) / n, *clients)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(rec), jax.tree_util.tree_leaves(expect)))
    assert err < 1e-2


# ---------------------------------------------------------------------------
# compress: seeded uplink, limb drop, plain quantization
# ---------------------------------------------------------------------------


def test_seeded_encrypt_decrypts_and_expands_bitexact():
    v = np.random.RandomState(0).randn(2, CTX.slots).astype(np.float32)
    coeffs = encoding.encode_jnp(jnp.asarray(v), CTX)
    ct = cipher.encrypt_coeffs_seeded(CTX, SK, coeffs, jax.random.PRNGKey(1),
                                      a_seed=77)
    out = cipher.decrypt_values(CTX, SK, ct)
    assert float(np.abs(np.asarray(out) - v).max()) < 3e-3
    sct = wc.seed_compress(ct, 77)
    np.testing.assert_array_equal(np.asarray(sct.expand(CTX).data),
                                  np.asarray(ct.data))


def test_seeded_uplink_bytes_leq_055x():
    v = np.random.RandomState(0).randn(3, CTX.slots).astype(np.float32)
    coeffs = encoding.encode_jnp(jnp.asarray(v), CTX)
    ct = cipher.encrypt_coeffs_seeded(CTX, SK, coeffs, jax.random.PRNGKey(1),
                                      a_seed=9)
    full = wf.serialize_ciphertext(ct)
    seeded = wf.serialize_seeded_ciphertext(wc.seed_compress(ct, 9))
    assert len(seeded) <= 0.55 * len(full)
    # and round-trips through the generic parser
    out, _ = wf.deserialize(seeded)
    np.testing.assert_array_equal(np.asarray(out.expand(CTX).data),
                                  np.asarray(ct.data))


def test_seeded_mixes_with_pk_ciphertexts():
    v = np.random.RandomState(3).randn(1, CTX.slots).astype(np.float32)
    coeffs = encoding.encode_jnp(jnp.asarray(v), CTX)
    ct_pk = cipher.encrypt_coeffs(CTX, PK, coeffs, jax.random.PRNGKey(4))
    ct_sk = cipher.encrypt_coeffs_seeded(CTX, SK, coeffs,
                                         jax.random.PRNGKey(5), a_seed=11)
    both = cipher.Ciphertext(
        data=jnp.stack([ct_pk.data, ct_sk.data]), scale=ct_pk.scale)
    agg = cipher.weighted_sum(CTX, both, [0.5, 0.5])
    out = cipher.decrypt_values(CTX, SK, agg)
    assert float(np.abs(np.asarray(out) - v).max()) < 3e-3


def test_limb_drop_halves_bytes_coarse_decrypt():
    v, ct = fresh_ct(b=1, seed=7)
    w = cipher.mul_plain_scalar(CTX, ct, 1.0)     # scale delta**2, like agg
    dropped = wc.limb_drop(CTX, w, 1)
    assert dropped.n_limbs == 1
    blob_full = wf.serialize_ciphertext(w)
    blob_drop = wf.serialize_ciphertext(dropped)
    assert len(blob_drop) < 0.55 * len(blob_full)
    out = cipher.decrypt_values_np(CTX, SK, dropped)
    # scale after the drop is delta**2/q ~ 2**11: coarse but faithful
    assert float(np.abs(out - v).max()) < 0.3


@pytest.mark.parametrize("codec,atol", [("f32", 0.0), ("f16", 2e-3),
                                        ("i8", 5e-2)])
def test_plain_quantization_tolerance(codec, atol):
    x = np.random.RandomState(0).randn(500).astype(np.float32)
    arr, qscale = wc.quantize_plain(x, codec)
    out = wc.dequantize_plain(arr, codec, qscale)
    assert float(np.abs(out - x).max()) <= atol + 1e-9
    if codec != "f32":
        assert arr.nbytes < x.nbytes


@pytest.mark.parametrize("x", [
    np.zeros(0, dtype=np.float32),               # empty segment
    np.zeros(16, dtype=np.float32),              # all-zero segment
], ids=["empty", "all-zero"])
def test_i8_degenerate_segments_quantize_to_zeros_scale_one(x):
    """Regression: amax == 0 made scale = 0 and x/scale put NaN on the
    wire.  Degenerate segments must emit zeros with scale 1 instead."""
    arr, qscale = wc.quantize_plain(x, "i8")
    assert qscale == 1.0 and arr.dtype == np.int8 and not arr.any()
    out = wc.dequantize_plain(arr, "i8", qscale)
    assert np.isfinite(out).all() and not out.any()


def test_i8_single_nonzero_and_subnormal_amax_stay_finite():
    x = np.zeros(10, dtype=np.float32)
    x[3] = 0.5
    arr, qscale = wc.quantize_plain(x, "i8")
    out = wc.dequantize_plain(arr, "i8", qscale)
    assert np.isfinite(out).all()
    assert float(np.abs(out - x).max()) <= 0.5 / 127 + 1e-9
    # a subnormal amax must never produce NaN/inf on the wire, whichever
    # branch (guard or normal quantization) it takes
    tiny = np.full(8, 1e-42, dtype=np.float32)
    arr, qscale = wc.quantize_plain(tiny, "i8")
    assert np.isfinite(qscale) and qscale > 0.0
    assert np.isfinite(arr.astype(np.float64)).all()
    out = wc.dequantize_plain(arr, "i8", qscale)
    assert np.isfinite(out).all()
    assert float(np.abs(out - tiny).max()) <= 1e-42


def test_i8_all_zero_plain_survives_update_stream():
    """End to end: an all-zero plain partition under the i8 codec packs,
    ingests, and aggregates to exact zeros (it used to poison the fold
    with NaN)."""
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(8))
    zeroed = ProtectedUpdate(ct=upd.ct, plain=jnp.zeros_like(upd.plain))
    blob = ws.pack_update_frames(zeroed, cid=0, n_samples=1,
                                 plain_codec="i8")
    ing = ws.StreamIngest(CTX)
    ing.ingest(blob, 1.0)
    out = ing.finalize()
    assert np.isfinite(np.asarray(out.plain)).all()
    assert not np.asarray(out.plain).any()


# ---------------------------------------------------------------------------
# stream: chunked ingest, O(1) buffers, bit parity with batch aggregation
# ---------------------------------------------------------------------------


def _clients_updates(agg, m, n=6):
    clients = [jax.tree_util.tree_map(lambda x, i=i: x + 0.05 * i, m)
               for i in range(n)]
    ups = [agg.client_protect(c, PK, jax.random.PRNGKey(40 + i))
           for i, c in enumerate(clients)]
    return clients, ups


def test_streaming_bitexact_vs_batch_and_o1_buffers():
    agg, m = make_agg()
    clients, ups = _clients_updates(agg, m, n=6)
    wts = [1.0 / 6] * 6
    batch = agg.server_aggregate(ups, wts)

    ing = ws.StreamIngest(CTX)
    for u, w in zip(ups, wts):
        ing.ingest_update(u, w)
    out = ing.finalize()
    np.testing.assert_array_equal(np.asarray(batch.ct.data, np.uint32),
                                  np.asarray(out.ct.data, np.uint32))
    assert out.ct.scale == batch.ct.scale
    np.testing.assert_allclose(np.asarray(batch.plain), np.asarray(out.plain),
                               atol=1e-5)
    # server-side update buffers stay O(1) in the client count: at most ONE
    # update's chunks are resident between flushes
    assert ing.peak_chunk_buffers == agg.part.n_chunks
    assert ing.clients_ingested == 6
    # one chunk-batched accumulate launch per flush, one flush per update
    assert ing.accum_launches == 6


def test_serialized_seeded_stream_recovers_fedavg():
    agg, m = make_agg()
    n = 5
    clients = [jax.tree_util.tree_map(lambda x, i=i: x + 0.1 * i, m)
               for i in range(n)]
    blobs = []
    for i, c in enumerate(clients):
        upd = agg.client_protect_seeded(c, SK, jax.random.PRNGKey(60 + i),
                                        a_seed=500 + i)
        sct = wc.seed_compress(upd.ct, 500 + i)
        blobs.append(ws.pack_update_frames(upd, cid=i, n_samples=4, rnd=0,
                                           seeded=sct))
    metas = [ws.peek_update_meta(b) for b in blobs]
    assert all(mt.seeded and mt.n_chunks == agg.part.n_chunks for mt in metas)
    ing = ws.StreamIngest(CTX)
    for b in blobs:
        ing.ingest(b, 1.0 / n)
    rec = agg.client_recover_params(ing.finalize(), SK)
    expect = jax.tree_util.tree_map(lambda *xs: sum(xs) / n, *clients)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(rec), jax.tree_util.tree_leaves(expect)))
    assert err < 1e-2
    # ready-chunk buffering: one update's chunks resident at the peak,
    # folded by ONE accumulate launch per client update (not per chunk)
    assert ing.peak_chunk_buffers == agg.part.n_chunks
    assert ing.accum_launches == n


def test_stream_rejects_truncated_update():
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    blob = ws.pack_update_frames(upd, cid=0, n_samples=1)
    # chop off the END frame
    *frames, _ = list(wf.iter_frames(blob))
    truncated = blob[:len(blob) - wf.HEADER_BYTES]
    ing = ws.StreamIngest(CTX)
    with pytest.raises(wf.WireError):
        ing.ingest(truncated, 1.0)


def test_stream_rejected_update_contributes_nothing():
    """A rejected update must leave NO trace: not its chunks, not its
    plain segment, not the scale it tried to establish."""
    agg, m = make_agg()
    good = ws.pack_update_frames(agg.client_protect(
        m, PK, jax.random.PRNGKey(1)), cid=0, n_samples=1)
    bad_upd = agg.client_protect(m, PK, jax.random.PRNGKey(2))
    bad = ws.pack_update_frames(bad_upd, cid=1, n_samples=1)
    # chop off the END frame -> rejected, but its PLAIN_SEGMENT and chunks
    # were already parsed by then
    truncated = bad[:len(bad) - wf.HEADER_BYTES]

    ing_clean = ws.StreamIngest(CTX)
    ing_clean.ingest(good, 1.0)
    clean = ing_clean.finalize()

    ing = ws.StreamIngest(CTX)
    with pytest.raises(wf.WireError):
        ing.ingest(truncated, 1.0)
    assert ing._in_scale is None and not ing._pending
    # the rejected chunks must not have pinned accumulator dims either
    assert ing._acc_ct is None
    ing.ingest(good, 1.0)
    out = ing.finalize()
    np.testing.assert_array_equal(np.asarray(out.ct.data),
                                  np.asarray(clean.ct.data))
    np.testing.assert_array_equal(np.asarray(out.plain),
                                  np.asarray(clean.plain))


def test_stream_corrupt_chunk_payload_drops_buffered_chunks():
    """Parse failures below the frame envelope (e.g. a short chunk payload)
    must roll the rejected update's buffered chunks back AND surface as
    WireError — never a raw struct/numpy error."""
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    blob = ws.pack_update_frames(upd, cid=0, n_samples=1)
    frames = []
    off = 0
    while off < len(blob):
        _, _, _, end = wf.parse_frame(blob, off)
        frames.append(blob[off:end])
        off = end
    # replace the SECOND chunk with a syntactically-valid frame whose
    # payload is too short to parse
    corrupt = wf.frame(wf.T_CT_CHUNK, b"\x01")
    mangled = b"".join(frames[:2] + [corrupt] + frames[3:])
    ing = ws.StreamIngest(CTX)
    with pytest.raises(wf.WireError):
        ing.ingest(mangled, 1.0)
    assert not ing._pending          # first chunk was rolled back
    assert ing.peak_chunk_buffers <= agg.part.n_chunks


def test_stream_device_error_propagates_not_rejected(monkeypatch):
    """A JAX runtime error while a chunk is expanded on the device is a
    fault of this server, not a malformed update: it propagates as itself,
    is not counted as a wire rejection, and leaves nothing of the update in
    the accumulator.  A corrupt blob still raises WireError."""
    agg, m = make_agg()
    v = agg.client_protect_seeded(m, SK, jax.random.PRNGKey(1), a_seed=5)
    blob = ws.pack_update_frames(v, cid=0, n_samples=1,
                                 seeded=wc.seed_compress(v.ct, 5))
    ing = ws.StreamIngest(CTX)

    def device_fault(*a, **k):
        raise jax.errors.JaxRuntimeError("INTERNAL: simulated device fault")

    with monkeypatch.context() as mp:
        mp.setattr(cipher, "expand_a_rows", device_fault)
        with pytest.raises(jax.errors.JaxRuntimeError):
            ing.ingest(blob, 1.0)
    assert ing.rejected_updates == 0
    assert ing._acc_ct is None and not ing._pending
    with pytest.raises(wf.WireError):
        ing.ingest(blob[:len(blob) - wf.HEADER_BYTES], 1.0)
    assert ing.rejected_updates == 1
    ing.ingest(blob, 1.0)                # the same update, healthy device
    clean = ws.StreamIngest(CTX)
    clean.ingest(blob, 1.0)
    np.testing.assert_array_equal(np.asarray(ing.finalize().ct.data),
                                  np.asarray(clean.finalize().ct.data))


# ---------------------------------------------------------------------------
# decoder fuzzing: any mutation/truncation -> WireError, never a crash,
# hang, or over-read.  The deterministic sweeps below run in every
# environment; the @given variants widen the same properties with
# hypothesis when it is installed (tests/_hyp.py guard otherwise).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _fuzz_corpus() -> tuple:
    """Valid frames of every type, covering BOTH wire versions and BOTH
    seed-derivation paths (v1's implicit derive byte and v2's explicit
    one)."""
    blobs = []
    _, ct = fresh_ct(b=1, seed=3)
    for v in (1, 2):
        blobs.append(wf.serialize_ciphertext(ct, version=v))
    sct = wc.seed_compress(_seeded_ct(b=1, seed=2, a_seed=5), 5)
    for v in (1, 2):
        blobs.append(wf.serialize_seeded_ciphertext(sct, version=v))
    blobs.append(wf.serialize_keyset(PK))
    agg, m = make_agg()
    blobs.append(wf.serialize_partition(agg.part))
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(5))
    for v in (1, 2):
        blobs.append(wf.serialize_update(upd, version=v))
    upd_s = agg.client_protect_seeded(m, SK, jax.random.PRNGKey(6), a_seed=9)
    for v in (1, 2):
        blobs.append(wf.serialize_update(
            upd_s, seeded=wc.seed_compress(upd_s.ct, 9), version=v))
    # the v2-only paths: DERIVE_CTR seeded frames and the transcipher
    # (masked chunk + escrow seed) frames
    sct_ctr = wc.seed_compress(
        _seeded_ct_derive(wc.DERIVE_CTR, b=1, seed=4, a_seed=13), 13,
        derive=wc.DERIVE_CTR)
    blobs.append(wf.serialize_seeded_ciphertext(sct_ctr))
    cm, _ = _provisioned(a_seed=19, n_chunks=1, seed=6)
    blobs.append(wf.serialize_masked_chunk(_masked_chunk(cm, seed=6)))
    blobs.append(wf.serialize_transcipher_seed(
        wc.seed_compress(cm.seed_ct, cm.escrow_a_seed, cm.derive)))
    return tuple(bytes(b) for b in blobs)


def _decode_ok_or_wire_error(blob: bytes) -> None:
    """The fuzz property: decode either succeeds or raises WireError."""
    try:
        wf.deserialize(blob, CTX)
    except wf.WireError:
        pass           # includes NeedMoreData for truncations


def test_fuzz_corpus_is_valid():
    for blob in _fuzz_corpus():
        out, end = wf.deserialize(blob, CTX)
        assert end == len(blob) and out is not None


def test_fuzz_truncation_always_wire_error():
    """EVERY proper prefix of every valid frame must be rejected with
    WireError (NeedMoreData for envelope-level cuts)."""
    for blob in _fuzz_corpus():
        cuts = set(range(0, min(len(blob), 64))) | {
            len(blob) * k // 23 for k in range(23)} | {len(blob) - 1}
        for cut in sorted(cuts):
            if cut >= len(blob):
                continue
            with pytest.raises(wf.WireError):
                wf.deserialize(blob[:cut], CTX)


def test_fuzz_mutation_never_crashes():
    """Single-byte mutations anywhere in any frame: decode either succeeds
    (a data byte changed) or raises WireError — no other exception type,
    no hang, no over-read."""
    rng = np.random.RandomState(0)
    for blob in _fuzz_corpus():
        positions = np.concatenate([
            np.arange(min(len(blob), 48)),           # every header byte
            rng.randint(0, len(blob), size=64)])     # random payload bytes
        for pos in positions:
            b = bytearray(blob)
            b[pos] ^= 1 + rng.randint(0, 255)
            _decode_ok_or_wire_error(bytes(b))


def test_fuzz_garbage_and_resized_buffers():
    rng = np.random.RandomState(1)
    for n in (0, 1, wf.HEADER_BYTES - 1, wf.HEADER_BYTES, 64, 4096):
        _decode_ok_or_wire_error(rng.bytes(n))
    # valid header, absurd declared length
    for blob in _fuzz_corpus()[:2]:
        _decode_ok_or_wire_error(blob + rng.bytes(17))    # trailing junk
        grown = bytearray(blob)
        grown[8:16] = (2 ** 62).to_bytes(8, "little")     # payload_len
        _decode_ok_or_wire_error(bytes(grown))


def test_fuzz_stream_ingest_never_crashes():
    """The streaming server path under the same property: a mutated or
    truncated update blob raises WireError and leaves the ingest clean for
    the next client."""
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    blob = ws.pack_update_frames(upd, cid=0, n_samples=1)
    rng = np.random.RandomState(2)
    ing = ws.StreamIngest(CTX)
    rejected = 0
    for _ in range(60):
        b = bytearray(blob)
        if rng.rand() < 0.5:
            b = b[:rng.randint(0, len(blob))]
        else:
            b[rng.randint(0, len(b))] ^= 1 + rng.randint(0, 255)
        try:
            ing.ingest(bytes(b), 0.5)
        except wf.WireError:
            rejected += 1
    assert rejected > 0
    # after arbitrary rejections the ingest still accepts a clean update
    ing.ingest(blob, 1.0)
    assert ing.finalize() is not None


def test_fuzz_transcipher_stream_ingest_never_crashes():
    """Same property for the masked (transcipher) update stream: mutations
    and truncations reject with WireError, leave no partial state, and the
    ingest still accepts the clean blob afterwards."""
    from repro.core.ckks import transcipher as tc
    cm, sm = _provisioned(a_seed=19, n_chunks=2, seed=6)
    v = np.random.RandomState(6).randn(2, CTX.slots).astype(np.float32)
    mc = wc.MaskedChunk(masked=tc.mask_values(CTX, cm, v), a_seed=cm.a_seed,
                        scale=cm.scale, derive=cm.derive)
    sct = wc.seed_compress(cm.seed_ct, cm.escrow_a_seed, cm.derive)
    blob = ws.pack_masked_update_frames(
        mc, sct, np.zeros(4, np.float32), cid=0, n_samples=1, rnd=0)
    rng = np.random.RandomState(3)
    ing = ws.StreamIngest(CTX, transcipher_materials={(0, 0): sm})
    rejected = 0
    for _ in range(60):
        b = bytearray(blob)
        if rng.rand() < 0.5:
            b = b[:rng.randint(0, len(blob))]
        else:
            b[rng.randint(0, len(b))] ^= 1 + rng.randint(0, 255)
        try:
            ing.ingest(bytes(b), 0.5)
        except wf.WireError:
            rejected += 1
            assert not ing._pending
    assert rejected > 0
    ing.ingest(blob, 1.0)
    assert ing.finalize() is not None


@given(st.data())
@settings(max_examples=80, deadline=None)
def test_hyp_mutation_rejected_or_decoded(data):
    blobs = _fuzz_corpus()
    blob = data.draw(st.sampled_from(blobs))
    pos = data.draw(st.integers(0, len(blob) - 1))
    val = data.draw(st.integers(0, 255))
    b = bytearray(blob)
    b[pos] = val
    _decode_ok_or_wire_error(bytes(b))


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_hyp_truncation_rejected(data):
    blobs = _fuzz_corpus()
    blob = data.draw(st.sampled_from(blobs))
    cut = data.draw(st.integers(0, len(blob) - 1))
    with pytest.raises(wf.WireError):
        wf.deserialize(blob[:cut], CTX)


@given(st.data())
@settings(max_examples=40, deadline=None)
def test_hyp_valid_frames_roundtrip(data):
    """Arbitrary valid ciphertext/seeded frames round-trip bit-exactly on
    both wire versions and both derive paths."""
    b = data.draw(st.integers(1, 3))
    seed = data.draw(st.integers(0, 2 ** 16))
    version = data.draw(st.sampled_from([1, 2]))
    seeded = data.draw(st.booleans())
    if seeded:
        a_seed = data.draw(st.integers(0, 2 ** 31))
        sct = wc.seed_compress(_seeded_ct(b=b, seed=seed, a_seed=a_seed),
                               a_seed)
        out, end = wf.deserialize(
            wf.serialize_seeded_ciphertext(sct, version=version))
        np.testing.assert_array_equal(np.asarray(sct.c0, np.uint32), out.c0)
        assert out.seed == sct.seed and out.derive == wc.DERIVE_FOLD_CHUNK
    else:
        _, ct = fresh_ct(b=b, seed=seed)
        blob = wf.serialize_ciphertext(ct, version=version)
        out, end = wf.deserialize(blob)
        assert end == len(blob)
        np.testing.assert_array_equal(np.asarray(ct.data, np.uint32),
                                      out.data)
        assert out.scale == ct.scale


@given(st.data())
@settings(max_examples=60, deadline=None)
def test_hyp_stream_ingest_mutation(data):
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    blob = ws.pack_update_frames(upd, cid=0, n_samples=1)
    pos = data.draw(st.integers(0, len(blob) - 1))
    val = data.draw(st.integers(0, 255))
    b = bytearray(blob)
    b[pos] = val
    ing = ws.StreamIngest(CTX)
    try:
        ing.ingest(bytes(b), 1.0)
    except wf.WireError:
        assert not ing._pending          # rejected updates leave no trace


def test_stream_mismatched_plain_segment_rejected_atomically():
    """A well-framed update whose plain segment length disagrees with the
    running aggregation must be rejected as WireError INSIDE the rollback
    scope: its buffered ciphertext chunks are dropped and the plain
    accumulator keeps its exact pre-ingest values."""
    agg, m = make_agg()
    good = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    ing = ws.StreamIngest(CTX)
    ing.ingest(ws.pack_update_frames(good, cid=0, n_samples=1), 0.5)
    snap_plain = np.array(ing._acc_plain)
    snap_acc = {i: np.asarray(v) for i, v in ing._acc_ct.items()}
    bad = ProtectedUpdate(ct=good.ct, plain=good.plain[:-5])
    with pytest.raises(wf.WireError, match="plain segment"):
        ing.ingest(ws.pack_update_frames(bad, cid=1, n_samples=1), 0.5)
    assert not ing._pending              # rejected chunks dropped
    np.testing.assert_array_equal(np.asarray(ing._acc_plain), snap_plain)
    # a clean third client still folds, unaffected by the rejection
    ing.ingest(ws.pack_update_frames(good, cid=2, n_samples=1), 0.5)
    for i, v in snap_acc.items():
        assert not np.array_equal(np.asarray(ing._acc_ct[i]), v)


def test_stream_mismatched_chunk_shape_rejected_atomically():
    """Same contract for the ciphertext side: a chunk whose (L, N) dims
    disagree with the pinned aggregation dims raises WireError and leaves
    no queued chunks behind."""
    agg, m = make_agg()
    good = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    ing = ws.StreamIngest(CTX)
    ing.ingest(ws.pack_update_frames(good, cid=0, n_samples=1), 0.5)
    n_chunks = good.ct.data.shape[0]
    bad_ct = cipher.Ciphertext(
        data=jnp.zeros((n_chunks, CTX.n_limbs, 2, CTX.n_poly // 2),
                       jnp.uint32),
        scale=good.ct.scale)
    bad = ProtectedUpdate(ct=bad_ct, plain=good.plain)
    with pytest.raises(wf.WireError, match="chunk shape"):
        ing.ingest(ws.pack_update_frames(bad, cid=1, n_samples=1), 0.5)
    assert not ing._pending
    ing.ingest(ws.pack_update_frames(good, cid=2, n_samples=1), 0.5)
    assert ing.finalize() is not None


def test_stream_rejects_missing_or_duplicate_chunk():
    agg, m = make_agg()
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(1))
    assert agg.part.n_chunks >= 2
    blob = ws.pack_update_frames(upd, cid=0, n_samples=1)
    frames = []
    off = 0
    while off < len(blob):
        _, _, _, end = wf.parse_frame(blob, off)
        frames.append(blob[off:end])
        off = end
    # frames: BEGIN, CT_CHUNK * n, PLAIN, END — drop one chunk frame
    dropped = b"".join(frames[:1] + frames[2:])
    with pytest.raises(wf.WireError, match="chunks"):
        ws.StreamIngest(CTX).ingest(dropped, 1.0)
    # duplicate a chunk frame
    duped = b"".join(frames[:2] + [frames[1]] + frames[2:])
    with pytest.raises(wf.WireError, match="duplicate"):
        ws.StreamIngest(CTX).ingest(duped, 1.0)


# ---------------------------------------------------------------------------
# budget ledger
# ---------------------------------------------------------------------------


def test_ledger_record_blob_classes_and_totals():
    agg, m = make_agg()
    upd = agg.client_protect_seeded(m, SK, jax.random.PRNGKey(2), a_seed=3)
    sct = wc.seed_compress(upd.ct, 3)
    blob = ws.pack_update_frames(upd, cid=7, n_samples=2, rnd=1, seeded=sct,
                                 plain_codec="f16")
    led = wb.BandwidthLedger()
    total = led.record_blob(blob, rnd=1, cid=7, direction=wb.UPLINK)
    assert total == len(blob)
    assert led.total(wb.UPLINK, 1) == len(blob)
    s = led.round_summary(1)
    assert s["uplink_bytes"] == len(blob) and s["downlink_bytes"] == 0
    assert s["by_kind"]["up/seeded_ciphertext"] > 0
    assert s["by_kind"]["up/plain"] > 0
    comp = led.compression_summary(CTX, agg.part, 1)
    assert comp["compression_ratio"] > 1.0
    assert comp["measured_uplink_bytes"] == len(blob)


# ---------------------------------------------------------------------------
# overhead_report (satellite coverage)
# ---------------------------------------------------------------------------


def test_overhead_report_consistency():
    agg, _ = make_agg(p=0.4)
    rep = agg.overhead_report()
    part = agg.part
    assert rep["n_total"] == part.n_total
    assert rep["n_enc"] == part.n_enc
    assert rep["n_ciphertexts"] == part.n_chunks
    assert rep["ratio"] == pytest.approx(part.n_enc / part.n_total)
    assert rep["bytes_total"] == rep["bytes_encrypted"] + rep["bytes_plain"]
    assert rep["bytes_plain"] == 4 * part.n_plain
    assert rep["bytes_all_plain"] == 4 * part.n_total
    assert rep["comm_ratio"] == pytest.approx(
        rep["bytes_total"] / rep["bytes_all_plain"])


def test_overhead_report_monotone_in_p():
    reps = [make_agg(p=p)[0].overhead_report() for p in (0.1, 0.5, 1.0)]
    assert reps[0]["n_enc"] <= reps[1]["n_enc"] <= reps[2]["n_enc"]
    assert reps[0]["bytes_total"] <= reps[1]["bytes_total"]
    # all-encrypted blows up communication; selective shrinks it
    assert reps[2]["comm_ratio"] > reps[0]["comm_ratio"]


def test_overhead_report_vs_measured_wire():
    """The report's byte model matches the measured raw-u32 wire within
    framing overhead for the uncompressed path."""
    agg, m = make_agg(p=0.4)
    upd = agg.client_protect(m, PK, jax.random.PRNGKey(3))
    blob = wf.serialize_update(upd)
    est = CTX.encrypted_bytes(agg.part.n_enc, packed=False) \
        + CTX.plaintext_bytes(agg.part.n_plain)
    assert abs(len(blob) - est) < 256   # headers only
