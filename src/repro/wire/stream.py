"""Streaming uplink ingest: fold arriving ciphertext chunks into the
running modular accumulator, never materializing all n_clients updates.

Client side — pack_update_frames() emits, per update:

    UPDATE_BEGIN   (cid, n_samples, round, n_chunks, ct_kind)
    CT_CHUNK * n   (chunk_idx + one-chunk ciphertext/seeded-ciphertext frame)
    PLAIN_SEGMENT  (quantized plaintext partition)
    UPDATE_END

Server side — StreamIngest parses frames incrementally (any byte slicing),
BUFFERS each decoded chunk in a ready queue, and folds the whole queue in
ONE chunk-batched accumulate launch per flush:

    acc[k] = acc[k] + w[k] (*) ct[k]    for every ready row k

via `ops.weighted_accum_chunks` (kernels/he_agg.he_weighted_accum_chunks —
the RNS-limb axis and the ready-row axis are both grid dimensions of a
single `pallas_call`).  `ingest()` flushes once per client update, so the
launch count is O(clients), not O(clients * n_chunks); `accum_launches`
instruments this and tests assert it.  Attaching a `ShardedHe` engine
(core/ckks/sharded.py) shards the flush over the device mesh — ready rows
along ``data``, limbs along ``model`` — with no change in results.

Server-side update buffers stay O(1) in the number of clients: one
accumulator plus at most ONE update's worth of ready chunks
(`peak_chunk_buffers` instruments this; tests assert it).

The modular arithmetic is identical to the batch weighted_sum applied in
arrival order, so the streamed aggregate is bit-for-bit equal to the
in-memory path — flush batching does not change a single bit.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import struct

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.ckks import encoding
from repro.core.ckks import transcipher as _tc
from repro.core.ckks.cipher import Ciphertext
from repro.core.ckks.params import CkksContext
from repro.core.secure_agg import ProtectedUpdate
from repro.kernels import ops
from repro.wire import compress as _c
from repro.wire import format as wf

_BEGIN = struct.Struct("<IIIIB")

CT_FULL = 0
CT_SEEDED = 1
CT_TRANSCIPHER = 2
_CT_KINDS = (CT_FULL, CT_SEEDED, CT_TRANSCIPHER)

# escrow-rollback sentinel: "this (cid, round) had no escrow seed before
# the update under ingest touched it"
_ESCROW_MISSING = object()


@dataclasses.dataclass(frozen=True)
class UpdateMeta:
    cid: int
    n_samples: int
    round: int
    n_chunks: int
    seeded: bool
    transcipher: bool = False


# ---------------------------------------------------------------------------
# client side: update -> frames
# ---------------------------------------------------------------------------


def pack_update_frames(upd: ProtectedUpdate, *, cid: int, n_samples: int,
                       rnd: int = 0,
                       seeded: _c.SeededCiphertext | None = None,
                       plain_codec: str = "f32",
                       version: int | None = None) -> bytes:
    """One client's ProtectedUpdate -> concatenated wire frames.

    Args:
        upd: the update (ct data u32[n_chunks, L, 2, N] + plain f32).
        cid: client id for the UPDATE_BEGIN header.
        n_samples: local sample count (the server's FedAvg weight input).
        rnd: round number for the header.
        seeded: optional compress.seed_compress result; each CT_CHUNK then
            carries (seed, c0-chunk) instead of the full chunk, and its
            `derive` id rides in every per-chunk seeded frame (wire v2).
        plain_codec: "f32" | "f16" | "i8" quantizer for the plain segment.
        version: wire version for every emitted frame (default: the
            REPRO_WIRE_VERSION / wf.VERSION emit default).  version=1
            requires seeded.derive == DERIVE_FOLD_CHUNK.

    Returns:
        bytes: UPDATE_BEGIN + CT_CHUNK * n_chunks + PLAIN_SEGMENT +
        UPDATE_END, each a length-prefixed wire frame (DESIGN.md §6.1,
        §9.2 for the v2 layout diff).
    """
    with obs.span("wire.pack", cid=cid):
        n_chunks = int(upd.ct.data.shape[0])
        kind = CT_SEEDED if seeded is not None else CT_FULL
        out = [wf.frame(wf.T_UPDATE_BEGIN,
                        _BEGIN.pack(cid, n_samples, rnd, n_chunks, kind),
                        version=version)]
        # the update's two device arrays, fetched to the host once
        with obs.span("wire.pack.fetch"):
            ct_host = np.asarray(seeded.c0 if seeded is not None
                                 else upd.ct.data)
            plain_host = np.asarray(upd.plain)
        with obs.span("wire.pack.ct_frames"):
            for b in range(n_chunks):
                if seeded is not None:
                    chunk = _c.SeededCiphertext(
                        c0=ct_host[b:b + 1], seed=seeded.seed,
                        scale=seeded.scale, chunk_offset=b,
                        derive=seeded.derive)
                    inner = wf.serialize_seeded_ciphertext(chunk,
                                                           version=version)
                else:
                    inner = wf.serialize_ciphertext(Ciphertext(
                        data=ct_host[b:b + 1], scale=upd.ct.scale),
                        version=version)
                out.append(wf.frame(wf.T_CT_CHUNK,
                                    struct.pack("<I", b) + inner,
                                    version=version))
        with obs.span("wire.pack.plain"):
            arr, qscale = _c.quantize_plain(plain_host, plain_codec)
            out.append(wf.serialize_plain_segment(arr, plain_codec, qscale,
                                                  version=version))
        out.append(wf.frame(wf.T_UPDATE_END, b"", version=version))
        with obs.span("wire.pack.join"):
            return b"".join(out)


def pack_masked_update_frames(masked: _c.MaskedChunk,
                              seed_ct: _c.SeededCiphertext, plain, *,
                              cid: int, n_samples: int, rnd: int = 0,
                              plain_codec: str = "f32",
                              version: int | None = None) -> bytes:
    """One transcipher client's masked update -> concatenated wire frames.

    The thin-client analogue of pack_update_frames: UPDATE_BEGIN (kind =
    CT_TRANSCIPHER) + the escrow TRANSCIPHER_SEED frame + one MASKED_CHUNK
    per row nested in CT_CHUNK + PLAIN_SEGMENT + UPDATE_END.  Transcipher
    frames are v2+ only — version=1 raises the serializer's WireError
    (DESIGN.md §15).

    Args:
        masked: the full masked update (masked u32[n_chunks, N] plus the
            a_seed/derive/scale/chunk_offset the server unmask needs).
        seed_ct: the escrow seeded-ciphertext wire form of the keystream
            seed (compress.seed_compress of ClientMaterials.seed_ct).
        plain: the plaintext partition (selective encryption remainder).
    """
    n_chunks = masked.n_chunks
    host = np.asarray(masked.masked, dtype=np.uint32)
    out = [wf.frame(wf.T_UPDATE_BEGIN,
                    _BEGIN.pack(cid, n_samples, rnd, n_chunks,
                                CT_TRANSCIPHER),
                    version=version),
           wf.serialize_transcipher_seed(seed_ct, version=version)]
    for b in range(n_chunks):
        chunk = _c.MaskedChunk(masked=host[b:b + 1], a_seed=masked.a_seed,
                               scale=masked.scale,
                               chunk_offset=masked.chunk_offset + b,
                               derive=masked.derive)
        inner = wf.serialize_masked_chunk(chunk, version=version)
        out.append(wf.frame(wf.T_CT_CHUNK, struct.pack("<I", b) + inner,
                            version=version))
    arr, qscale = _c.quantize_plain(np.asarray(plain), plain_codec)
    out.append(wf.serialize_plain_segment(arr, plain_codec, qscale,
                                          version=version))
    out.append(wf.frame(wf.T_UPDATE_END, b"", version=version))
    return b"".join(out)


def peek_update_meta(blob: bytes) -> UpdateMeta:
    """Read only the UPDATE_BEGIN header (e.g. to compute FedAvg weights
    before a second ingest pass)."""
    ftype, _, payload, _ = wf.parse_frame(blob, 0)
    if ftype != wf.T_UPDATE_BEGIN:
        raise wf.WireError(f"expected UPDATE_BEGIN, got {ftype:#x}")
    try:
        cid, n_samples, rnd, n_chunks, kind = _BEGIN.unpack_from(payload, 0)
    except struct.error as e:
        raise wf.WireError(f"short UPDATE_BEGIN payload: {e}") from e
    return UpdateMeta(cid=cid, n_samples=n_samples, round=rnd,
                      n_chunks=n_chunks, seeded=kind == CT_SEEDED,
                      transcipher=kind == CT_TRANSCIPHER)


# ---------------------------------------------------------------------------
# server side: streaming modular accumulator
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _parsing():
    """Convert what a corrupt payload raises from host-side parse code
    into WireError — wrap parse code only, never device work."""
    try:
        yield
    except wf.WireError:
        raise
    except wf.PARSE_ERRORS as e:
        raise wf.WireError(f"malformed update stream: {e!r}") from e


@functools.partial(jax.jit, static_argnames=("ctx", "token"))
def _accum_chunks_graph(ctx: CkksContext, token, accs, cts, w_mont):
    """One chunk-batched fold: acc[k] + w[k] (*) ct[k] for every ready row,
    all limbs and rows in a single launch."""
    return ops.weighted_accum_chunks(accs, cts, w_mont, ctx)


class StreamIngest:
    """Accumulates arriving client updates chunk-by-chunk.

    Decoded chunks are buffered in a ready queue and folded by `flush()` —
    one chunk-batched accumulate launch per flush, not one per chunk.
    `ingest()`/`ingest_update()` flush automatically at the end of each
    update, so at most one update's worth of chunks is ever resident
    (O(1) in the client count; `peak_chunk_buffers` proves it) and the
    launch count is one per client (`accum_launches` proves it).

    Usage:
        ingest = StreamIngest(ctx)
        for blob, w in arriving:   # any interleaving of byte slices works
            ingest.ingest(blob, weight=w)
        agg = ingest.finalize()    # ProtectedUpdate, scale = in_scale*delta

    Attributes:
        accum_launches: accumulate launches issued so far (== flushes that
            had ready chunks; the one-launch-per-flush invariant).
        peak_chunk_buffers: max decoded-but-unfolded chunks ever resident.
        clients_ingested / bytes_ingested: ingest counters.

    All four are views over `repro.obs` registry instruments labeled with
    this instance's ingest id (``wire_ingest_*``), so process-wide
    telemetry and the legacy per-instance attributes read the same value
    by construction (tests/test_obs.py asserts bit-equality).
    """

    _ids = itertools.count()

    def __init__(self, ctx: CkksContext, sharded=None,
                 transcipher_materials: dict | None = None):
        """Args:
            ctx: CkksContext of the arriving ciphertexts.
            sharded: optional core.ckks.sharded.ShardedHe; when given,
                flushes run as sharded graphs over its mesh (ready rows ->
                data axis, limbs -> model axis), bit-identical results.
            transcipher_materials: optional {(cid, round):
                transcipher.ServerMaterials} registry; masked transcipher
                updates from unprovisioned (cid, round) pairs are rejected
                with an actionable WireError (DESIGN.md §15).
        """
        self.ctx = ctx
        self.sharded = sharded
        self._acc_ct = None            # dict chunk_idx -> u32[2, L, N]
        self._acc_plain = None         # f32[n_plain]
        self._in_scale = None
        self._pending = []             # ready queue: (chunk_idx, data, w)
        self._transcipher = dict(transcipher_materials or {})
        # escrow keystream-seed ciphertexts received so far, keyed like the
        # materials registry — the audit trail a key authority can decrypt
        self.escrow_seeds: dict = {}
        # registry-backed instrumentation, one label set per ingest
        # instance (obs.REGISTRY.total("wire_ingest_...") aggregates
        # across instances for process-level telemetry)
        self.ingest_id = str(next(self._ids))
        lab = {"ingest": self.ingest_id}
        self._m_launches = obs.counter("wire_ingest_accum_launches", **lab)
        self._m_clients = obs.counter("wire_ingest_clients", **lab)
        self._m_bytes = obs.counter("wire_ingest_bytes", **lab)
        # O(1)-memory instrumentation: decoded ciphertext chunk buffers
        # resident beyond the accumulator.  Incremented where a chunk is
        # decoded, decremented once it has been folded — a regression that
        # buffers several updates before folding shows up as peak >
        # n_chunks of one update.
        self._m_resident = obs.gauge("wire_ingest_resident_chunks", **lab)
        self._m_peak = obs.gauge("wire_ingest_peak_chunk_buffers", **lab)
        # updates rejected (and atomically rolled back) by ingest(): the
        # aggregation service's fault accounting reads this series
        self._m_rejected = obs.counter("wire_ingest_rejected_updates", **lab)

    # -- legacy counter views (registry-backed) ------------------------------

    @property
    def accum_launches(self) -> int:
        return int(self._m_launches.value)

    @property
    def clients_ingested(self) -> int:
        return int(self._m_clients.value)

    @property
    def bytes_ingested(self) -> int:
        return int(self._m_bytes.value)

    @property
    def peak_chunk_buffers(self) -> int:
        return int(self._m_peak.value)

    @property
    def rejected_updates(self) -> int:
        return int(self._m_rejected.value)

    def add_transcipher_materials(self, cid: int, rnd: int,
                                  materials) -> None:
        """Register one (cid, round)'s transcipher.ServerMaterials before
        its masked update arrives (serve/service.py provisioning path)."""
        self._transcipher[(int(cid), int(rnd))] = materials

    # -- internals ----------------------------------------------------------

    def _w_mont(self, weight: float):
        return jnp.asarray(encoding.encode_scalar_residues(float(weight),
                                                           self.ctx))

    def _note_decoded(self, n: int) -> None:
        self._m_resident.add(n)
        self._m_peak.set_max(self._m_resident.value)

    def _buffer_chunk(self, chunk_idx: int, data, scale: float,
                      w_mont) -> None:
        """Queue one decoded chunk (data u32[1, L, 2, N]) for the next
        flush; validates the scale, dtype, and shape against the running
        aggregation — a wire-mutated chunk must fail HERE, inside ingest's
        rollback scope, not later in a flush the rollback cannot reach."""
        if self._in_scale is None:
            self._in_scale = float(scale)
        elif abs(self._in_scale - scale) > 1e-6 * self._in_scale:
            raise wf.WireError("mixed ciphertext scales in one aggregation")
        if data.dtype != np.uint32:
            raise wf.WireError(
                f"ciphertext chunk dtype {data.dtype} is not uint32")
        if self._acc_ct is None:
            self._n_limbs, self._n = data.shape[-3], data.shape[-1]
            self._acc_ct = {}
        if tuple(data.shape) != (1, self._n_limbs, 2, self._n):
            raise wf.WireError(
                f"ciphertext chunk shape {tuple(data.shape)} does not match "
                f"this aggregation's (1, {self._n_limbs}, 2, {self._n})")
        self._note_decoded(+1)
        # limbs to axis -2 (ops layout): [1, L, 2, N] -> [2, L, N]
        x = jnp.moveaxis(jnp.asarray(data), -3, -2)[0]
        self._pending.append((int(chunk_idx), x, w_mont))

    def flush(self) -> None:
        """Fold every ready chunk into the accumulator — ONE chunk-batched
        accumulate launch per pass (a second pass only happens if the same
        chunk index was buffered twice, to preserve arrival order)."""
        with obs.span("wire.flush"):
            while self._pending:
                batch, rest, seen = [], [], set()
                for item in self._pending:
                    if item[0] in seen:
                        rest.append(item)
                    else:
                        seen.add(item[0])
                        batch.append(item)
                self._pending = rest
                idxs = [i for i, _, _ in batch]
                cts = jnp.stack([x for _, x, _ in batch])    # [K, 2, L, N]
                ws = jnp.stack([w for _, _, w in batch])     # [K, L]
                zero = jnp.zeros((2, self._n_limbs, self._n),
                                 dtype=jnp.uint32)
                accs = jnp.stack([self._acc_ct.get(i, zero) for i in idxs])
                token = ops.backend_token()
                with obs.kernel_launch(
                        "weighted_accum_chunks", token, rows=len(batch),
                        sharded=self.sharded is not None) as kl:
                    if self.sharded is not None:
                        out = kl.done(self.sharded.weighted_accum_chunks(
                            accs, cts, ws))
                    else:
                        out = kl.done(_accum_chunks_graph(self.ctx, token,
                                                          accs, cts, ws))
                self._m_launches.inc()
                for j, i in enumerate(idxs):
                    self._acc_ct[i] = out[j]
                self._note_decoded(-len(batch))

    def _unmask_chunk(self, meta: UpdateMeta, mc: _c.MaskedChunk):
        """Transcipher one arriving masked chunk into its seeded-equivalent
        ciphertext (core/ckks/transcipher.server_unmask).  Runs inside the
        ingest rollback scope: unprovisioned or mismatched materials reject
        the whole update atomically."""
        sm = self._transcipher.get((meta.cid, meta.round))
        if sm is None:
            raise wf.WireError(
                f"no transcipher materials provisioned for client "
                f"{meta.cid} round {meta.round}; register ServerMaterials "
                f"(transcipher.provision) before ingest (DESIGN.md §15)")
        if int(mc.a_seed) != int(sm.a_seed) \
                or int(mc.derive) != int(sm.derive):
            raise wf.WireError(
                f"masked chunk parameters (a_seed={mc.a_seed}, "
                f"derive={mc.derive}) do not match the provisioned "
                f"materials (a_seed={sm.a_seed}, derive={sm.derive}) for "
                f"client {meta.cid} round {meta.round}")
        masked = np.asarray(mc.masked)
        if masked.ndim != 2 or masked.shape[1] != self.ctx.n_poly:
            raise wf.WireError(
                f"masked chunk rows {list(masked.shape)} are not "
                f"[rows, {self.ctx.n_poly}]")
        try:
            _tc.check_unmask_rows(sm, int(mc.chunk_offset), masked.shape[0])
        except ValueError as e:
            raise wf.WireError(f"transcipher unmask failed: {e}") from e
        return _tc.server_unmask(self.ctx, sm, masked, int(mc.chunk_offset))

    def _fold_plain_decoded(self, plain: np.ndarray, weight: float) -> None:
        if self._acc_plain is None:
            self._acc_plain = np.zeros(plain.shape, dtype=np.float32)
        elif plain.shape != self._acc_plain.shape:
            raise wf.WireError(
                f"plain segment shape {plain.shape} does not match this "
                f"aggregation's {self._acc_plain.shape}")
        self._acc_plain += np.float32(weight) * plain

    def _fold_plain(self, arr, codec: str, qscale: float,
                    weight: float) -> None:
        self._fold_plain_decoded(_c.dequantize_plain(arr, codec, qscale),
                                 weight)

    def _parse_begin(self, payload) -> UpdateMeta:
        with _parsing():
            cid, n_samples, rnd, n_chunks, kind = _BEGIN.unpack_from(payload,
                                                                     0)
        if kind not in _CT_KINDS:
            raise wf.WireError(f"unknown ct_kind {kind} in UPDATE_BEGIN; "
                               f"this build implements {_CT_KINDS}")
        return UpdateMeta(cid, n_samples, rnd, n_chunks, kind == CT_SEEDED,
                          kind == CT_TRANSCIPHER)

    def _parse_chunk(self, payload, meta: UpdateMeta | None,
                     chunks_seen: set):
        """One CT_CHUNK frame -> (chunk index, its nested payload), the
        index checked against the header and the chunks seen so far."""
        if meta is None:
            raise wf.WireError("CT_CHUNK before UPDATE_BEGIN")
        with _parsing():
            (chunk_idx,) = struct.unpack_from("<I", payload, 0)
        if chunk_idx >= meta.n_chunks:
            raise wf.WireError(f"chunk index {chunk_idx} >= declared "
                               f"n_chunks {meta.n_chunks}")
        if chunk_idx in chunks_seen:
            raise wf.WireError(f"duplicate chunk {chunk_idx}")
        chunks_seen.add(chunk_idx)
        inner, _ = wf.deserialize(payload, self.ctx, off=4)
        # the nested payload kind must MATCH the declared ct_kind:
        # dispatching on isinstance alone would let a masked chunk slip
        # into a seeded/full update (or vice versa), misclassifying
        # UpdateMeta and the ledger — a wire-consistency violation,
        # rejected atomically
        got = ("masked" if isinstance(inner, _c.MaskedChunk)
               else "seeded" if isinstance(inner, _c.SeededCiphertext)
               else "full")
        want = ("masked" if meta.transcipher
                else "seeded" if meta.seeded else "full")
        if got != want:
            raise wf.WireError(
                f"CT_CHUNK {chunk_idx} carries a {got} payload but the "
                f"update's declared ct_kind expects {want}")
        return chunk_idx, inner

    def _parse_escrow(self, payload, meta: UpdateMeta | None,
                      escrow_prev: dict) -> None:
        """Record a TRANSCIPHER_SEED frame's escrow ciphertext, keeping the
        prior value of its (cid, round) entry in `escrow_prev` for the
        rollback."""
        if meta is None:
            raise wf.WireError("TRANSCIPHER_SEED before UPDATE_BEGIN")
        if not meta.transcipher:
            raise wf.WireError(
                "TRANSCIPHER_SEED frame in a non-transcipher update "
                "(declared ct_kind is not CT_TRANSCIPHER)")
        sct, _ = wf.deserialize(payload, self.ctx, off=0)
        if not isinstance(sct, _c.SeededCiphertext):
            raise wf.WireError("TRANSCIPHER_SEED must nest a seeded-"
                               f"ciphertext frame, got {type(sct).__name__}")
        key = (meta.cid, meta.round)
        if key not in escrow_prev:
            escrow_prev[key] = self.escrow_seeds.get(key, _ESCROW_MISSING)
        self.escrow_seeds[key] = sct

    # -- public API ---------------------------------------------------------

    def ingest(self, blob: bytes, weight: float) -> UpdateMeta:
        """Parse one client's frames, buffer its chunks, and flush them in
        one accumulate launch.

        Validates the stream against its own UPDATE_BEGIN header: the set
        of received chunk indices must be exactly {0..n_chunks-1} — a
        dropped or duplicated CT_CHUNK frame is an error, never a silent
        partial contribution to the aggregate.

        Args:
            blob: one client's serialized frame stream.
            weight: FedAvg weight for this client.

        Returns:
            The update's UpdateMeta header.
        """
        with obs.span("wire.ingest", nbytes=len(blob)) as sp:
            meta = self._ingest_spanned(blob, weight, sp)
        return meta

    def _ingest_spanned(self, blob: bytes, weight: float, sp) -> UpdateMeta:
        meta = None
        w_mont = self._w_mont(weight)
        saw_end = False
        chunks_seen: set[int] = set()
        plain_segments = []            # folded only after validation
        n_buffered = 0
        escrow_prev: dict = {}         # escrow keys this update touched
                                       # -> prior value (or _ESCROW_MISSING)
        prev_in_scale = self._in_scale
        acc_was_uninit = self._acc_ct is None
        try:
            off = 0
            while off < len(blob):
                # the frame cut and everything read from it on the host
                with obs.span("wire.ingest.parse"):
                    with _parsing():
                        ftype, _, payload, off = wf.parse_frame(blob, off)
                    if ftype == wf.T_UPDATE_BEGIN:
                        meta = self._parse_begin(payload)
                    elif ftype == wf.T_CT_CHUNK:
                        chunk_idx, inner = self._parse_chunk(
                            payload, meta, chunks_seen)
                    elif ftype == wf.T_TRANSCIPHER_SEED:
                        self._parse_escrow(payload, meta, escrow_prev)
                    elif ftype == wf.T_UPDATE_END:
                        saw_end = True
                    elif ftype != wf.T_PLAIN_SEGMENT:
                        raise wf.WireError(f"unexpected frame type "
                                           f"{ftype:#x} in update stream")
                if ftype == wf.T_CT_CHUNK:
                    if isinstance(inner, _c.MaskedChunk):
                        with obs.span("wire.ingest.unmask"):
                            inner = self._unmask_chunk(meta, inner)
                            self._buffer_chunk(chunk_idx, inner.data,
                                               inner.scale, w_mont)
                    else:
                        with obs.span("wire.ingest.expand"):
                            if isinstance(inner, _c.SeededCiphertext):
                                wf.check_seeded(inner, self.ctx)
                                inner = inner.expand(self.ctx)
                            self._buffer_chunk(chunk_idx, inner.data,
                                               inner.scale, w_mont)
                    n_buffered += 1
                elif ftype == wf.T_PLAIN_SEGMENT:
                    # decode AND shape-validate inside the rollback scope —
                    # a wire-mutated dim must reject the whole update here;
                    # the fold after validation then cannot fail, so the
                    # success path needs no accumulator snapshot
                    with obs.span("wire.ingest.plain_decode"):
                        with _parsing():
                            plain = _c.dequantize_plain(
                                *wf._parse_plain_segment(payload))
                        ref_shape = (self._acc_plain.shape
                                     if self._acc_plain is not None
                                     else plain_segments[0].shape
                                     if plain_segments else None)
                        if ref_shape is not None \
                                and plain.shape != ref_shape:
                            raise wf.WireError(
                                f"plain segment shape {plain.shape} does "
                                f"not match this aggregation's {ref_shape}")
                    plain_segments.append(plain)
            if meta is None or not saw_end:
                raise wf.WireError("truncated update stream")
            if len(chunks_seen) != meta.n_chunks:
                raise wf.WireError(
                    f"update declared {meta.n_chunks} chunks, "
                    f"received {len(chunks_seen)}")
        except Exception as e:
            # NOTHING of a failed update may reach the accumulator — drop
            # its queued chunks and roll back any state its chunks
            # initialized, whatever the failure.  Only a WireError is a
            # rejected update; anything else (a JAX runtime or compile
            # error in expand/unmask/fold) propagates as itself.
            if n_buffered:
                del self._pending[len(self._pending) - n_buffered:]
                self._note_decoded(-n_buffered)
            # restore every escrow entry this update touched to its PRIOR
            # value — a rejected re-submission must not leave its seed
            # ciphertext shadowing the accepted one in the audit trail
            for k, prev in escrow_prev.items():
                if prev is _ESCROW_MISSING:
                    self.escrow_seeds.pop(k, None)
                else:
                    self.escrow_seeds[k] = prev
            self._in_scale = prev_in_scale
            if acc_was_uninit:
                # the rejected chunks must not pin the limb/poly dims either
                self._acc_ct = None
            if isinstance(e, wf.WireError):
                self._m_rejected.inc()
            raise
        # validated above: these folds cannot fail, so no rollback is needed
        # past this point (and no per-ingest accumulator snapshot either)
        with obs.span("wire.ingest.plain_fold"):
            for plain in plain_segments:
                self._fold_plain_decoded(plain, weight)
        self.flush()
        self._m_clients.inc()
        self._m_bytes.inc(len(blob))
        sp.set(cid=meta.cid, round=meta.round, n_chunks=meta.n_chunks)
        return meta

    def ingest_update(self, upd: ProtectedUpdate, weight: float) -> None:
        """In-memory streaming (no serialization): the caller already holds
        the whole decoded update; its chunks are buffered and folded in one
        flush — still O(1) in the client count."""
        with obs.span("wire.ingest", in_memory=True):
            w_mont = self._w_mont(weight)
            data = np.asarray(upd.ct.data)
            for b in range(data.shape[0]):
                self._buffer_chunk(b, data[b:b + 1], upd.ct.scale, w_mont)
            self.flush()
            self._fold_plain(np.asarray(upd.plain), "f32", 1.0, weight)
            self._m_clients.inc()

    # -- checkpointing (repro.serve crash-safe resume) -----------------------

    def export_state(self) -> tuple[dict, dict]:
        """-> (arrays, meta): the full accumulator state as a
        checkpointable pytree of numpy arrays plus a json-safe meta dict.

        The split matches `ckpt.store.save_checkpoint(tree, extra)`:
        arrays ride the npz payload, scalars the manifest.  Restoring via
        `restore_state` and continuing is bit-exact — the modular
        accumulator is exact integers and `acc_plain` is the literal f32
        partial sum, so the resumed fold reproduces the uninterrupted
        run's bits (tests/test_serve.py asserts it at every crash point).

        Raises RuntimeError with unflushed chunks pending: flush() (or
        ingest(), which flushes) before checkpointing.
        """
        if self._pending:
            raise RuntimeError("cannot export StreamIngest state with "
                               "unflushed chunks pending; call flush()")
        idxs = sorted(self._acc_ct) if self._acc_ct else []
        arrays = {
            "chunk_idx": np.asarray(idxs, dtype=np.int32),
            "acc_ct": (np.stack([np.asarray(self._acc_ct[i]) for i in idxs])
                       if idxs else np.zeros((0, 2, 0, 0), dtype=np.uint32)),
            "acc_plain": (np.asarray(self._acc_plain)
                          if self._acc_plain is not None
                          else np.zeros((0,), dtype=np.float32)),
        }
        meta = {
            "in_scale": self._in_scale,
            "has_plain": self._acc_plain is not None,
            "clients": self.clients_ingested,
            "bytes": self.bytes_ingested,
            "launches": self.accum_launches,
            "rejected": self.rejected_updates,
        }
        return arrays, meta

    def restore_state(self, arrays: dict, meta: dict) -> None:
        """Load a checkpointed accumulator (the export_state inverse) into
        this EMPTY ingest; counters resume at their checkpointed values so
        launch/byte accounting survives a restart."""
        if self._acc_ct is not None or self._pending \
                or self.clients_ingested:
            raise RuntimeError("restore_state needs a fresh StreamIngest")
        idxs = np.asarray(arrays["chunk_idx"]).tolist()
        acc = np.asarray(arrays["acc_ct"])
        if idxs:
            self._n_limbs = int(acc.shape[-2])
            self._n = int(acc.shape[-1])
            self._acc_ct = {int(i): jnp.asarray(acc[j])
                            for j, i in enumerate(idxs)}
        if meta.get("has_plain"):
            self._acc_plain = np.asarray(arrays["acc_plain"],
                                         dtype=np.float32).copy()
        if meta.get("in_scale") is not None:
            self._in_scale = float(meta["in_scale"])
        self._m_clients.inc(int(meta.get("clients", 0)))
        self._m_bytes.inc(int(meta.get("bytes", 0)))
        self._m_launches.inc(int(meta.get("launches", 0)))
        self._m_rejected.inc(int(meta.get("rejected", 0)))

    def finalize(self) -> ProtectedUpdate:
        """-> aggregated ProtectedUpdate (ct scale = in_scale * delta).
        Raises WireError if nothing arrived or chunk indices have holes."""
        with obs.span("wire.finalize", n_updates=self.clients_ingested,
                      launches=self.accum_launches):
            self.flush()
            if self.clients_ingested == 0 or self._acc_ct is None:
                raise wf.WireError("no updates ingested")
            n_chunks = max(self._acc_ct) + 1
            if sorted(self._acc_ct) != list(range(n_chunks)):
                raise wf.WireError("missing ciphertext chunks at finalize")
            data = jnp.stack([jnp.moveaxis(self._acc_ct[b], -3, -2)
                              for b in range(n_chunks)], axis=0)
            ct = Ciphertext(data=data, scale=self._in_scale * self.ctx.delta)
            plain = jnp.asarray(self._acc_plain
                                if self._acc_plain is not None
                                else np.zeros((0,), np.float32))
            return ProtectedUpdate(ct=ct, plain=plain)
