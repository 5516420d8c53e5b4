"""repro.wire binary format: versioned, length-prefixed frames.

Every FL artifact travels as one or more frames:

    [4s magic "RPWR"][u8 version][u8 type][u16 flags][u64 payload_len][payload]

Length-prefixing makes the stream self-delimiting: a receiver can split a
byte stream into frames without understanding the payloads, and frames nest
(a PROTECTED_UPDATE payload contains a ciphertext frame and a plain-segment
frame).  Arrays inside payloads are encoded as

    [u8 dtype_code][u8 ndim][u32 dims...][raw little-endian bytes]

All integers are little-endian.  See DESIGN.md §6 for the full layout and
the compression flags; DESIGN.md §9.2 is the normative v2 appendix.

Versioning (DESIGN.md §9.2)
---------------------------
The header's version byte is per FRAME, so frames of different versions mix
freely in one stream (a v2 update may nest a v1 ciphertext frame and vice
versa).  This build speaks versions 1 and 2:

  * v1 — the PR-1 layout.  Decoded forever; never removed.
  * v2 — identical to v1 for every frame type EXCEPT SEEDED_CIPHERTEXT,
    which gains a trailing `u8 derive` field in the fixed header naming the
    per-chunk seed-derivation algorithm (DERIVE_* below).  v1 seeded frames
    decode with the implicit v1 algorithm, DERIVE_FOLD_CHUNK.

Emission defaults to `VERSION` (= 2); set REPRO_WIRE_VERSION=1 to pin a
sender to the legacy layout during rollout (canonical knob list: README.md
"Environment variables & flags").  Unknown versions raise WireError — the
protocol never guesses at layouts it postdates.
"""
from __future__ import annotations

import dataclasses
import os
import struct
from typing import Iterator

import numpy as np

from repro.core.ckks.cipher import Ciphertext
from repro.core.packing import MaskPartition
from repro.wire.compress import (DERIVE_CTR, DERIVE_FOLD_CHUNK,
                                 DERIVES, MaskedChunk, SeededCiphertext)

MAGIC = b"RPWR"
VERSION = 2                      # default emit version
SUPPORTED_VERSIONS = (1, 2)      # what parse_frame accepts

_HEADER = struct.Struct("<4sBBHQ")
HEADER_BYTES = _HEADER.size

# frame types (payload layouts: DESIGN.md §8.5 for v1, §9.2 for the v2 diff)
T_CIPHERTEXT = 0x01          # f64 scale + u32[B, L, 2, N] array (all versions)
T_SEEDED_CIPHERTEXT = 0x02   # v1: f64 scale, u64 seed, u32 chunk_offset +
                             #     u32[B, L, N] c0
                             # v2: + u8 derive between chunk_offset and c0
T_PROTECTED_UPDATE = 0x03    # nested (SEEDED_)CIPHERTEXT + PLAIN_SEGMENT
T_KEYSET = 0x04              # named-array bundle: pk / eval keys / sk shares
T_MASK_PARTITION = 0x05      # u64 n_total, u32 slots + enc/plain idx arrays
# streaming uplink protocol (repro.wire.stream); layouts version-invariant
T_UPDATE_BEGIN = 0x06        # u32 cid, n_samples, round, n_chunks; u8 ct_kind
T_CT_CHUNK = 0x07            # u32 chunk_idx + one nested one-chunk ct frame
T_PLAIN_SEGMENT = 0x08       # u8 codec, f64 qscale + quantized array
T_UPDATE_END = 0x09          # empty payload
# transcipher (hybrid-HE) uplink frames (DESIGN.md §15); v2+ only — these
# frame types postdate v1 and have no legacy layout to imply
T_MASKED_CHUNK = 0x0A        # f64 scale, u64 a_seed, u32 chunk_offset,
                             #     u8 derive + u32[B, N] masked coefficients
T_TRANSCIPHER_SEED = 0x0B    # one nested SEEDED_CIPHERTEXT frame: the
                             #     escrow encryption of the keystream seed

# seed-derivation algorithm ids carried by v2 SEEDED_CIPHERTEXT frames
# (DESIGN.md §9.2).  The registry lives in core/ckks/cipher.py and is
# re-exported through compress.py (import layering: this module imports
# SeededCiphertext from there); DERIVES is the sorted tuple of known ids —
# currently (DERIVE_FOLD_CHUNK, DERIVE_CTR) = (1, 2).

_DTYPE_CODES = {
    np.dtype(np.uint32): 0, np.dtype(np.float32): 1, np.dtype(np.float16): 2,
    np.dtype(np.int8): 3, np.dtype(np.float64): 4, np.dtype(np.int32): 5,
    np.dtype(np.uint8): 6, np.dtype(np.int64): 7,
}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}

_PLAIN_CODEC_IDS = {"f32": 0, "f16": 2, "i8": 3}
_PLAIN_CODEC_NAMES = {v: k for k, v in _PLAIN_CODEC_IDS.items()}


class WireError(ValueError):
    pass


# What a corrupt payload can raise from the host-side parsers (struct,
# numpy, int conversions).  These — and only these, and only around parse
# code — become WireError.  Device work (seed expansion, unmask, folds) is
# never inside such a conversion: a JAX runtime or compile error is a fault
# of this server, not a malformed update, and must propagate as itself.
PARSE_ERRORS = (struct.error, ValueError, KeyError, IndexError, TypeError,
                OverflowError)


class NeedMoreData(WireError):
    """Raised when a buffer ends mid-frame (incremental readers catch it)."""


def _emit_version_from_env() -> int:
    """Sender-side pin for staged rollouts: REPRO_WIRE_VERSION=1 makes
    every frame() call emit the legacy layout (README.md "Environment
    variables & flags").  Read once at import, like REPRO_HE_BACKEND;
    bad values fail HERE, loudly, not at the first emit."""
    raw = os.environ.get("REPRO_WIRE_VERSION")
    if raw is None:
        return VERSION
    try:
        v = int(raw)
    except ValueError:
        v = None
    if v not in SUPPORTED_VERSIONS:
        raise WireError(
            f"REPRO_WIRE_VERSION={raw!r} is not a supported wire version; "
            f"this build speaks {SUPPORTED_VERSIONS} (README.md "
            "'Environment variables & flags')")
    return v


EMIT_VERSION = _emit_version_from_env()


# ---------------------------------------------------------------------------
# frame envelope
# ---------------------------------------------------------------------------


def frame(ftype: int, payload: bytes, flags: int = 0,
          version: int | None = None) -> bytes:
    """Wrap `payload` in a frame envelope.

    `version` defaults to EMIT_VERSION (the REPRO_WIRE_VERSION override,
    else VERSION); pass it explicitly to emit a specific legacy layout —
    the caller is responsible for the payload matching that version."""
    version = EMIT_VERSION if version is None else version
    if version not in SUPPORTED_VERSIONS:
        raise WireError(
            f"cannot emit wire version {version}; this build speaks "
            f"{SUPPORTED_VERSIONS} (README.md 'Environment variables & "
            "flags', REPRO_WIRE_VERSION)")
    return _HEADER.pack(MAGIC, version, ftype, flags, len(payload)) + payload


def parse_frame_v(buf, off: int = 0) -> tuple[int, int, int, memoryview, int]:
    """-> (ftype, flags, version, payload, next_off).

    Raises NeedMoreData on a truncated buffer; WireError on bad magic or a
    version this build does not speak (the error names the README section
    and the REPRO_WIRE_VERSION sender pin so operators know which side to
    flip)."""
    view = memoryview(buf)
    if len(view) - off < HEADER_BYTES:
        raise NeedMoreData("incomplete frame header")
    magic, version, ftype, flags, plen = _HEADER.unpack_from(view, off)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic!r} at offset {off}")
    if version not in SUPPORTED_VERSIONS:
        raise WireError(
            f"unsupported wire version {version}: this build speaks "
            f"versions {SUPPORTED_VERSIONS}. Upgrade this receiver, or pin "
            "the sender to a legacy layout with REPRO_WIRE_VERSION=1 — see "
            "README.md 'Environment variables & flags' and the version "
            "rules in DESIGN.md §9.2")
    end = off + HEADER_BYTES + plen
    if len(view) < end:
        raise NeedMoreData("incomplete frame payload")
    return ftype, flags, version, view[off + HEADER_BYTES:end], end


def parse_frame(buf, off: int = 0) -> tuple[int, int, memoryview, int]:
    """-> (ftype, flags, payload, next_off); parse_frame_v without the
    version (kept for callers that only split frames)."""
    ftype, flags, _, payload, end = parse_frame_v(buf, off)
    return ftype, flags, payload, end


def iter_frames(buf) -> Iterator[tuple[int, int, memoryview]]:
    off = 0
    n = len(buf)
    while off < n:
        ftype, flags, payload, off = parse_frame(buf, off)
        yield ftype, flags, payload


class FrameReader:
    """Incremental frame splitter: feed() arbitrary byte slices, pop()
    complete frames.  Holds at most one partial frame of buffered bytes."""

    def __init__(self):
        self._buf = bytearray()

    def feed(self, data: bytes) -> None:
        self._buf.extend(data)

    def pop(self):
        """-> (ftype, flags, payload bytes) or None if no complete frame."""
        try:
            ftype, flags, payload, end = parse_frame(self._buf, 0)
        except NeedMoreData:
            return None
        out = (ftype, flags, bytes(payload))
        payload.release()          # else the bytearray can't be resized
        del self._buf[:end]
        return out

    def __iter__(self):
        while True:
            item = self.pop()
            if item is None:
                return
            yield item


# ---------------------------------------------------------------------------
# array primitive
# ---------------------------------------------------------------------------


def pack_array(a) -> bytes:
    a = np.ascontiguousarray(np.asarray(a))
    code = _DTYPE_CODES.get(a.dtype)
    if code is None:
        raise WireError(f"unsupported wire dtype {a.dtype}")
    head = struct.pack("<BB", code, a.ndim)
    dims = struct.pack(f"<{a.ndim}I", *a.shape) if a.ndim else b""
    return head + dims + a.tobytes()


def unpack_array(payload, off: int = 0) -> tuple[np.ndarray, int]:
    view = memoryview(payload)
    code, ndim = struct.unpack_from("<BB", view, off)
    off += 2
    shape = struct.unpack_from(f"<{ndim}I", view, off) if ndim else ()
    off += 4 * ndim
    dtype = _CODE_DTYPES.get(code)
    if dtype is None:
        raise WireError(f"unknown dtype code {code}")
    # python-int size math (u32 dims from a corrupt frame can overflow
    # fixed-width accumulators), bounds-checked BEFORE touching the buffer:
    # the decoder must never over-read, however the dims were mutated.
    count = 1
    for d in shape:
        count *= int(d)
    nbytes = count * dtype.itemsize
    if nbytes > len(view) - off:
        raise WireError(
            f"array of {count} x {dtype} ({nbytes} B) exceeds the "
            f"{len(view) - off} payload bytes remaining")
    arr = np.frombuffer(view, dtype=dtype, count=count, offset=off)
    return arr.reshape(shape).copy(), off + nbytes


# ---------------------------------------------------------------------------
# ciphertexts
# ---------------------------------------------------------------------------


def serialize_ciphertext(ct: Ciphertext, version: int | None = None) -> bytes:
    """Full ciphertext -> one frame (payload layout version-invariant)."""
    payload = struct.pack("<d", float(ct.scale)) + pack_array(
        np.asarray(ct.data, dtype=np.uint32))
    return frame(T_CIPHERTEXT, payload, version=version)


def _parse_ciphertext(payload) -> Ciphertext:
    (scale,) = struct.unpack_from("<d", payload, 0)
    data, _ = unpack_array(payload, 8)
    return Ciphertext(data=data, scale=scale)


def serialize_seeded_ciphertext(sct: SeededCiphertext,
                                version: int | None = None) -> bytes:
    """Seeded ciphertext -> one frame.

    v2 (default) carries sct.derive as the per-chunk seed-derivation id;
    v1 has no derive field and can only express DERIVE_FOLD_CHUNK (the
    implicit v1 algorithm) — any other derive id refuses to down-serialize
    rather than silently changing meaning."""
    version = EMIT_VERSION if version is None else version
    arr = pack_array(np.asarray(sct.c0, dtype=np.uint32))
    head = struct.pack("<dQI", float(sct.scale), int(sct.seed),
                       int(sct.chunk_offset))
    if version == 1:
        if sct.derive != DERIVE_FOLD_CHUNK:
            raise WireError(
                f"seed-derivation id {sct.derive} is not expressible in "
                "wire v1 frames (v1 implies derive="
                f"{DERIVE_FOLD_CHUNK}); emit v2 (DESIGN.md §9.2)")
        return frame(T_SEEDED_CIPHERTEXT, head + arr, version=1)
    return frame(T_SEEDED_CIPHERTEXT,
                 head + struct.pack("<B", int(sct.derive)) + arr,
                 version=version)


def _parse_seeded_ciphertext(payload, version: int = 1) -> SeededCiphertext:
    scale, seed, chunk_offset = struct.unpack_from("<dQI", payload, 0)
    off = struct.calcsize("<dQI")
    derive = DERIVE_FOLD_CHUNK
    if version >= 2:
        (derive,) = struct.unpack_from("<B", payload, off)
        off += 1
        if derive not in DERIVES:
            raise WireError(
                f"unknown seed-derivation id {derive} in v{version} seeded "
                f"ciphertext; this build knows {DERIVES} (DESIGN.md §9.2)")
    c0, _ = unpack_array(payload, off)
    return SeededCiphertext(c0=c0, seed=seed, scale=scale,
                            chunk_offset=chunk_offset, derive=derive)


# ---------------------------------------------------------------------------
# transcipher uplink (DESIGN.md §15)
# ---------------------------------------------------------------------------


def serialize_masked_chunk(mc: MaskedChunk,
                           version: int | None = None) -> bytes:
    """Masked transcipher chunk -> one frame.  v2+ only: the type postdates
    v1, so down-serialization refuses rather than inventing a layout."""
    version = EMIT_VERSION if version is None else version
    if version < 2:
        raise WireError(
            "transcipher masked chunks are not expressible in wire v1 "
            "frames; emit v2 (DESIGN.md §15)")
    head = struct.pack("<dQI", float(mc.scale), int(mc.a_seed),
                       int(mc.chunk_offset))
    payload = head + struct.pack("<B", int(mc.derive)) \
        + pack_array(np.asarray(mc.masked, dtype=np.uint32))
    return frame(T_MASKED_CHUNK, payload, version=version)


def _parse_masked_chunk(payload, version: int) -> MaskedChunk:
    if version < 2:
        raise WireError(
            "masked transcipher chunk in a v1 frame; transcipher requires "
            "wire v2 (DESIGN.md §15)")
    scale, a_seed, chunk_offset = struct.unpack_from("<dQI", payload, 0)
    off = struct.calcsize("<dQI")
    (derive,) = struct.unpack_from("<B", payload, off)
    off += 1
    if derive not in DERIVES:
        raise WireError(
            f"unknown seed-derivation id {derive} in v{version} masked "
            f"chunk; this build knows {DERIVES} (DESIGN.md §9.2)")
    masked, _ = unpack_array(payload, off)
    if masked.dtype != np.uint32 or masked.ndim != 2:
        raise WireError(
            f"masked chunk array must be u32[B, N], got "
            f"{masked.dtype}[{masked.ndim}d]")
    return MaskedChunk(masked=masked, a_seed=a_seed, scale=scale,
                       chunk_offset=chunk_offset, derive=derive)


def serialize_transcipher_seed(sct: SeededCiphertext,
                               version: int | None = None) -> bytes:
    """The escrow keystream-seed ciphertext -> one wrapper frame (nests a
    normal seeded-ciphertext frame; v2+ only like every transcipher
    frame)."""
    version = EMIT_VERSION if version is None else version
    if version < 2:
        raise WireError(
            "transcipher seed frames are not expressible in wire v1 "
            "frames; emit v2 (DESIGN.md §15)")
    return frame(T_TRANSCIPHER_SEED,
                 serialize_seeded_ciphertext(sct, version=version),
                 version=version)


# ---------------------------------------------------------------------------
# plain segment (quantized plaintext partition)
# ---------------------------------------------------------------------------


def serialize_plain_segment(arr: np.ndarray, codec: str, qscale: float,
                            version: int | None = None) -> bytes:
    payload = struct.pack("<Bd", _PLAIN_CODEC_IDS[codec], float(qscale)) \
        + pack_array(arr)
    return frame(T_PLAIN_SEGMENT, payload, version=version)


def _parse_plain_segment(payload) -> tuple[np.ndarray, str, float]:
    codec_id, qscale = struct.unpack_from("<Bd", payload, 0)
    arr, _ = unpack_array(payload, struct.calcsize("<Bd"))
    return arr, _PLAIN_CODEC_NAMES[codec_id], qscale


# ---------------------------------------------------------------------------
# protected update (one-shot, non-streaming)
# ---------------------------------------------------------------------------


def serialize_update(upd, *, seeded: SeededCiphertext | None = None,
                     plain_codec: str = "f32",
                     version: int | None = None) -> bytes:
    """ProtectedUpdate -> one nested frame.

    If `seeded` is given it replaces upd.ct on the wire (the caller got it
    from compress.seed_compress on a seeded encryption of the same values).
    `version` pins every frame in the nest (default: the emit default).
    """
    from repro.wire import compress as _c
    ct_frame = (serialize_seeded_ciphertext(seeded, version=version)
                if seeded is not None
                else serialize_ciphertext(upd.ct, version=version))
    arr, qscale = _c.quantize_plain(np.asarray(upd.plain), plain_codec)
    return frame(T_PROTECTED_UPDATE,
                 ct_frame + serialize_plain_segment(arr, plain_codec, qscale,
                                                    version=version),
                 version=version)


def _parse_update(payload, ctx):
    from repro.core.secure_agg import ProtectedUpdate
    from repro.wire import compress as _c
    ftype, _, ct_version, ct_payload, off = parse_frame_v(payload, 0)
    if ftype == T_CIPHERTEXT:
        ct = _parse_ciphertext(ct_payload)
    elif ftype == T_SEEDED_CIPHERTEXT:
        if ctx is None:
            raise WireError("seeded ciphertext needs a ctx to expand")
        # deserialize expands it, outside the parse-error conversion
        ct = _parse_seeded_ciphertext(ct_payload, ct_version)
    else:
        raise WireError(f"unexpected inner frame type {ftype}")
    ftype, _, pl_payload, _ = parse_frame(payload, off)
    if ftype != T_PLAIN_SEGMENT:
        raise WireError(f"expected plain segment, got type {ftype}")
    arr, codec, qscale = _parse_plain_segment(pl_payload)
    return ProtectedUpdate(ct=ct, plain=_c.dequantize_plain(arr, codec, qscale))


# ---------------------------------------------------------------------------
# key bundles + mask partition
# ---------------------------------------------------------------------------


def serialize_keyset(keys: dict) -> bytes:
    """dict[str, array] -> frame (covers pk, eval keys, threshold shares)."""
    parts = [struct.pack("<I", len(keys))]
    for name, arr in sorted(keys.items()):
        nb = name.encode("utf-8")
        parts.append(struct.pack("<H", len(nb)) + nb)
        parts.append(pack_array(np.asarray(arr, dtype=np.uint32)))
    return frame(T_KEYSET, b"".join(parts))


def _parse_keyset(payload) -> dict:
    (n,) = struct.unpack_from("<I", payload, 0)
    if n > (len(payload) - 4) // 4:
        # every entry needs >= 4 bytes (name length + array head): a bound
        # that keeps a corrupt count from driving a multi-billion-iteration
        # parse loop
        raise WireError(f"keyset declares {n} entries but only "
                        f"{len(payload) - 4} payload bytes follow")
    off = 4
    out = {}
    for _ in range(n):
        (nlen,) = struct.unpack_from("<H", payload, off)
        off += 2
        name = bytes(memoryview(payload)[off:off + nlen]).decode("utf-8")
        off += nlen
        arr, off = unpack_array(payload, off)
        out[name] = arr
    return out


def serialize_partition(part: MaskPartition) -> bytes:
    payload = struct.pack("<QI", part.n_total, part.slots) \
        + pack_array(part.enc_idx) + pack_array(part.plain_idx)
    return frame(T_MASK_PARTITION, payload)


def _parse_partition(payload) -> MaskPartition:
    n_total, slots = struct.unpack_from("<QI", payload, 0)
    off = struct.calcsize("<QI")
    enc_idx, off = unpack_array(payload, off)
    plain_idx, _ = unpack_array(payload, off)
    return MaskPartition(n_total=int(n_total),
                         enc_idx=enc_idx.astype(np.int32),
                         plain_idx=plain_idx.astype(np.int32),
                         slots=int(slots))


# ---------------------------------------------------------------------------
# generic entry point
# ---------------------------------------------------------------------------

_PARSERS = {
    T_CIPHERTEXT: lambda p, ctx, v: _parse_ciphertext(p),
    T_SEEDED_CIPHERTEXT: lambda p, ctx, v: _parse_seeded_ciphertext(p, v),
    T_PROTECTED_UPDATE: lambda p, ctx, v: _parse_update(p, ctx),
    T_KEYSET: lambda p, ctx, v: _parse_keyset(p),
    T_MASK_PARTITION: lambda p, ctx, v: _parse_partition(p),
    T_MASKED_CHUNK: lambda p, ctx, v: _parse_masked_chunk(p, v),
    # unwrap to the nested escrow seeded-ciphertext artifact
    T_TRANSCIPHER_SEED: lambda p, ctx, v: deserialize(p, ctx, 0)[0],
}


def deserialize(buf, ctx=None, off: int = 0):
    """One frame -> (artifact, next_off).  `ctx` is needed to expand seeded
    ciphertexts nested in protected updates.

    Version handling is per frame (header byte): v1 and v2 frames decode
    transparently — the only layout difference is the seeded-ciphertext
    derive field (DESIGN.md §9.2) — and unsupported versions raise
    WireError before any payload is touched.

    Robustness contract (fuzzed in tests/test_wire.py): ANY mutated or
    truncated input raises WireError (NeedMoreData for a short buffer) —
    the decoder never surfaces a raw struct/numpy error, never loops on a
    corrupt count, and never reads past the frame payload."""
    ftype, _, version, payload, end = parse_frame_v(buf, off)
    parser = _PARSERS.get(ftype)
    if parser is None:
        raise WireError(f"no parser for frame type {ftype:#x}")
    try:
        out = parser(payload, ctx, version)
    except WireError:
        raise
    except PARSE_ERRORS as e:
        # struct.error / KeyError / reshape ValueError etc. from a payload
        # whose bytes were mutated after the envelope survived
        raise WireError(
            f"malformed frame type {ftype:#x} payload: {e!r}") from e
    if ftype == T_PROTECTED_UPDATE and not isinstance(out.ct, Ciphertext):
        check_seeded(out.ct, ctx)
        out = dataclasses.replace(out, ct=out.ct.expand(ctx))
    return out, end


def check_seeded(sct, ctx) -> None:
    """Validate a parsed seeded ciphertext against `ctx` before its `a`
    rows are expanded on the device, so a wire-mutated shape, seed or
    chunk offset is rejected here as WireError and never reaches a device
    graph."""
    c0 = np.asarray(sct.c0)
    if (c0.ndim != 3 or c0.dtype != np.uint32
            or c0.shape[2] != ctx.n_poly
            or not 1 <= c0.shape[1] <= ctx.n_limbs):
        raise WireError(
            f"seeded ciphertext c0 {c0.dtype}{list(c0.shape)} does not fit "
            f"this context's [chunks, <= {ctx.n_limbs} limbs, "
            f"{ctx.n_poly}]")
    if not 0 <= int(sct.seed) < 1 << 63:
        # PRNGKey takes a signed 64-bit seed; no conforming sender emits
        # a larger one
        raise WireError(f"seeded ciphertext seed {sct.seed} exceeds 2^63")
    if not 0 <= int(sct.chunk_offset) <= (1 << 31) - c0.shape[0]:
        # chunk ids are int32 in the expansion graph
        raise WireError(f"seeded ciphertext chunks [{sct.chunk_offset}, "
                        f"+{c0.shape[0]}) exceed 2^31")
