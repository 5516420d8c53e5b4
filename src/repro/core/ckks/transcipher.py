"""Transcipher (hybrid-HE) uplink: additive-masked updates, server-side
homomorphic unmask into the seeded-ciphertext accumulator path.

The thin-client problem (DESIGN.md §15): the seeded uplink still makes
every client run L forward NTTs and the full RNS sampling stack.  Hybrid
homomorphic encryption moves that work to the server: the client encrypts
its update with a cheap symmetric stream cipher and the server
*transciphers* the result into CKKS without ever seeing the plaintext.

This implementation is an additive-mask instance chosen so the server
output is BIT-IDENTICAL to the seeded-CKKS path (the acceptance
invariant, pinned by tests/test_transcipher.py):

  offline (provisioner = any sk holder, per client x round):
    seed    = FRESH SECRET keystream seed (64-bit), drawn from the
              provisioner's secret noise PRNG key — never from the
              wire-public a_seed (the pad must depend on secret material;
              it reaches the client only inside ClientMaterials, i.e. out
              of band, and auditors only via seed_ct)
    c0_zero = c0 of a seeded encryption of ZERO        (-a s + e, [B, L, N])
    K       = keystream pad = PRG(seed), uniform u32[B, N] in
              [2^30, 2^32 - 2^30)
    D       = c0_zero - NTT(lift(K))                   (server material)
    seed_ct = tiny seeded CKKS encryption of the keystream seed's four
              u16 digits (1 chunk) under escrow_a_seed — the
              "HE-encrypted symmetric key" of the HHE literature, shipped
              on the uplink for escrow/audit.

  online (client, NO NTT / NO modular arithmetic):
    c       = encode_centered(values)                  (FFT + rint, i64[B, N])
    masked  = (c + K) as u32                            -> the wire

  server (per arriving chunk, kernels/lift.py riding LimbTables):
    c0 = NTT(mod_lift(masked)) + D
    a  = expand_a_rows(a_seed, ...)     (the negotiated derive id)
    ct = stack([c0, a])  ->  existing StreamIngest accumulator

  why it is exact: the pad window keeps masked = c + K inside [1, 2^32-2]
  with NO u32 wrap (|c| < 2^30 is validated client-side), so
  NTT((c+K) mod q) - NTT(K mod q) = NTT(c mod q) per limb, and
  c0 = NTT(c mod q) + c0_zero — precisely the seeded path's c0 for the
  same noise key.  Uplink bytes: 4 B/coeff vs L x 4 B/coeff seeded c0
  (0.5x at L=2), measured by `benchmarks.run uplink-hybrid`.

Security note (prototype scope): a one-time additive pad over Z_2^32 —
seed/pad reuse across rounds leaks differences, exactly like a_seed reuse
in the seeded path; the provisioner role models the HHE setup phase
(Correia et al.; Nguyen et al.) where symmetric key material is
established out of band.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ckks import cipher, encoding
from repro.core.ckks.cipher import (DERIVE_CTR, DERIVE_FOLD_CHUNK,
                                    Ciphertext)
from repro.core.ckks.params import CkksContext
from repro.kernels import ops

# client-side centered coefficients must satisfy |c| < 2**BOUND_BITS; with
# the pad window below, masked = c + K then spans [1, 2**32 - 2] with no
# u32 wrap (the exactness anchor).  2**30 also matches the q < 2**30 prime
# bound, so any encodable plaintext already fits.
BOUND_BITS = 30
_PAD_LO = np.uint32(1 << BOUND_BITS)

# the escrow ciphertext's own (public) a_seed lives in a region disjoint
# from every caller-issued update a_seed, so no PUBLIC a stream is keyed
# twice (a_seed itself stays < 2**40 in every caller — fl/client.py
# derives it as rnd*1e6 + cid).  The keystream seed is NOT partitioned
# from a_seed: it is fresh secret material (see provision) — deriving it
# from any wire-public value would let a passive observer recompute the
# pad and strip the mask.
ESCROW_SEED_OFFSET = 1 << 40

# fold_in tag under which provision() draws the secret keystream seed
# from the noise key (disjoint from the per-chunk noise ids 0..B-1 and
# the escrow-noise tag 0x5EED).
_PAD_KEY_TAG = 0x5AD5EED


def _pad_base_key(keystream_seed: int):
    """The 64-bit keystream seed as raw threefry key words [hi, lo] —
    what PRNGKey(seed) builds, but accepting the full u64 range (PRNGKey
    overflows past 2^63, and secret seeds are uniform over 64 bits)."""
    s = int(keystream_seed)
    return jnp.array([(s >> 32) & 0xFFFFFFFF, s & 0xFFFFFFFF],
                     dtype=jnp.uint32)


def expand_pad_rows(n_poly: int, keystream_seed: int, start, count: int,
                    derive: int = DERIVE_CTR):
    """Keystream pad rows u32[count, N], uniform in [2^30, 2^32 - 2^30).

    `keystream_seed` is SECRET (provision() draws it from the
    provisioner's noise key): everything else here — the derive registry,
    the chunk indices — is public, so the seed is the only thing standing
    between a wire observer and the pad.  Per-chunk keys come from the
    SAME wire-negotiated derive registry as the a stream
    (cipher.derive_chunk_keys), so pads are re-derivable for any
    contiguous chunk slice — client and provisioner agree bit for bit,
    and streaming chunks need no global state.  The window is exactly
    [2^30, 3*2^30): lo + a uniform 31-bit draw."""
    base = _pad_base_key(keystream_seed)
    keys = cipher.derive_chunk_keys(base, start, count, derive)
    hi = jnp.uint32(1 << 31)      # u32 literal: 2**31 overflows int32 args
    with cipher.normative_streams():
        return jax.vmap(
            lambda k: _PAD_LO + jax.random.randint(
                k, (n_poly,), jnp.uint32(0), hi, dtype=jnp.uint32))(keys)


def escrow_values(keystream_seed: int, ctx: CkksContext) -> np.ndarray:
    """The keystream seed's four u16 digits as a 1-chunk slot vector —
    what `seed_ct` encrypts (little-endian digit order, slots 0..3)."""
    vals = np.zeros((1, ctx.slots), dtype=np.float32)
    for i in range(4):
        vals[0, i] = float((int(keystream_seed) >> (16 * i)) & 0xFFFF)
    return vals


@dataclasses.dataclass
class ClientMaterials:
    """What a thin client holds for one (client, round): symmetric key
    material plus the pre-provisioned escrow ciphertext it forwards.
    Contains NO CKKS secret-key material and requires NO NTT to use.
    `keystream_seed` is the symmetric SECRET: it must reach the client
    over a confidential channel (the HHE setup phase), never the
    aggregation wire — only its escrow ciphertext is ever serialized."""

    keystream_seed: int
    a_seed: int
    chunk_offset: int
    n_chunks: int
    derive: int
    scale: float
    seed_ct: Ciphertext          # escrow encryption of the keystream seed
    escrow_a_seed: int           # its a_seed (wire layer seed-compresses)


@dataclasses.dataclass
class ServerMaterials:
    """What the aggregator holds: the unmask offset D = c0_zero - NTT(K)
    and the public-stream parameters.  D is a single ciphertext component
    — it hides K under an encryption of zero, so holding it reveals
    neither the pad nor any update."""

    d: Any                       # u32[B, L, N], NTT domain
    a_seed: int
    chunk_offset: int
    n_chunks: int
    derive: int
    scale: float


def provision(ctx: CkksContext, sk: dict, key, a_seed: int, n_chunks: int,
              *, chunk_offset: int = 0, derive: int = DERIVE_CTR,
              scale: float | None = None, keystream_seed: int | None = None
              ) -> tuple[ClientMaterials, ServerMaterials]:
    """Offline HHE setup for one (client, round): draw a fresh SECRET
    keystream seed, build the server's unmask material D, and
    escrow-encrypt the seed.  `key` is the noise PRNG key the SEEDED path
    would have used — same key, same a_seed => the unmasked server
    ciphertext is bit-identical to `encrypt_coeffs_seeded` (the tests'
    invariant).

    The keystream seed is the pad's only secret: by default it is drawn
    from `key` (which never crosses the wire), or the caller supplies one
    established out of band (`keystream_seed=`).  It must NEVER be derived
    from a_seed or any other wire-visible value — a_seed rides cleartext
    in every MASKED_CHUNK frame, so a pad re-derivable from it would hand
    the plaintext update to any passive observer.  It reaches the client
    only inside ClientMaterials and auditors only via the escrow
    ciphertext; ServerMaterials never contains it."""
    scale = float(scale if scale is not None else ctx.delta)
    if keystream_seed is None:
        # four u16 digits from the secret noise key -> uniform 64-bit seed
        # (the same digit decomposition escrow_values() encrypts)
        with cipher.normative_streams():
            digits = jax.random.randint(
                jax.random.fold_in(key, _PAD_KEY_TAG), (4,), 0, 1 << 16)
        keystream_seed = sum(int(d) << (16 * i)
                             for i, d in enumerate(np.asarray(digits)))
    keystream_seed = int(keystream_seed)
    if not 0 <= keystream_seed < 1 << 64:
        raise ValueError(
            f"keystream_seed must fit the escrow encoding's 64 bits, got "
            f"{keystream_seed}")
    escrow_a_seed = int(a_seed) + ESCROW_SEED_OFFSET
    l = ctx.n_limbs
    zeros = jnp.zeros((n_chunks, l, ctx.n_poly), dtype=jnp.uint32)
    ct_zero = cipher.encrypt_coeffs_seeded(ctx, sk, zeros, key, a_seed,
                                           scale=scale, derive=derive)
    c0_zero = ct_zero.data[..., 0, :]                       # [B, L, N]
    pad = expand_pad_rows(ctx.n_poly, keystream_seed, chunk_offset,
                          n_chunks, derive)
    ntt_k = ops.ntt_fwd(ops.mod_lift(pad, l, ctx), ctx)
    d = ops.mod_sub(c0_zero, ntt_k, ctx)
    seed_ct = cipher.encrypt_values_seeded(
        ctx, sk, jnp.asarray(escrow_values(keystream_seed, ctx)),
        jax.random.fold_in(key, 0x5EED), escrow_a_seed, derive=derive)
    cm = ClientMaterials(keystream_seed=keystream_seed, a_seed=int(a_seed),
                         chunk_offset=int(chunk_offset),
                         n_chunks=int(n_chunks), derive=int(derive),
                         scale=scale, seed_ct=seed_ct,
                         escrow_a_seed=escrow_a_seed)
    sm = ServerMaterials(d=d, a_seed=int(a_seed),
                         chunk_offset=int(chunk_offset),
                         n_chunks=int(n_chunks), derive=int(derive),
                         scale=scale)
    return cm, sm


# ---------------------------------------------------------------------------
# client online path — numpy only, no NTT, no modular arithmetic
# ---------------------------------------------------------------------------


def mask_coeffs_centered(ctx: CkksContext, cm: ClientMaterials,
                         c_int: np.ndarray) -> np.ndarray:
    """Centered i64 coefficients [B, N] -> masked u32[B, N] for the wire.

    The one validation a thin client must run: |c| < 2**BOUND_BITS, so the
    integer sum c + K cannot wrap u32 (exactness would silently die
    otherwise)."""
    c_int = np.asarray(c_int, dtype=np.int64)
    if c_int.shape[0] != cm.n_chunks:
        raise ValueError(
            f"masked update has {c_int.shape[0]} chunks but the provisioned "
            f"materials cover {cm.n_chunks}; re-provision for this shape")
    amax = int(np.max(np.abs(c_int))) if c_int.size else 0
    if amax >= (1 << BOUND_BITS):
        raise ValueError(
            f"centered coefficient magnitude {amax} >= 2**{BOUND_BITS}; "
            f"the transcipher pad window cannot absorb it — lower the "
            f"encoding delta or the update norm (DESIGN.md §15)")
    pad = np.asarray(expand_pad_rows(
        ctx.n_poly, cm.keystream_seed, cm.chunk_offset, c_int.shape[0],
        cm.derive)).astype(np.int64)
    return (pad + c_int).astype(np.uint32)     # in [1, 2**32 - 2], exact


def mask_values(ctx: CkksContext, cm: ClientMaterials,
                values: np.ndarray) -> np.ndarray:
    """f32[B, slots] update -> masked u32[B, N]: the entire client-side
    encrypt is one real FFT, a rint, and an add."""
    c_int = encoding.encode_centered(
        np.asarray(values, dtype=np.float32), ctx, cm.scale)
    return mask_coeffs_centered(ctx, cm, c_int)


# ---------------------------------------------------------------------------
# server transcipher — lift + NTT + offset, then the normal seeded shape
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("ctx", "token", "derive"))
def _unmask_graph(ctx: CkksContext, token, d_rows, masked, a_base,
                  row_start, derive: int):
    l = d_rows.shape[-2]
    c0 = ops.mod_add(ops.ntt_fwd(ops.mod_lift(masked, l, ctx), ctx),
                     d_rows, ctx)
    keys = cipher.derive_chunk_keys(a_base, row_start, masked.shape[0],
                                    derive)
    a = jax.vmap(lambda k: cipher._uniform_residues(
        k, (ctx.n_poly,), ctx.tables.qs))(keys)
    return jnp.stack([c0, a], axis=-2)


def check_unmask_rows(sm: ServerMaterials, chunk_idx: int,
                      rows: int) -> None:
    """ValueError unless chunk rows [chunk_idx, chunk_idx + rows) lie in
    the range `sm` was provisioned for."""
    r0 = int(chunk_idx) - sm.chunk_offset
    if r0 < 0 or r0 + rows > sm.n_chunks:
        raise ValueError(
            f"chunk rows [{chunk_idx}, {chunk_idx + rows}) fall outside the "
            f"provisioned range [{sm.chunk_offset}, "
            f"{sm.chunk_offset + sm.n_chunks})")


def server_unmask(ctx: CkksContext, sm: ServerMaterials, masked_rows,
                  chunk_idx: int) -> Ciphertext:
    """Masked u32[B, N] rows starting at global `chunk_idx` -> the full
    seeded-equivalent ciphertext chunk u32[B, L, 2, N].

    One jitted graph: mod_lift (kernels/lift.py), forward NTT, the D
    offset, and the derive-registry a expansion.  Output bits equal the
    seeded path's for the provisioning noise key — so the result drops
    straight into the existing StreamIngest accumulator."""
    masked = jnp.asarray(masked_rows, dtype=jnp.uint32)
    b = int(masked.shape[0])
    check_unmask_rows(sm, chunk_idx, b)
    r0 = int(chunk_idx) - sm.chunk_offset
    data = _unmask_graph(ctx, ops.backend_token(), sm.d[r0:r0 + b], masked,
                         jax.random.PRNGKey(int(sm.a_seed)), chunk_idx,
                         int(sm.derive))
    return Ciphertext(data=data, scale=sm.scale)


# ---------------------------------------------------------------------------
# byte accounting (benchmarks/run.py uplink-hybrid)
# ---------------------------------------------------------------------------


def masked_uplink_bytes(n_chunks: int, n_poly: int) -> int:
    """Wire bytes of the masked payload: 4 B/coeff, limb-free."""
    return n_chunks * n_poly * 4


def seeded_uplink_bytes(n_chunks: int, n_limbs: int, n_poly: int) -> int:
    """Wire bytes of the seeded-CKKS c0 payload: L x 4 B/coeff."""
    return n_chunks * n_limbs * n_poly * 4
