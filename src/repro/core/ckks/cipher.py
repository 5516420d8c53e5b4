"""RNS-CKKS cipher: keygen / encrypt / decrypt / homomorphic ops.

Built on the limb-fused execution engine (kernels/ops.py): every sampling
helper vectorizes the RNS limb axis via the stacked constant tables on
`CkksContext.tables`, and keygen / encrypt / decrypt / weighted_sum each run
as ONE jitted graph (static-keyed on (ctx, ops.backend_token()) so backend
registry changes retrace).  Ciphertexts are u32[..., L, 2, N] tensors in
bit-reversed NTT domain, wrapped with their scale.

Scale discipline (depth-1, the paper's setting):
  fresh ct: scale = delta
  ct (*) plain-scalar weight: scale = delta**2   (no rescale — lazy; decode
  divides by the ct scale, saving one iNTT+NTT per limb per round. `rescale`
  is still provided and tested.)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ckks import encoding
from repro.core.ckks.params import CkksContext
from repro.kernels import ops, ref as _ref


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Ciphertext:
    """data: u32[..., L, 2, N] NTT domain; scale: encoding scale."""

    data: Any
    scale: float = dataclasses.field(metadata=dict(static=True), default=1.0)

    @property
    def n_limbs(self):
        return self.data.shape[-3]

    @property
    def c0(self):
        return self.data[..., 0, :]

    @property
    def c1(self):
        return self.data[..., 1, :]


# ---------------------------------------------------------------------------
# sampling helpers (all jittable)
# ---------------------------------------------------------------------------
#
# Each sampler takes the stacked u32[L] prime table explicitly (not a ctx)
# so the sharded engine (core/ckks/sharded.py) can hand in a per-shard limb
# slice: the random draw's SHAPE never involves L for the ternary/gaussian
# samplers, so the PRNG stream — and therefore the ciphertext — is
# bit-identical however the limb axis is sharded.
#
# The encrypt bodies additionally derive one PRNG key PER CIPHERTEXT CHUNK
# via fold_in(key, chunk_id) (`_chunk_keys`) and draw each chunk's samples
# with shape (N,): no draw shape involves the batch size either, so the
# stream is invariant under sharding the chunk axis across devices — each
# shard re-derives its local chunks' keys from the global chunk ids.  This
# is the wire-v2 derivation contract (DESIGN.md §9).
#
# Every draw and key split here runs under `normative_streams()`: the
# streams are wire format (DESIGN.md §9.2, §15.2) — a server regenerates a
# client's `a` from its seed — so they must not follow JAX's default
# threefry mode, which became `partitionable` in JAX 0.5 and reorders the
# bits.  Model training and everything else keep the JAX default.


def normative_streams():
    """Context that pins the original (non-partitionable) threefry key
    stream for the draws and splits inside it.  Used at trace time, so a
    jitted graph traced inside keeps the pinned stream."""
    return jax.threefry_partitionable(False)


def split_key(key, num: int = 2):
    """jax.random.split under the pinned stream (keys that feed the
    normative draws)."""
    with normative_streams():
        return jax.random.split(key, num)


def _ternary_residues(key, shape, qs):
    """Uniform ternary {-1,0,1} -> per-limb residues u32[..., L, N].

    One draw of ternary symbols over `shape`, broadcast against the u32[L]
    prime table `qs` — the limb axis is never looped (and never drawn)."""
    with normative_streams():
        t = jax.random.randint(key, shape, 0, 3)[..., None, :]  # {-1,0,1}
    qm1 = (jnp.asarray(qs) - np.uint32(1))[:, None]         # [L, 1]
    r = jnp.where(t == 0, qm1,
                  jnp.where(t == 1, np.uint32(0), np.uint32(1)))
    return r.astype(jnp.uint32)  # [..., L, N]


def _gaussian_residues(key, shape, qs, sigma: float):
    """Discrete-gaussian residues u32[..., L, N]: one normal draw over
    `shape`, centered-reduced against each limb prime."""
    with normative_streams():
        e = jnp.rint(float(sigma) * jax.random.normal(key, shape)) \
            .astype(jnp.int32)
    return _ref.mod_reduce_centered(e[..., None, :],
                                    jnp.asarray(qs)[:, None])  # [..., L, N]


# ---------------------------------------------------------------------------
# per-chunk seed-derivation registry (wire-v2 derive ids, DESIGN.md §9.2)
# ---------------------------------------------------------------------------
#
# A seeded ciphertext's public c1 = a stream is expanded per chunk from a
# base PRNG key; the DERIVE id carried by wire-v2 SEEDED_CIPHERTEXT frames
# names HOW chunk i's key is derived from (base, i).  Both sides — client
# encrypt (here and in sharded.py) and server expand_a_rows — dispatch
# through this registry, so adding an algorithm is one entry.  Only the
# public a stream is derive-governed; the secret noise stream always uses
# fold_in (it never crosses the wire).

DERIVE_FOLD_CHUNK = 1    # chunk i's key = fold_in(base, i)
DERIVE_CTR = 2           # chunk i's key = [h_hi, h_lo + i], h = one
                         # fold_in hash of the base key (counter mode)

# DERIVE_CTR domain-separation tag: every counter stream starts from
# fold_in(base, _CTR_TAG), so base keys that differ in ANY bit map to
# unrelated counter blocks (PRNGKey(s) and PRNGKey(s+1) differ only in
# the low word — without the hash their streams would be shifted copies).
_CTR_TAG = 0x435452      # "CTR"


def _fold_chunk_keys(base, start, count: int):
    """DERIVE_FOLD_CHUNK: key for chunk i is fold_in(base, i) with i the
    GLOBAL chunk index, so any contiguous slice of the chunk axis can
    re-derive exactly its own keys — the property that lets the sharded
    engine split the batch across the `data` mesh axis without changing a
    single sampled bit (DESIGN.md §9).  `start` may be a traced offset
    (the sharded client passes axis_index * b_loc)."""
    ids = jnp.asarray(start) + jnp.arange(count)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(ids)


def _ctr_keys(base, start, count: int):
    """DERIVE_CTR: ONE fold_in hash of the base key (domain separation,
    `_CTR_TAG`), then chunk i's key is the raw uint32[2] counter block
    [h_hi, h_lo + i] over the hashed words (wrap is mod 2^32, matching the
    u32 wire id space).  Still cheaper than a fold_in chain — one hash per
    STREAM, not per chunk — and equally shard-invariant: the counter is
    the GLOBAL chunk index.

    The up-front hash is load-bearing: callers key streams from SEQUENTIAL
    seeds (fl.client.uplink_a_seed packs rnd*1_000_003 + cid, so adjacent
    clients' base keys differ only in the low word).  Counting over the
    RAW words would make client cid's chunk i+1 key equal client cid+1's
    chunk i key — the same uniform `a` (and pad) row reused across
    different ciphertexts, the exact a_seed-reuse leak the seeded-path
    docstrings warn about.  Hashing first maps nearby seeds to unrelated
    counter blocks, so cross-stream collisions need a ~2^-64 birthday
    coincidence instead of mere adjacency."""
    h = jnp.asarray(jax.random.fold_in(base, _CTR_TAG), dtype=jnp.uint32)
    ctr = jnp.asarray(start, jnp.uint32) + jnp.arange(count,
                                                      dtype=jnp.uint32)
    hi = jnp.broadcast_to(h[0], ctr.shape)
    return jnp.stack([hi, h[1] + ctr], axis=-1)


DERIVE_KEYFNS = {DERIVE_FOLD_CHUNK: _fold_chunk_keys,
                 DERIVE_CTR: _ctr_keys}
DERIVES = tuple(sorted(DERIVE_KEYFNS))


def derive_chunk_keys(base, start, count: int,
                      derive: int = DERIVE_FOLD_CHUNK):
    """Per-chunk PRNG keys for ciphertext chunks [start, start+count),
    derived by the registered algorithm `derive`.  Unknown ids raise the
    actionable registry error (the wire layer re-raises it as WireError)."""
    fn = DERIVE_KEYFNS.get(derive)
    if fn is None:
        raise ValueError(
            f"unknown seed-derivation id {derive}; this build implements "
            f"{DERIVES} (DESIGN.md §9.2)")
    return fn(base, start, count)


def _chunk_keys(key, start, count: int):
    """Noise-stream chunk keys: always fold_in (never wire-negotiated)."""
    return _fold_chunk_keys(key, start, count)


def _uniform_residues(key, shape, qs):
    """Uniform residues u32[..., L, N]: ONE randint draw of the full
    [..., L, N] block with the per-limb prime table as broadcast maxval.

    Unlike the other samplers, the draw shape includes L, so the stream
    depends on the limb count: sharded keygen draws the FULL table on every
    shard and slices its local limbs (see sharded.py) to stay bit-identical.
    """
    qs = jnp.asarray(qs, dtype=jnp.uint32)
    full = shape[:-1] + (qs.shape[0], shape[-1])
    with normative_streams():
        return jax.random.randint(key, full, jnp.uint32(0), qs[:, None],
                                  dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# key generation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("ctx", "token"))
def _keygen_graph(ctx: CkksContext, token, key):
    k_s, k_a, k_e = split_key(key, 3)
    n = ctx.n_poly
    qs = ctx.tables.qs
    s = ops.ntt_fwd(_ternary_residues(k_s, (n,), qs), ctx)        # [L, N]
    s_mont = ops.to_mont(s, ctx)
    a = _uniform_residues(k_a, (n,), qs)                          # NTT domain
    e = ops.ntt_fwd(_gaussian_residues(k_e, (n,), qs, ctx.error_sigma), ctx)
    a_s = ops.mont_mul(a, s_mont, ctx)
    pk0 = ops.mod_add(ops.mod_neg(a_s, ctx), e, ctx)
    return s_mont, ops.to_mont(pk0, ctx), ops.to_mont(a, ctx)


def keygen(ctx: CkksContext, key) -> tuple[dict, dict]:
    """Returns (sk, pk) — one jitted graph.

    sk = {"s_mont": u32[L, N]}           NTT-domain Montgomery secret
    pk = {"pk0_mont", "pk1_mont": u32[L, N]}  b = -(a s) + e, a
    """
    s_mont, pk0_mont, pk1_mont = _keygen_graph(ctx, ops.backend_token(), key)
    return {"s_mont": s_mont}, {"pk0_mont": pk0_mont, "pk1_mont": pk1_mont}


# ---------------------------------------------------------------------------
# encrypt / decrypt
# ---------------------------------------------------------------------------

def _encrypt_body(ctx: CkksContext, pk0_mont, pk1_mont, m_coeff, key,
                  chunk_start: int = 0):
    """Shared trace of the public-key encrypt graph (m_coeff already
    coefficient-domain residues).

    Chunk i's (u, e0, e1) draws come from split(fold_in(key, i), 3) — one
    (N,)-shaped draw per chunk, never a (B, N) batch draw — so the stream
    only depends on each chunk's global index, not on how many chunks this
    trace happens to hold.  `chunk_start` offsets the global ids; the
    sharded engine passes each shard's row offset and gets bit-identical
    ciphertexts (DESIGN.md §9)."""
    b = m_coeff.shape[0]
    n = ctx.n_poly
    qs = ctx.tables.qs
    sigma = ctx.error_sigma
    k3 = jax.vmap(lambda k: split_key(k, 3))(
        _chunk_keys(key, chunk_start, b))                    # [B, 3] keys
    m = ops.ntt_fwd(m_coeff, ctx)
    u = ops.ntt_fwd(jax.vmap(
        lambda k: _ternary_residues(k, (n,), qs))(k3[:, 0]), ctx)
    e0 = ops.ntt_fwd(jax.vmap(
        lambda k: _gaussian_residues(k, (n,), qs, sigma))(k3[:, 1]), ctx)
    e1 = ops.ntt_fwd(jax.vmap(
        lambda k: _gaussian_residues(k, (n,), qs, sigma))(k3[:, 2]), ctx)
    c0 = ops.mul_add(u, pk0_mont[None], ops.mod_add(e0, m, ctx), ctx)
    c1 = ops.mul_add(u, pk1_mont[None], e1, ctx)
    return jnp.stack([c0, c1], axis=-2)


@functools.partial(jax.jit, static_argnames=("ctx", "token"))
def _encrypt_graph(ctx: CkksContext, token, pk0_mont, pk1_mont, m_coeff, key):
    return _encrypt_body(ctx, pk0_mont, pk1_mont, m_coeff, key)


@functools.partial(jax.jit, static_argnames=("ctx", "token"))
def _encrypt_values_graph(ctx: CkksContext, token, pk0_mont, pk1_mont,
                          values, key):
    """Encode (length-2N FFT) + encrypt as ONE jitted dispatch: a client
    update goes weights -> ciphertext without leaving the graph."""
    return _encrypt_body(ctx, pk0_mont, pk1_mont,
                         encoding.encode_jnp(values, ctx), key)


def encrypt_coeffs(ctx: CkksContext, pk: dict, m_coeff, key,
                   scale: float | None = None) -> Ciphertext:
    """Public-key encryption of pre-encoded residues.

    Args:
        ctx: CkksContext.
        pk: {"pk0_mont", "pk1_mont": u32[L, N]} public key (Montgomery,
            NTT domain).
        m_coeff: u32[B, L, N] coefficient-domain residues (from encode).
        key: jax PRNG key for the (u, e0, e1) draws.
        scale: encoding scale of m_coeff (default ctx.delta).

    Returns:
        Ciphertext with data u32[B, L, 2, N]; sampling, NTTs and the two
        mul_adds run as one jitted graph.
    """
    scale = float(scale if scale is not None else ctx.delta)
    data = _encrypt_graph(ctx, ops.backend_token(), pk["pk0_mont"],
                          pk["pk1_mont"], m_coeff, key)
    return Ciphertext(data=data, scale=scale)


def encrypt_values(ctx: CkksContext, pk: dict, values, key) -> Ciphertext:
    """values: f32[B, slots] -> fresh ciphertext.

    The canonical-embedding encode FFT is folded into the same jitted
    graph as the encrypt sampling/NTTs — one dispatch end to end.
    """
    data = _encrypt_values_graph(ctx, ops.backend_token(), pk["pk0_mont"],
                                 pk["pk1_mont"], values, key)
    return Ciphertext(data=data, scale=float(ctx.delta))


def expand_a_rows(ctx: CkksContext, a_seed: int, start: int, count: int,
                  derive: int = DERIVE_FOLD_CHUNK):
    """Deterministic uniform `a` rows [start, start+count) from a public seed.

    Row i is expanded from derive_chunk_keys(PRNGKey(a_seed), ...)[i] —
    the wire-negotiated derive algorithm — so a receiver can regenerate any
    single chunk independently (streaming ingest never needs the whole
    batch).  Returns u32[count, L, N] in NTT domain (uniform residues are
    uniform in either domain; both sides just agree on this convention,
    matching keygen's treatment of `a`).
    """
    base = jax.random.PRNGKey(int(a_seed))
    return _expand_a_graph(ctx, base, start, int(count), int(derive))


@functools.partial(jax.jit, static_argnames=("ctx", "count", "derive"))
def _expand_a_graph(ctx: CkksContext, base, start, count: int,
                    derive: int):
    """One dispatch per expansion — a streaming server calls this once per
    arriving chunk.  `start` is traced, so every chunk of an update shares
    one compiled graph."""
    keys = derive_chunk_keys(base, start, count, derive)
    return jax.vmap(
        lambda k: _uniform_residues(k, (ctx.n_poly,), ctx.tables.qs))(keys)
    # [count, L, N]


def expand_a(ctx: CkksContext, a_seed: int, batch: int,
             derive: int = DERIVE_FOLD_CHUNK):
    """Full-batch `a` expansion (rows 0..batch-1)."""
    return expand_a_rows(ctx, a_seed, 0, batch, derive)


def encrypt_coeffs_seeded(ctx: CkksContext, sk: dict, m_coeff, key,
                          a_seed: int, scale: float | None = None,
                          derive: int = DERIVE_FOLD_CHUNK) -> Ciphertext:
    """Secret-key encryption with seed-expandable c1 (uplink compression).

    ct = (c0, c1) with c1 = a = PRG(a_seed) and c0 = -(a s) + e + m, so the
    wire only needs (a_seed, c0) — half the fresh-ciphertext bytes.  Chunk
    b's c1 row expands per the wire-v2 `derive` algorithm (the registry
    above; DESIGN.md §9.2), matched bit for bit by expand_a_rows and by the
    sharded client.  The decryption identity c0 + c1 s = m + e matches the
    public-key path, so seeded and pk ciphertexts mix freely under the
    homomorphic ops.  `a_seed` must be unique per (client, round); reuse
    leaks m1 - m2.
    """
    scale = float(scale if scale is not None else ctx.delta)
    # PRNGKey is built host-side: a_seed is 64-bit on the wire, and the key
    # must match the server-side expand_a_rows stream exactly
    a_base = jax.random.PRNGKey(int(a_seed))
    data = _encrypt_seeded_graph(ctx, ops.backend_token(), sk["s_mont"],
                                 m_coeff, key, a_base, int(derive))
    return Ciphertext(data=data, scale=scale)


@functools.partial(jax.jit, static_argnames=("ctx", "token", "derive"))
def _encrypt_seeded_graph(ctx: CkksContext, token, s_mont, m_coeff, key,
                          a_base, derive: int = DERIVE_FOLD_CHUNK):
    return _seeded_body_from_coeffs(ctx, s_mont, m_coeff, key, a_base,
                                    derive=derive)


def _seeded_body_from_coeffs(ctx, s_mont, m_coeff, key, a_base,
                             chunk_start: int = 0,
                             derive: int = DERIVE_FOLD_CHUNK):
    """Shared trace of the seeded secret-key encrypt graph.

    Both streams are per-chunk (wire-v2 derivation, DESIGN.md §9):
      c1 chunk i = uniform from derive_chunk_keys(a_base, ...)[i] — public,
          matches the server-side expand_a_rows regeneration for the SAME
          derive id;
      e  chunk i = gaussian from fold_in(key, i)    — secret noise, one
          (N,) draw per chunk so the stream is chunk-shard-invariant (the
          noise stream never crosses the wire, so it is not derive-
          negotiated).
    """
    b = m_coeff.shape[0]
    n = ctx.n_poly
    qs = ctx.tables.qs
    sigma = ctx.error_sigma
    m = ops.ntt_fwd(m_coeff, ctx)
    a = jax.vmap(lambda k: _uniform_residues(k, (n,), qs))(
        derive_chunk_keys(a_base, chunk_start, b, derive))   # [B, L, N]
    e = ops.ntt_fwd(jax.vmap(
        lambda k: _gaussian_residues(k, (n,), qs, sigma))(
            _chunk_keys(key, chunk_start, b)), ctx)
    a_s = ops.mont_mul(a, s_mont[None], ctx)
    c0 = ops.mod_add(ops.mod_neg(a_s, ctx), ops.mod_add(e, m, ctx), ctx)
    return jnp.stack([c0, a], axis=-2)


@functools.partial(jax.jit, static_argnames=("ctx", "token", "derive"))
def _encrypt_seeded_values_graph(ctx: CkksContext, token, s_mont, values,
                                 key, a_base,
                                 derive: int = DERIVE_FOLD_CHUNK):
    return _seeded_body_from_coeffs(ctx, s_mont,
                                    encoding.encode_jnp(values, ctx), key,
                                    a_base, derive=derive)


def encrypt_values_seeded(ctx: CkksContext, sk: dict, values, key,
                          a_seed: int,
                          derive: int = DERIVE_FOLD_CHUNK) -> Ciphertext:
    """f32[B, slots] -> seeded secret-key ciphertext in ONE dispatch.

    Same wire convention as encrypt_coeffs_seeded (c1 = PRG(a_seed),
    per-chunk expansion by the negotiated `derive` id); the encode FFT runs
    inside the jitted graph.  ShardedHe.encrypt_values_seeded is the
    multi-chip version and produces identical bits.
    """
    a_base = jax.random.PRNGKey(int(a_seed))
    data = _encrypt_seeded_values_graph(ctx, ops.backend_token(),
                                        sk["s_mont"], values, key, a_base,
                                        int(derive))
    return Ciphertext(data=data, scale=float(ctx.delta))


def drop_limbs(ctx: CkksContext, ct: Ciphertext, keep: int) -> Ciphertext:
    """Rescale away trailing RNS limbs until only `keep` remain.

    Lossy downlink compression: each dropped limb divides the scale by that
    limb's prime, trading ~log2(q) bits of plaintext precision for a
    (L-keep)/L cut in ciphertext bytes.  decode must go through the
    any-limb-count np path when keep < 2.
    """
    assert 1 <= keep <= ct.n_limbs
    while ct.n_limbs > keep:
        ct = rescale(ctx, ct)
    return ct


@functools.partial(jax.jit, static_argnames=("ctx", "token"))
def _decrypt_graph(ctx: CkksContext, token, s_mont, data):
    c0 = data[..., 0, :]
    c1 = data[..., 1, :]
    phase = ops.mul_add(c1, s_mont[None], c0, ctx)
    return ops.ntt_inv(phase, ctx)


def decrypt_to_coeffs(ctx: CkksContext, sk: dict, ct: Ciphertext):
    """-> u32[B, L, N] coefficient-domain residues of m + noise — one jitted
    graph.  Handles rescaled ciphertexts (fewer limbs than the context)."""
    s = sk["s_mont"][: ct.n_limbs]
    return _decrypt_graph(ctx, ops.backend_token(), s, ct.data)


def decrypt_values(ctx: CkksContext, sk: dict, ct: Ciphertext):
    """-> f32[B, slots] (jnp decode path, 2-limb)."""
    return encoding.decode_jnp(decrypt_to_coeffs(ctx, sk, ct), ctx, ct.scale)


def decrypt_values_np(ctx: CkksContext, sk: dict, ct: Ciphertext) -> np.ndarray:
    """High-precision host decode (any limb count)."""
    coeffs = np.asarray(decrypt_to_coeffs(ctx, sk, ct))
    return encoding.decode_np(coeffs, ctx, ct.scale)


# ---------------------------------------------------------------------------
# homomorphic ops
# ---------------------------------------------------------------------------

def _limbs_to_minus2(data):
    """[..., L, 2, N] -> [..., 2, L, N]: ops.* helpers broadcast per-limb
    constants over axis -2, so the limb axis must sit there."""
    return jnp.moveaxis(data, -3, -2)


def _limbs_to_minus3(data):
    return jnp.moveaxis(data, -2, -3)


def add(ctx: CkksContext, a: Ciphertext, b: Ciphertext) -> Ciphertext:
    assert abs(a.scale - b.scale) < 1e-6 * a.scale
    out = ops.mod_add(_limbs_to_minus2(a.data), _limbs_to_minus2(b.data), ctx)
    return Ciphertext(data=_limbs_to_minus3(out), scale=a.scale)


def mul_plain_scalar(ctx: CkksContext, ct: Ciphertext, w: float) -> Ciphertext:
    """ct x plaintext scalar (encoded at delta): one multiplicative depth."""
    w_mont = encoding.encode_scalar_residues(w, ctx)   # u32[L]
    wb = jnp.asarray(w_mont)[:, None]                  # [L, N->bcast]
    out = ops.mont_mul(_limbs_to_minus2(ct.data), wb, ctx)
    return Ciphertext(data=_limbs_to_minus3(out), scale=ct.scale * ctx.delta)


def mul_plain_vec(ctx: CkksContext, ct: Ciphertext, pt_mont) -> Ciphertext:
    """ct x plaintext vector; pt_mont: u32[L, N] NTT-domain Montgomery."""
    out = ops.mont_mul(_limbs_to_minus2(ct.data), pt_mont, ctx)
    return Ciphertext(data=_limbs_to_minus3(out), scale=ct.scale * ctx.delta)


@functools.partial(jax.jit, static_argnames=("ctx", "token"))
def _weighted_sum_graph(ctx: CkksContext, token, data, w_mont):
    # fold the (c0,c1) component axis into batch: [C, ..., L, 2, N] ->
    # [C, ..., 2, L, N] so the kernel sees limbs at axis -2.
    x = jnp.moveaxis(data, -3, -2)
    out = ops.weighted_sum(x, w_mont, ctx)
    return jnp.moveaxis(out, -2, -3)


def weighted_sum(ctx: CkksContext, cts: Ciphertext, weights) -> Ciphertext:
    """Fused FedAvg aggregation: sum_i w_i * ct_i over the leading axis.

    cts.data: u32[C, ..., L, 2, N]; weights: python floats len C.
    One jitted graph over the fused kernel (single pass over client
    ciphertexts, all limbs in one launch).
    """
    w_mont = encoding.encode_weights_mont(weights, ctx)          # [C, L]
    data = _weighted_sum_graph(ctx, ops.backend_token(), cts.data,
                               jnp.asarray(w_mont))
    return Ciphertext(data=data, scale=cts.scale * ctx.delta)


def rescale(ctx: CkksContext, ct: Ciphertext) -> Ciphertext:
    """Drop the last RNS limb: c'_j = (c_j - lift(c_last)) * q_last^{-1} mod q_j.

    Needs a domain switch for the last limb (iNTT under q_last, re-NTT under
    each remaining q_j) because NTT evaluation points differ per prime.  The
    remaining-limb axis is vectorized via the fused engine — the per-limb
    lift constants are u32[L-1] host tables broadcast into the graph.
    """
    l = ct.n_limbs
    assert l >= 2
    q_last = ctx.primes[l - 1]
    lc_last = ctx.limbs[l - 1]
    t = ctx.tables.take(l - 1)
    # last limb to coefficient domain (exact)
    c_last_ntt = ct.data[..., l - 1, :, :]
    flat = c_last_ntt.reshape((-1, ctx.n_poly))
    c_last = _ref.ntt_inv(flat, jnp.asarray(lc_last.psi_inv_rev_mont),
                          np.asarray(lc_last.n_inv_mont),
                          np.uint32(q_last), np.uint32(lc_last.qinv_neg))
    # centered lift of v in [0, q_last) into each Z_qj: primes are within 2x
    # of each other, so v mod qj needs at most one conditional subtract.
    qjs = t.qs[:, None]                                         # [L-1, 1]
    v = c_last[..., None, :]                                    # [B, 1, N]
    need_sub = (np.uint32(q_last) > t.qs)[:, None]              # [L-1, 1]
    v_mod = jnp.where(need_sub & (v >= qjs), v - qjs, v)
    half = np.uint32(q_last // 2)
    q_last_mod = (np.uint32(q_last) % t.qs)[:, None]            # [L-1, 1]
    lifted = jnp.where(jnp.broadcast_to(v > half, v_mod.shape),
                       ops.mod_sub(v_mod, q_last_mod, ctx), v_mod)
    lifted_ntt = ops.ntt_fwd(lifted, ctx)                       # [B, L-1, N]
    cj = jnp.moveaxis(ct.data[..., : l - 1, :, :], -3, -2)      # [..., 2, L-1, N]
    cj = cj.reshape((-1, l - 1, ctx.n_poly))
    diff = ops.mod_sub(cj, lifted_ntt, ctx)
    inv_mont = np.asarray([pow(q_last, -1, int(qj)) * (1 << 32) % int(qj)
                           for qj in t.qs], dtype=np.uint32)[:, None]
    out = ops.mont_mul(diff, inv_mont, ctx)
    data = jnp.moveaxis(
        out.reshape(ct.data.shape[:-3] + (2, l - 1, ctx.n_poly)), -2, -3)
    return Ciphertext(data=data, scale=ct.scale / q_last)
