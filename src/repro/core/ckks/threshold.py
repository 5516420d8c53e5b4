"""Threshold CKKS key management (paper §2.2 / Appendix B).

Two variants:
  * additive n-of-n — each party i holds s_i with s = sum_i s_i; joint pk is
    generated interactively from a common random `a` (b_i = -(a s_i) + e_i);
    decryption combines per-party partial decryptions d_i = c1*s_i + e_smudge.
  * Shamir t-of-n — coefficients of s are secret-shared over each limb field;
    any t parties reconstruct via Lagrange coefficients folded into their
    partial decryptions.

Smudging noise (sigma_smudge >> sigma_err) statistically hides each party's
share in its partial decryption, matching the standard threshold-HE argument
(Asharov et al., 2012).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ckks import cipher
from repro.core.ckks.cipher import Ciphertext
from repro.core.ckks.params import CkksContext
from repro.kernels import ops, ref as _ref

DEFAULT_SMUDGE_SIGMA = 2.0 ** 12


# ---------------------------------------------------------------------------
# additive n-of-n
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ThresholdParty:
    index: int
    s_mont: object   # u32[L, N] NTT-domain Montgomery share


def threshold_keygen(ctx: CkksContext, key, n_parties: int
                     ) -> tuple[list[ThresholdParty], dict]:
    """Interactive additive keygen. Returns (parties, joint pk)."""
    n = ctx.n_poly
    k_a, k_rest = cipher.split_key(key)
    a = cipher._uniform_residues(k_a, (n,), ctx.tables.qs)      # common reference poly
    a_mont = ops.to_mont(a, ctx)
    parties = []
    b_sum = None
    for i in range(n_parties):
        k_s, k_e = cipher.split_key(jax.random.fold_in(k_rest, i))
        s_i = ops.ntt_fwd(cipher._ternary_residues(k_s, (n,), ctx.tables.qs), ctx)
        s_i_mont = ops.to_mont(s_i, ctx)
        e_i = ops.ntt_fwd(cipher._gaussian_residues(k_e, (n,), ctx.tables.qs, ctx.error_sigma), ctx)
        b_i = ops.mod_add(ops.mod_neg(ops.mont_mul(a, s_i_mont, ctx), ctx),
                          e_i, ctx)
        b_sum = b_i if b_sum is None else ops.mod_add(b_sum, b_i, ctx)
        parties.append(ThresholdParty(index=i, s_mont=s_i_mont))
    pk = {"pk0_mont": ops.to_mont(b_sum, ctx), "pk1_mont": a_mont}
    return parties, pk


def partial_decrypt(ctx: CkksContext, party: ThresholdParty, ct: Ciphertext,
                    key, smudge_sigma: float = DEFAULT_SMUDGE_SIGMA):
    """d_i = c1 (*) s_i + e_smudge  (NTT domain)."""
    b = ct.data.shape[0]
    e = ops.ntt_fwd(
        cipher._gaussian_residues(key, (b, ctx.n_poly), ctx.tables.qs, smudge_sigma),
        ctx)
    return ops.mul_add(ct.c1, party.s_mont[None], e, ctx)


def combine_partials(ctx: CkksContext, ct: Ciphertext, partials: list):
    """m~ = c0 + sum_i d_i -> coefficient-domain residues."""
    acc = ct.c0
    for d in partials:
        acc = ops.mod_add(acc, d, ctx)
    return ops.ntt_inv(acc, ctx)


# ---------------------------------------------------------------------------
# Shamir t-of-n
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShamirParty:
    index: int          # evaluation point x = index + 1
    share: object       # u32[L, N] NTT-domain share of s (normal form)


def shamir_share_secret(ctx: CkksContext, sk: dict, key, n_parties: int,
                        threshold: int) -> list[ShamirParty]:
    """Split sk into Shamir shares over each limb field."""
    s = ops.from_mont(sk["s_mont"], ctx)     # [L, N] normal form
    coeff_keys = cipher.split_key(key, threshold - 1)
    coeffs = [cipher._uniform_residues(k, (ctx.n_poly,), ctx.tables.qs)
              for k in coeff_keys]           # each [L, N]
    parties = []
    for i in range(n_parties):
        x = i + 1
        acc = s
        x_pow_mont = [jnp.asarray(
            np.asarray([pow(x, k + 1, q) * (1 << 32) % q for q in ctx.primes],
                       dtype=np.uint32))[:, None] for k in range(threshold - 1)]
        for k, c in enumerate(coeffs):
            acc = ops.mod_add(acc, ops.mont_mul(c, x_pow_mont[k], ctx), ctx)
        parties.append(ShamirParty(index=i, share=acc))
    return parties


def _lagrange_at_zero(indices: list[int], q: int) -> list[int]:
    """lambda_j = prod_{m != j} x_m / (x_m - x_j) mod q (x = index+1)."""
    lams = []
    xs = [i + 1 for i in indices]
    for j, xj in enumerate(xs):
        num, den = 1, 1
        for m, xm in enumerate(xs):
            if m == j:
                continue
            num = num * xm % q
            den = den * ((xm - xj) % q) % q
        lams.append(num * pow(den, -1, q) % q)
    return lams


def shamir_partial_decrypt(ctx: CkksContext, party: ShamirParty,
                           active_indices: list[int], ct: Ciphertext, key,
                           smudge_sigma: float = DEFAULT_SMUDGE_SIGMA):
    """d_j = c1 (*) (lambda_j * share_j) + e_smudge for the active subset."""
    pos = active_indices.index(party.index)
    lam_mont = jnp.asarray(np.asarray(
        [_lagrange_at_zero(active_indices, q)[pos] * (1 << 32) % q
         for q in ctx.primes], dtype=np.uint32))[:, None]
    lam_share = ops.mont_mul(party.share, lam_mont, ctx)      # normal form
    lam_share_mont = ops.to_mont(lam_share, ctx)
    b = ct.data.shape[0]
    e = ops.ntt_fwd(
        cipher._gaussian_residues(key, (b, ctx.n_poly), ctx.tables.qs, smudge_sigma),
        ctx)
    return ops.mul_add(ct.c1, lam_share_mont[None], e, ctx)
