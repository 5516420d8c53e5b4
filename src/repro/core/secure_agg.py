"""Algorithm 1: HE-based federated aggregation with Selective Parameter
Encryption.

Data flow per round (single-key setup; threshold variant in fl/orchestrator):

  client:  vec = flatten(W_i)
           enc, plain = split_by_mask(vec, partition)         # static indices
           ct_i = Enc(pk, encode(enc))                        # [n_chunks] cts
           (optional) plain += Laplace(b)
  server:  ct_glob   = sum_i alpha_i (*) ct_i   # limb-fused kernel, one
                                                # launch across all RNS limbs
           plain_glob = sum_i alpha_i * plain_i               # plaintext
  client:  enc_glob = decode(Dec(sk, ct_glob))
           W_glob = unflatten(merge(enc_glob, plain_glob))

The server never sees the masked (most attack-prone) parameters in
plaintext; weights alpha_i are plaintext by default (paper §2.3) costing the
single multiplicative depth.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp, packing, selection
from repro.core.ckks import cipher, encoding, transcipher
from repro.core.ckks.cipher import Ciphertext
from repro.core.ckks.params import CkksContext
from repro.core.packing import FlatSpec, MaskPartition


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ProtectedUpdate:
    """One client's outgoing update: encrypted chunks + plaintext rest."""

    ct: Ciphertext          # data u32[n_chunks, L, 2, N]
    plain: Any              # f32[n_plain]


@dataclasses.dataclass(frozen=True)
class AggregatorConfig:
    p_ratio: float = 0.1
    strategy: str = "top_p"      # top_p | random | per_layer | recipe | all | none
    dp_b: float = 0.0            # Laplace scale on plaintext part (0 = off)
    seed: int = 0


class SelectiveHEAggregator:
    """Stateful glue object owning (ctx, partition, flat spec)."""

    def __init__(self, ctx: CkksContext, spec: FlatSpec,
                 part: MaskPartition, cfg: AggregatorConfig):
        self.ctx = ctx
        self.spec = spec
        self.part = part
        self.cfg = cfg

    # -- construction -------------------------------------------------------

    @staticmethod
    def build(ctx: CkksContext, params, sens_vec: np.ndarray,
              cfg: AggregatorConfig) -> "SelectiveHEAggregator":
        spec = packing.make_flat_spec(params)
        mask = selection.build_mask(sens_vec, cfg.strategy, cfg.p_ratio,
                                    offsets=spec.offsets, sizes=spec.sizes,
                                    seed=cfg.seed)
        part = packing.make_partition(mask, ctx.slots)
        return SelectiveHEAggregator(ctx, spec, part, cfg)

    # -- client side ---------------------------------------------------------

    def client_protect(self, params, pk: dict, key,
                       sharded=None) -> ProtectedUpdate:
        vec, _ = packing.flatten_params(params)
        return self.client_protect_vec(vec, pk, key, sharded=sharded)

    def client_protect_vec(self, vec, pk: dict, key,
                           sharded=None) -> ProtectedUpdate:
        """Protect one flat update vector.

        With `sharded` (a core.ckks.sharded.ShardedHe), the encode FFT +
        encrypt run as one sharded dispatch over its mesh — ciphertext
        chunks along `data`, limbs along `model` — bit-identical to the
        single-device path (per-chunk key derivation, DESIGN.md §9).
        """
        enc_vals, plain = packing.split_by_mask(vec, self.part)
        k_enc, k_dp = cipher.split_key(key)
        # encode FFT + encrypt run as ONE jitted dispatch (weights ->
        # ciphertext without leaving the graph)
        if sharded is not None:
            ct = sharded.encrypt_values(pk, enc_vals, k_enc)
        else:
            ct = cipher.encrypt_values(self.ctx, pk, enc_vals, k_enc)
        if self.cfg.dp_b > 0:
            plain = dp.laplace_noise_vec(plain, k_dp, self.cfg.dp_b)
        return ProtectedUpdate(ct=ct, plain=plain)

    def client_protect_seeded(self, params, sk: dict, key, a_seed: int,
                              sharded=None,
                              derive: int = cipher.DERIVE_FOLD_CHUNK
                              ) -> ProtectedUpdate:
        """client_protect via the seeded secret-key encrypt path: c1 is
        PRG(a_seed), so the wire layer (repro.wire) can ship (seed, c0) and
        halve uplink ciphertext bytes.  `a_seed` must be unique per
        (client, round); `derive` picks the per-chunk seed-derivation id
        (cipher.DERIVES, DESIGN.md §9.2) the wire will advertise.

        With `sharded`, the whole weights -> seeded-ciphertext graph is one
        multi-chip dispatch (ShardedHe.encrypt_values_seeded) producing the
        same bits as the single-device path — the uplink counterpart of the
        server's sharded aggregation."""
        vec, _ = packing.flatten_params(params)
        enc_vals, plain = packing.split_by_mask(vec, self.part)
        k_enc, k_dp = cipher.split_key(key)
        if sharded is not None:
            ct = sharded.encrypt_values_seeded(sk, enc_vals, k_enc, a_seed,
                                               derive=derive)
        else:
            ct = cipher.encrypt_values_seeded(self.ctx, sk, enc_vals, k_enc,
                                              a_seed, derive=derive)
        if self.cfg.dp_b > 0:
            plain = dp.laplace_noise_vec(plain, k_dp, self.cfg.dp_b)
        return ProtectedUpdate(ct=ct, plain=plain)

    def client_protect_transcipher(self, params,
                                   cm: transcipher.ClientMaterials,
                                   key) -> tuple[np.ndarray, Any]:
        """Thin-client protect: mask the encrypted partition with the
        provisioned keystream — no NTT, no RNS arithmetic on the client
        (core/ckks/transcipher.py, DESIGN.md §15).

        Returns (masked u32[n_chunks, N], plain); the wire layer frames
        them (stream.pack_masked_update_frames) together with the escrow
        seed ciphertext from `cm`.  `key` is split exactly like
        client_protect_seeded's so an enabled dp_b adds the SAME plaintext
        noise as the seeded path under the same key — the transcipher
        round stays bit-comparable end to end."""
        vec, _ = packing.flatten_params(params)
        enc_vals, plain = packing.split_by_mask(vec, self.part)
        _, k_dp = cipher.split_key(key)
        masked = transcipher.mask_values(self.ctx, cm,
                                         np.asarray(enc_vals))
        if self.cfg.dp_b > 0:
            plain = dp.laplace_noise_vec(plain, k_dp, self.cfg.dp_b)
        return masked, plain

    def client_recover(self, agg: ProtectedUpdate, sk: dict):
        """Decrypt + merge -> flat global vector."""
        if agg.ct.n_limbs == 2:
            enc = cipher.decrypt_values(self.ctx, sk, agg.ct)
        else:
            # limb-dropped downlink (repro.wire.compress.limb_drop): the jnp
            # decode path is 2-limb only, fall back to the any-count host path
            enc = jnp.asarray(cipher.decrypt_values_np(self.ctx, sk, agg.ct))
        return packing.merge_by_mask(enc, agg.plain, self.part)

    def client_recover_params(self, agg: ProtectedUpdate, sk: dict):
        return packing.unflatten_params(self.client_recover(agg, sk), self.spec)

    # -- server side ---------------------------------------------------------

    def server_aggregate(self, updates: Sequence[ProtectedUpdate],
                         weights: Sequence[float],
                         sharded=None) -> ProtectedUpdate:
        """sum_i alpha_i [[enc_i]]  +  sum_i alpha_i plain_i.

        Args:
            updates: one ProtectedUpdate per received client.
            weights: FedAvg weights alpha_i (python floats).
            sharded: optional core.ckks.sharded.ShardedHe; when given the
                HE aggregation runs sharded over its mesh (ciphertext
                chunks -> data axis, RNS limbs -> model axis) with
                bit-identical results to the single-device path.

        Returns:
            The aggregated ProtectedUpdate (ct scale = in_scale * delta).
        """
        cts = Ciphertext(
            data=jnp.stack([u.ct.data for u in updates]),
            scale=updates[0].ct.scale)
        if sharded is not None:
            ct_glob = sharded.weighted_sum(cts, list(weights))
        else:
            ct_glob = cipher.weighted_sum(self.ctx, cts, list(weights))
        w = jnp.asarray(np.asarray(weights, dtype=np.float32))
        plain_glob = jnp.einsum("c,cp->p",
                                w, jnp.stack([u.plain for u in updates]))
        return ProtectedUpdate(ct=ct_glob, plain=plain_glob)

    # -- reporting (paper's overhead tables) ---------------------------------

    def overhead_report(self) -> dict:
        part = self.part
        ct_bytes = self.ctx.encrypted_bytes(part.n_enc)
        pt_bytes = self.ctx.plaintext_bytes(part.n_plain)
        return {
            "n_total": part.n_total,
            "n_enc": part.n_enc,
            "ratio": part.ratio,
            "n_ciphertexts": part.n_chunks,
            "bytes_encrypted": ct_bytes,
            "bytes_plain": pt_bytes,
            "bytes_total": ct_bytes + pt_bytes,
            "bytes_all_plain": self.ctx.plaintext_bytes(part.n_total),
            "comm_ratio": (ct_bytes + pt_bytes)
                          / max(1, self.ctx.plaintext_bytes(part.n_total)),
        }


# ---------------------------------------------------------------------------
# encryption-mask agreement (paper §2.4 Step 2, Figure 4)
# ---------------------------------------------------------------------------


def agree_sensitivity(ctx: CkksContext, pk: dict, sk: dict,
                      local_sens_vecs: Sequence[np.ndarray],
                      weights: Sequence[float], key) -> np.ndarray:
    """HE-aggregate the clients' local sensitivity maps -> global map.

    Each client encrypts its map under pk; the server weighted-sums the
    ciphertexts (never seeing an individual map in the clear); the decrypted
    aggregate is the shared global sensitivity every client thresholds into
    the public mask (build_mask / agree_mask).
    """
    n = int(local_sens_vecs[0].size)
    slots = ctx.slots
    n_chunks = -(-n // slots)
    cts = []
    for i, s in enumerate(local_sens_vecs):
        buf = np.zeros(n_chunks * slots, dtype=np.float32)
        buf[:n] = np.asarray(s, dtype=np.float32).ravel()
        coeffs = jnp.asarray(encoding.encode_np(
            buf.reshape(n_chunks, slots), ctx))
        cts.append(cipher.encrypt_coeffs(ctx, pk, coeffs,
                                         jax.random.fold_in(key, i)))
    stacked = Ciphertext(data=jnp.stack([c.data for c in cts]),
                         scale=cts[0].scale)
    agg = cipher.weighted_sum(ctx, stacked, list(weights))
    return cipher.decrypt_values_np(ctx, sk, agg).ravel()[:n]


def agree_mask(ctx: CkksContext, pk: dict, sk: dict,
               local_sens_vecs: Sequence[np.ndarray],
               weights: Sequence[float], p: float, key, *,
               strategy: str = "top_p", offsets=None, sizes=None,
               seed: int = 0) -> np.ndarray:
    """Clients encrypt local sensitivity maps; server HE-aggregates them;
    clients decrypt the aggregate and derive the selection mask.

    `strategy` picks the selector applied to the decrypted aggregate
    (selection.build_mask): the global `top_p` default, `per_layer`, or
    the paper's `recipe` (top-p UNION first/last leaves) — the layer-aware
    strategies need `offsets`/`sizes` from the model's FlatSpec.

    (Algorithm 1 writes Select() over the ciphertext; comparisons are not
    CKKS-evaluable, so — as the paper's own implementation must — the
    decrypted aggregate is thresholded client-side and M becomes public FL
    configuration.  Documented in DESIGN.md §5 and §13.)
    """
    s_glob = agree_sensitivity(ctx, pk, sk, local_sens_vecs, weights, key)
    return selection.build_mask(s_glob, strategy, p, offsets=offsets,
                                sizes=sizes, seed=seed)
