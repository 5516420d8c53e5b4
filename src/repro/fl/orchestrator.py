"""FL task orchestration: the full paper pipeline (Figure 3).

  stage 1  key agreement        (KeyAuthority | ThresholdKeyAuthority)
  stage 2  encryption-mask calc (clients' sensitivity maps, HE-aggregated)
  stage 3  encrypted rounds     (Algorithm 1) with:
             - client sampling per round
             - dropout simulation (clients fail mid-round; weights
               renormalize over survivors — no protocol restart)
             - straggler deadlines (simulated wall-clock per client)
             - elastic client pool (join/leave between rounds)
             - round-boundary checkpointing + resume
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.ckpt import CheckpointManager
from repro.core import packing, secure_agg
from repro.core.secure_agg import AggregatorConfig, SelectiveHEAggregator
from repro.fl.client import ClientConfig, FLClient
from repro.fl.keys import KeyAuthority, ThresholdKeyAuthority
from repro.fl.server import FLServer, ReceivedUpdate
from repro.models import Model
from repro.wire import budget as wire_budget
from repro.wire import compress as wire_compress
from repro.wire import format as wire_format
from repro.wire.compress import WirePolicy


@dataclasses.dataclass
class FLRunConfig:
    n_rounds: int = 5
    clients_per_round: int = 0          # 0 = all
    dropout_prob: float = 0.0           # per-client, per-round
    straggler_prob: float = 0.0         # client exceeds the deadline
    deadline_s: float = float("inf")    # simulated round deadline
    threshold_mode: bool = False        # threshold HE decryption
    threshold_t: int = 0                # parties needed (0 = all)
    ckpt_dir: str | None = None
    ckpt_every: int = 1
    seed: int = 0
    # repro.wire transport: None keeps the legacy in-memory hand-off (comm
    # bytes estimated); a WirePolicy serializes every update, streams it
    # through the O(1)-memory server ingest, and logs measured bytes.
    wire_policy: WirePolicy | None = None


@dataclasses.dataclass
class RoundLog:
    round: int
    loss: float
    n_participating: int
    n_dropped: int
    comm_bytes: int
    wall_s: float
    comm_up_bytes: int = 0      # measured uplink (wire mode only)
    comm_down_bytes: int = 0    # measured downlink (wire mode only)
    comm_measured: bool = False  # True = bytes-on-wire, False = estimate


class FLTask:
    """Owns (model, clients, server, keys) and runs the 3-stage pipeline."""

    def __init__(self, model: Model, clients: list[FLClient],
                 agg_cfg: AggregatorConfig, run_cfg: FLRunConfig,
                 ctx=None):
        self.model = model
        self.clients = clients
        self.agg_cfg = agg_cfg
        self.run_cfg = run_cfg
        self.rng = np.random.RandomState(run_cfg.seed)

        # stage 1 — key agreement
        if run_cfg.threshold_mode:
            self.authority = ThresholdKeyAuthority(
                n_parties=len(clients), ctx=ctx, seed=run_cfg.seed)
            self.pk = self.authority.public_key()
            self.sk = None
        else:
            self.authority = KeyAuthority(ctx=ctx, seed=run_cfg.seed)
            self.pk, self.sk = self.authority.client_keys()
        self.ctx = self.authority.ctx

        self.global_params = model.init(jax.random.PRNGKey(run_cfg.seed))
        # base for per-(round, client) encryption keys, distinct from the
        # model-init stream
        self._round_key_base = jax.random.fold_in(
            jax.random.PRNGKey(run_cfg.seed), 0x5EC)
        self.server: FLServer | None = None
        self.aggregator: SelectiveHEAggregator | None = None
        # the task owns round accounting: always (re)attach its ledger, so
        # clients reused from a previous FLTask record into THIS task's
        # ledger rather than the old one
        self.ledger = wire_budget.BandwidthLedger()
        for c in clients:
            c.ledger = self.ledger
        self.logs: list[RoundLog] = []
        self._ckpt = (CheckpointManager(run_cfg.ckpt_dir)
                      if run_cfg.ckpt_dir else None)
        self._start_round = 0

    # -- stage 2: encryption-mask agreement -----------------------------------

    def agree_encryption_mask(self):
        spec = packing.make_flat_spec(self.global_params)
        if self.agg_cfg.strategy in ("all", "none", "random"):
            # sensitivity-free strategies: no map exchange needed
            sens = np.zeros(spec.total)
            self.aggregator = SelectiveHEAggregator.build(
                self.ctx, self.global_params, sens, self.agg_cfg)
        else:
            # sensitivity-driven strategies (top_p / per_layer / recipe):
            # HE-aggregate the clients' local maps, then apply the
            # configured selector to the decrypted aggregate
            sens_maps = [c.sensitivity_map(self.global_params)
                         for c in self.clients]
            weights = [1.0 / len(sens_maps)] * len(sens_maps)
            if self.run_cfg.threshold_mode:
                # threshold path: aggregate in the clear between clients
                # (maps are lower-sensitivity than weights; microbenchmarked
                # HE path is exercised in single-key mode)
                from repro.core import selection
                glob = sum(w * s for w, s in zip(weights, sens_maps))
                mask = selection.build_mask(
                    glob, self.agg_cfg.strategy, self.agg_cfg.p_ratio,
                    offsets=spec.offsets, sizes=spec.sizes,
                    seed=self.agg_cfg.seed)
            else:
                mask = secure_agg.agree_mask(
                    self.ctx, self.pk, self.sk, sens_maps, weights,
                    self.agg_cfg.p_ratio, jax.random.PRNGKey(7),
                    strategy=self.agg_cfg.strategy, offsets=spec.offsets,
                    sizes=spec.sizes, seed=self.agg_cfg.seed)
            part = packing.make_partition(mask, self.ctx.slots)
            self.aggregator = SelectiveHEAggregator(
                self.ctx, spec, part, self.agg_cfg)
        self.server = FLServer(self.aggregator, ledger=self.ledger)
        return self.aggregator

    # -- resume ----------------------------------------------------------------

    def maybe_resume(self):
        if self._ckpt is None:
            return
        tree, step, _ = self._ckpt.restore(self.global_params)
        if tree is not None:
            self.global_params = jax.tree_util.tree_map(jnp.asarray, tree)
            self._start_round = step + 1

    # -- stage 3: encrypted federated rounds ------------------------------------

    def run_round(self, rnd: int) -> RoundLog:
        with obs.span("round", round=rnd) as sp:
            log = self._run_round(rnd, sp)
            sp.set(loss=log.loss, n_participating=log.n_participating,
                   n_dropped=log.n_dropped, bytes_up=log.comm_up_bytes,
                   bytes_down=log.comm_down_bytes, wall_s=log.wall_s)
        return log

    def _run_round(self, rnd: int, sp) -> RoundLog:
        # perf_counter: monotonic, immune to wall-clock steps; RoundLog
        # wall_s is a duration, not a timestamp
        t0 = time.perf_counter()
        cfg = self.run_cfg
        n = len(self.clients)
        k = cfg.clients_per_round or n
        chosen = self.rng.choice(n, size=min(k, n), replace=False)

        use_wire = cfg.wire_policy is not None
        received, dropped = [], 0
        wire_blobs, wire_clients = [], []
        losses = []
        for ci in chosen:
            client = self.clients[ci]
            if self.rng.rand() < cfg.dropout_prob:
                dropped += 1
                continue                      # client crashed mid-round
            with obs.span("client", cid=int(ci)):
                local_params, loss = client.local_train(self.global_params)
                simulated_s = self.rng.exponential(1.0)
                if self.rng.rand() < cfg.straggler_prob:
                    simulated_s += cfg.deadline_s   # guaranteed late
                if simulated_s > cfg.deadline_s:
                    dropped += 1
                    continue                  # straggler cut at the deadline
                losses.append(loss)
                # collision-free per-(round, client) stream: fold_in is
                # injective per step, unlike the old PRNGKey(rnd * 1000 + ci)
                # arithmetic which collides once client indices reach the
                # round stride
                key = jax.random.fold_in(
                    jax.random.fold_in(self._round_key_base, rnd), int(ci))
                if use_wire:
                    blob = client.protect_and_pack(
                        self.aggregator, local_params, rnd=rnd,
                        policy=cfg.wire_policy, pk=self.pk,
                        sk=None if cfg.threshold_mode else self.sk, key=key)
                    wire_blobs.append(blob)
                    wire_clients.append(client)
                else:
                    upd = self.aggregator.client_protect(local_params,
                                                         self.pk, key)
                    received.append(ReceivedUpdate(
                        cid=int(ci), update=upd,
                        n_samples=max(1, client.n_samples), round_sent=rnd))
                # the next client trains beside this one's model otherwise
                del local_params
        if not received and not wire_blobs:
            # total dropout: keep the old global model, log and move on
            return RoundLog(rnd, float("nan"), 0, dropped, 0,
                            time.perf_counter() - t0)
        if use_wire:
            agg, n_recv = self._wire_round(rnd, wire_blobs, wire_clients)
            with obs.span("recover"):
                self.global_params = obs.maybe_block(self._recover(agg))
            up = self.ledger.total(wire_budget.UPLINK, rnd)
            down = self.ledger.total(wire_budget.DOWNLINK, rnd)
            log = RoundLog(rnd, float(np.mean(losses)), n_recv, dropped,
                           up + down, time.perf_counter() - t0,
                           comm_up_bytes=up, comm_down_bytes=down,
                           comm_measured=True)
        else:
            with obs.span("aggregate", n_updates=len(received)):
                agg = self.server.aggregate_sync(received)
            with obs.span("recover"):
                self.global_params = obs.maybe_block(self._recover(agg))
            rep = self.aggregator.overhead_report()
            comm = (rep["bytes_total"]) * len(received)
            log = RoundLog(rnd, float(np.mean(losses)), len(received),
                           dropped, comm, time.perf_counter() - t0)
        self.logs.append(log)
        if self._ckpt is not None and (rnd + 1) % cfg.ckpt_every == 0:
            with obs.span("checkpoint", round=rnd):
                self._ckpt.save(rnd, self.global_params,
                                extra={"loss": log.loss})
        return log

    def _wire_round(self, rnd, wire_blobs, wire_clients):
        """Serialized transport: stream blobs through the O(1) server
        ingest, apply the downlink policy, broadcast, deserialize."""
        policy = self.run_cfg.wire_policy
        with obs.span("aggregate", n_updates=len(wire_blobs)):
            agg = self.server.aggregate_wire(wire_blobs)
        with obs.span("broadcast", n_clients=len(wire_clients)):
            keep = policy.downlink_keep_limbs
            if (keep and keep < agg.ct.n_limbs
                    and not self.run_cfg.threshold_mode):
                agg = secure_agg.ProtectedUpdate(
                    ct=wire_compress.limb_drop(self.ctx, agg.ct, keep),
                    plain=agg.plain)
            blob_down = wire_format.serialize_update(agg)
            out = None
            for client in wire_clients:
                out = client.receive_global(blob_down, self.ctx, rnd=rnd)
        return out, len(wire_clients)

    def _recover(self, agg):
        if self.run_cfg.threshold_mode:
            t = self.run_cfg.threshold_t or len(self.clients)
            partials = [self.authority.partial_decrypt(
                i, agg.ct, jax.random.PRNGKey(900 + i)) for i in range(t)]
            coeffs = self.authority.combine(agg.ct, partials)
            from repro.core.ckks import encoding
            enc = jnp.asarray(encoding.decode_np(
                np.asarray(coeffs), self.ctx, agg.ct.scale))
            vec = packing.merge_by_mask(enc, agg.plain, self.aggregator.part)
            return packing.unflatten_params(vec, self.aggregator.spec)
        return self.aggregator.client_recover_params(agg, self.sk)

    def run(self) -> list[RoundLog]:
        if self.aggregator is None:
            self.agree_encryption_mask()
        self.maybe_resume()
        for rnd in range(self._start_round, self.run_cfg.n_rounds):
            self.run_round(rnd)
        return self.logs

    # -- elasticity -------------------------------------------------------------

    def add_client(self, client: FLClient):
        """Elastic scale-up: new clients only need (pk, sk) + the public
        mask — no re-keying, no mask re-agreement."""
        client.ledger = self.ledger
        self.clients.append(client)

    def remove_client(self, cid: int):
        self.clients = [c for c in self.clients if c.cid != cid]


def run_federated_training(model: Model, clients: list[FLClient],
                           agg_cfg: AggregatorConfig,
                           run_cfg: FLRunConfig, ctx=None) -> FLTask:
    task = FLTask(model, clients, agg_cfg, run_cfg, ctx=ctx)
    task.run()
    return task
