"""The one traffic generator: a traffic file names a role and its
parameters, and the role builds the cell's inputs from the seed, runs one
unit of work at a time through the program's entry points, and compares
what the window produced with the plain reference.

  fold    the silo server: a unit is one round, `StreamIngest.ingest` of
          every silo's update blob back to back into a fresh device
          accumulator, FedAvg weights from the headers, then `finalize`.
  client  one silo: `FLClient.local_train`, then `protect_and_pack` of the
          local model in seeded mode, each round from the model the last
          round produced.

Everything the program is given (weights, token rows, the encryption
mask, sample counts) is made here from the seed; nothing is read from
outside the checkout.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import check
import spec as spec_mod


def jax_key(seed: int, *tags: int):
    """A threefry key from all 64 bits of `seed` (PRNGKey keeps only the
    low 32 of a larger seed), folded with `tags`."""
    import jax
    import jax.numpy as jnp

    k = jnp.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    dtype=jnp.uint32)
    for t in tags:
        k = jax.random.fold_in(k, t)
    return k


def program_seed(seed: int, tag: int) -> int:
    """A 31-bit seed for a program API that takes an int seed."""
    return int(np.random.default_rng([seed, tag]).integers(1, 2 ** 31 - 1))


class Spans:
    """Host spans of the harness around calls into the program: seconds per
    name, and with tracing on a `bench.<name>` annotation in the trace."""

    def __init__(self, tracing: bool):
        self.tracing = tracing
        self.seconds: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        ann = (jax.profiler.TraceAnnotation("bench." + name)
               if self.tracing else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds.setdefault(name, []).append(time.perf_counter() - t0)


def bytes_in_use(chips: int) -> int:
    """Device memory in use now on the fullest of the cell's chips."""
    import jax

    return max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in jax.devices()[:chips])


def make_params(shapes, key):
    """Weights in the served type, on the device, in one jitted call: the
    published init of the family, not the program's init function.  Norm
    scales 1, biases 0, every other leaf N(0, 0.02)."""
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(name, s, k):
        if name.startswith("ln"):
            return jnp.ones(s.shape, jnp.float32)
        if name in ("bq", "bk", "bv"):
            return jnp.zeros(s.shape, jnp.float32)
        return 0.02 * jax.random.normal(k, s.shape, jnp.float32)

    def make(key):
        out = [leaf(str(getattr(path[-1], "key", path[-1])), s,
                    jax.random.fold_in(key, i)).astype(s.dtype)
               for i, (path, s) in enumerate(paths)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key)


def flat_host(params) -> np.ndarray:
    """The flat f32 vector of a parameter tree, leaves in tree order."""
    import jax

    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree_util.tree_leaves(params)])


def encryption_mask(n_total: int, share: float, seed: int) -> np.ndarray:
    """The public encryption mask: round(share * n_total) positions drawn
    uniformly without replacement (the `random` selection strategy)."""
    k = int(round(n_total * share))
    mask = np.zeros(n_total, dtype=bool)
    mask[np.random.default_rng([seed, 7]).choice(n_total, k,
                                                 replace=False)] = True
    return mask


class TokenStream:
    """Token rows for local training, all different: uniform ids from the
    seed, labels the next token (the last wraps to the first).  The first
    `keep` batches are kept for the reference."""

    def __init__(self, vocab: int, batch: int, seq_len: int, seed: int,
                 keep: int = 0):
        self.vocab, self.batch, self.seq_len = vocab, batch, seq_len
        self.rng = np.random.default_rng([seed, 11])
        self.keep = keep
        self.kept: list[dict] = []

    def next_batch(self) -> dict:
        toks = self.rng.integers(0, self.vocab, (self.batch, self.seq_len),
                                 dtype=np.int32)
        b = {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}
        if len(self.kept) < self.keep:
            self.kept.append({k: v.copy() for k, v in b.items()})
        return b


class _Common:
    """What both roles build: the model's shapes, the CKKS context and keys,
    the encryption mask and the program's selective aggregator."""

    def __init__(self, cell: spec_mod.Cell, seed: int, spans: Spans):
        from repro.core import packing
        from repro.core.ckks import params as ckks_params
        from repro.core.secure_agg import (AggregatorConfig,
                                           SelectiveHEAggregator)
        from repro.fl.keys import KeyAuthority
        from repro.models import build_model

        self.cell, self.seed, self.spans = cell, seed, spans
        c = cell.config
        dep = c["deployment"]
        self.cfg = spec_mod.model_config(c, cell.config_name)
        self.model = build_model(self.cfg)
        self.shapes = self.model.init_abstract()
        self.ctx = ckks_params.make_context(**dep["ckks"])
        self.pk, self.sk = KeyAuthority(
            self.ctx, seed=program_seed(seed, 1)).client_keys()
        self.spec = packing.make_flat_spec(self.shapes)
        self.mask = encryption_mask(self.spec.total, c["encrypted_share"],
                                    seed)
        self.enc_idx = np.flatnonzero(self.mask)
        self.plain_idx = np.flatnonzero(~self.mask)
        self.agg = SelectiveHEAggregator(
            self.ctx, self.spec,
            packing.make_partition(self.mask, self.ctx.slots),
            AggregatorConfig(p_ratio=c["encrypted_share"],
                             strategy=dep["selection"],
                             seed=program_seed(seed, 2)))
        self.n_chunks = self.agg.part.n_chunks
        self.n_silos = int(dep["silos_per_round"])
        self.plain_codec = dep["plain_codec"]
        self.work = {"ct": 0, "updates": 0, "tokens": 0}
        # device memory in use, sampled by a role at its boundaries in the
        # window
        self.hbm_in_use: list[int] = []

    def shapes_for_counts(self) -> dict:
        out = {"n_poly": self.ctx.n_poly, "n_limbs": self.ctx.n_limbs,
               "n_chunks": self.n_chunks, "params": self.spec.total,
               "family": self.cfg.family, "n_layers": self.cfg.n_layers,
               "d_model": self.cfg.d_model}
        if hasattr(self, "seq"):
            out["seq_len"] = self.seq
        return out

    def policy(self):
        from repro.wire.compress import WirePolicy

        return WirePolicy(plain_codec=self.plain_codec)

    def read_update(self, blob: bytes):
        """(encrypted values f32[n_enc], plain f32[n_plain]) carried by one
        update blob, read through the server's ingest at weight 1 and the
        secret key."""
        from repro.wire import stream as ws

        ing = ws.StreamIngest(self.ctx)
        ing.ingest(blob, 1.0)
        upd = ing.finalize()
        return self.decrypt(upd)

    def decrypt(self, upd):
        from repro.core.ckks import cipher

        enc = np.asarray(cipher.decrypt_values(self.ctx, self.sk, upd.ct),
                         np.float64).ravel()[: self.enc_idx.size]
        return enc, np.asarray(upd.plain, np.float32)


class Fold(_Common):
    """The silo server folding silo updates (see the module docstring)."""

    def setup(self) -> None:
        import jax

        from repro.fl import ClientConfig, FLClient

        t = self.cell.traffic
        dep = self.cell.config["deployment"]
        lo, hi = dep["samples_per_silo"]
        self.n_samples = np.random.default_rng([self.seed, 3]).integers(
            lo, hi + 1, self.n_silos)
        self.noise_std = float(t["update_noise_std"])
        self.blobs = []
        with self.spans("set-up blobs"):
            for i in range(self.n_silos):
                client = FLClient(i, self.model, None, ClientConfig())
                client.n_samples = int(self.n_samples[i])
                self.blobs.append(client.protect_and_pack(
                    self.agg, self.silo_params(i), rnd=0,
                    policy=self.policy(), sk=self.sk, mode=dep["uplink"]))
        # every program a round runs, compiled here: a round of its own of
        # the first silos' ingests, then its finalize
        with self.spans("set-up warm-up"):
            self.ingest = self.new_round()
            for i in range(int(t["warmup_updates"])):
                self.ingest_one(i)
            jax.block_until_ready(self.ingest.finalize())
        self.ingest = None
        self.rounds: list = []     # the first and the last round's aggregate

    def silo_params(self, i: int):
        """Silo i's local model: the round's global model (made from the
        seed) plus seeded noise, made on the device, held by no one else."""
        import jax

        std = self.noise_std

        @jax.jit
        def silo(params, key):
            leaves, treedef = jax.tree_util.tree_flatten(params)
            return jax.tree_util.tree_unflatten(treedef, [
                l + std * jax.random.normal(jax.random.fold_in(key, j),
                                            l.shape, l.dtype)
                for j, l in enumerate(leaves)])

        return silo(make_params(self.shapes, jax_key(self.seed, 4)),
                    jax_key(self.seed, 5, i))

    def new_round(self):
        """A fresh accumulator, and the round's FedAvg weights as the server
        takes them: from the sample counts in the update headers."""
        from repro.wire import stream as ws

        ns = np.asarray([ws.peek_update_meta(b).n_samples
                         for b in self.blobs], dtype=np.float64)
        self.weights = ns / ns.sum()
        return ws.StreamIngest(self.ctx)

    def ingest_one(self, i: int) -> None:
        import jax

        with self.spans("ingest"):
            self.ingest.ingest(self.blobs[i], float(self.weights[i]))
            # the fold is asynchronous on the device: the update counts once
            # it is in the accumulator
            jax.block_until_ready(self.ingest._acc_ct)

    def unit(self) -> None:
        """One round: every silo's update ingested, then finalized."""
        import jax

        self.ingest = self.new_round()
        for i in range(self.n_silos):
            self.ingest_one(i)
            self.hbm_in_use.append(bytes_in_use(self.cell.chips))
        with self.spans("finalize"):
            agg = jax.block_until_ready(self.ingest.finalize())
        self.hbm_in_use.append(bytes_in_use(self.cell.chips))
        self.ingest = None
        # the first round shows the step from the warm-up's round, the last
        # anything carried from round to round; the ones between are kept
        # by no one, so that a faster fold does not fill the chip
        self.rounds[1:] = [agg]
        self.work["ct"] += self.n_silos * self.n_chunks
        self.work["updates"] += self.n_silos

    def check(self) -> dict:
        """The first and the last round aggregate of the window against the
        silos' FedAvg: the encrypted partition (decrypted) in float64 and
        the plaintext partition exactly, in the server's f32 order."""
        ref_enc, ref_plain = self.reference()
        enc_gap, plain_gap = 0.0, 0.0
        for agg in self.rounds:
            enc, plain = self.decrypt(agg)
            enc_gap = max(enc_gap, check.rel_gap(enc, ref_enc))
            plain_gap = max(plain_gap, check.abs_gap(plain, ref_plain))
        return {"enc_gap": enc_gap, "plain_gap": plain_gap}

    def reference(self, precision: str = "float64"):
        """The round's FedAvg of the silos' vectors, with weights from the
        sample counts drawn from the seed (not from the blobs' headers),
        made from the seed: the encrypted partition in `precision` (float64,
        or bfloat16 for the control), the plaintext partition in f32 in the
        server's order of addition."""
        import jax.numpy as jnp

        dt = {"float64": np.float64,
              "bfloat16": np.dtype(jnp.bfloat16).type}[precision]
        w = self.n_samples / self.n_samples.sum()
        ref_enc = np.zeros(self.enc_idx.size, dt)
        ref_plain = np.zeros(self.plain_idx.size, np.float32)
        for i in range(self.n_silos):
            x = flat_host(self.silo_params(i))
            ref_enc = (ref_enc + dt(w[i]) * x[self.enc_idx].astype(dt)
                       ).astype(dt)
            ref_plain += np.float32(w[i]) * x[self.plain_idx]
        return ref_enc.astype(np.float64), ref_plain

    def control(self) -> dict:
        """The reference in bfloat16 in the program's place."""
        return {"enc_gap": check.rel_gap(self.reference("bfloat16")[0],
                                         self.reference()[0])}


class Client(_Common):
    """One silo producing its round update (see the module docstring)."""

    def setup(self) -> None:
        import jax

        from repro.fl import ClientConfig, FLClient

        from repro.optim import AdamWConfig

        t = self.cell.traffic
        tr = self.cell.config["training"]
        # FLClient runs AdamW at these defaults with no weight decay: a
        # configuration that states others is not what the program runs
        run = AdamWConfig()
        if (tr["optimizer"], tr["weight_decay"]) != ("adamw", 0.0) or any(
                tr[k] != getattr(run, k)
                for k in ("b1", "b2", "eps", "clip_norm")):
            raise ValueError(f"the client step runs AdamW {run} without "
                             f"weight decay, not {tr}")
        self.steps, self.batch, self.seq = (int(t["local_steps"]),
                                            int(t["batch"]),
                                            int(t["seq_len"]))
        self.stream = TokenStream(self.cfg.vocab, self.batch, self.seq,
                                  self.seed, keep=3)
        self.client = FLClient(0, self.model, self.stream,
                               ClientConfig(local_steps=self.steps,
                                            lr=float(tr["lr"])))
        self.global_params = make_params(self.shapes, jax_key(self.seed, 4))
        self.round = 0
        protect = self.agg.client_protect_seeded

        def protect_spanned(*a, **k):
            with self.spans("encrypt"):
                return jax.block_until_ready(protect(*a, **k))

        self.agg.client_protect_seeded = protect_spanned
        # the first steps go through the window's own calls; the reference
        # follows the first three
        self.capture = StepCapture(self.client, self.global_params,
                                   float(tr["b1"]))
        with self.spans("set-up warm-up"):
            for _ in range(int(t["warmup_updates"])):
                self.unit(count=False)
            # the reference follows three steps: train on to the third
            while len(self.capture.losses) < 3:
                self.global_params, _ = self.client.local_train(
                    self.global_params)
        self.capture.remove()
        self.work = {k: 0 for k in self.work}

    def unit(self, count: bool = True) -> None:
        import jax

        with self.spans("train"):
            local, _ = self.client.local_train(self.global_params)
            jax.block_until_ready(local)
        # the round's global model is not needed past training
        self.global_params = local
        with self.spans("protect"):
            self.blob = self.client.protect_and_pack(
                self.agg, local, rnd=self.round, policy=self.policy(),
                sk=self.sk, mode=self.cell.config["deployment"]["uplink"])
        self.round += 1
        if count:
            self.work["ct"] += self.n_chunks
            self.work["updates"] += 1
            self.work["tokens"] += self.steps * self.batch * self.seq

    def check(self) -> dict:
        """The last update of the run against the local model it came from,
        and the first three local steps against the reference."""
        import jax

        x = flat_host(self.global_params)
        enc, plain = self.read_update(self.blob)
        out = {"enc_gap": check.rel_gap(enc, x[self.enc_idx]),
               "plain_gap": check.abs_gap(plain, x[self.plain_idx])}
        prog = self.capture.readings()
        self.free()
        import reference

        ref = reference.train_readings(
            self.cell.config, make_params(self.shapes, jax_key(self.seed, 4)),
            self.stream.kept, self.steps)
        out.update(check.training_gaps(prog, ref))
        jax.clear_caches()
        return out

    def control(self) -> dict:
        """Readings that set the upper ends of the limits: the encrypted
        values in bfloat16 and the three steps in float8 in the program's
        place, and the fault `half_batch.*`: the reference's steps on half
        of each batch, the mean taken over the rest."""
        import jax.numpy as jnp

        import reference

        p0 = make_params(self.shapes, jax_key(self.seed, 4))
        x = flat_host(p0)[self.enc_idx].astype(np.float64)
        out = {"enc_gap": check.rel_gap(
            x.astype(jnp.bfloat16).astype(np.float64), x)}
        run = lambda **k: reference.train_readings(
            self.cell.config, p0, self.stream.kept, self.steps, **k)
        ref = run()
        out.update(check.training_gaps(run(precision="float8"), ref))
        out.update({"half_batch." + k: v for k, v in check.training_gaps(
            run(rows=self.batch // 2), ref).items()})
        return out

    def free(self) -> None:
        self.global_params = None
        self.blob = None
        self.client = None


class StepCapture:
    """Wraps the client's jitted step for the set-up's first three steps:
    each loss, the first gradient as AdamW got it (its first moment after
    one step over 1 - b1) and each leaf's change after three steps, read
    before the next step donates the state."""

    def __init__(self, client, p0, b1: float):
        import jax
        import jax.numpy as jnp

        self.client, self.p0, self.b1 = client, p0, b1
        self.inner = client._step
        self.losses: list[float] = []
        self.grad_norms = self.change_norms = None
        norms = lambda t: [jnp.linalg.norm(l.astype(jnp.float32).ravel())
                           for l in jax.tree_util.tree_leaves(t)]
        self._norms = jax.jit(norms)
        self._diff_norms = jax.jit(
            lambda a, b: norms(jax.tree_util.tree_map(
                lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
                a, b)))
        client._step = self

    def __call__(self, *args):
        out = self.inner(*args)
        params, opt_state, loss = out
        k = len(self.losses) + 1
        if k <= 3:
            self.losses.append(float(loss))
        if k == 1:
            self.grad_norms = np.asarray(
                self._norms(opt_state["m"]), np.float64) / (1.0 - self.b1)
        if k == 3:
            self.change_norms = np.asarray(
                self._diff_norms(params, self.p0), np.float64)
        return out

    def remove(self) -> None:
        self.client._step = self.inner
        self.p0 = None

    def readings(self) -> dict:
        return {"losses": np.asarray(self.losses, np.float64),
                "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}


ROLES = {"fold": Fold, "client": Client}
