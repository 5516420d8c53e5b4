"""Plain references of the models the cells train, in float32 at the
highest matmul precision, written from the published architectures.

They import nothing of the program.  They read the program's parameter
layout (stacked per-layer leaves under the names below) only to know which
array is which weight:

  qwen2  embed [V', d] (V' >= vocab_size; tied: the unembedding is the
         transpose of its first vocab_size rows); layers.ln1,
         ln2 [L, d]; wq, wk, wv [L, d, h*hd]; bq, bk, bv [L, h*hd];
         wo [L, h*hd, d]; w_gate, w_up [L, d, ff]; w_down [L, ff, d];
         ln_f [d].

`cast` is applied to both operands of every matmul: the identity for the
reference, a round trip through a lower precision for the control.
"""
from __future__ import annotations

import functools

import numpy as np


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + eps)) * scale


def _rope(x, theta):
    """Rotary embedding, rotate-half form (Qwen2 / Llama): x [B, S, H, hd]."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layers(layer, x, stacked):
    """x through every layer, the weights of layer i being each stacked
    leaf's row i; one layer's activations are kept for the backward."""
    import jax

    return jax.lax.scan(lambda h, w: (jax.checkpoint(layer)(h, w), None),
                        x, stacked)[0]


def qwen2_loss(params, batch, conf: dict, cast):
    """Mean next-token cross entropy of a Qwen2 decoder."""
    import jax
    import jax.numpy as jnp

    mm = lambda a, b: jnp.matmul(cast(a), cast(b))
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    kvh = conf["num_key_value_heads"]
    hd = d // h
    eps = conf["rms_norm_eps"]
    theta = float(conf["rope_theta"])
    lay = params["layers"]
    tokens = jnp.asarray(batch["tokens"])
    b, s = tokens.shape
    causal = np.tril(np.ones((s, s), bool))

    def layer(x, w):
        y = _rms_norm(x, w["ln1"], eps)
        q = (mm(y, w["wq"]) + w["bq"]).reshape(b, s, h, hd)
        k = (mm(y, w["wk"]) + w["bk"]).reshape(b, s, kvh, hd)
        v = (mm(y, w["wv"]) + w["bv"]).reshape(b, s, kvh, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
        att = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k)) / np.sqrt(hd)
        att = jnp.where(causal, att, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", cast(jax.nn.softmax(att, -1)),
                       cast(v)).reshape(b, s, h * hd)
        x = x + mm(o, w["wo"])
        y = _rms_norm(x, w["ln2"], eps)
        return x + mm(jax.nn.silu(mm(y, w["w_gate"])) * mm(y, w["w_up"]),
                      w["w_down"])

    x = _layers(layer, params["embed"][tokens], lay)
    x = _rms_norm(x, params["ln_f"], eps)
    # rows past vocab_size are the program's padding of the table
    return _xent(mm(x, params["embed"][: conf["vocab_size"]].T),
                 batch["labels"])


def _xent(logits, labels):
    import jax
    import jax.numpy as jnp

    labels = jnp.asarray(labels)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked)


LOSSES = {"qwen2": qwen2_loss}


def _fp8(x):
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


CASTS = {"float32": lambda x: x, "float8": _fp8}


def train_readings(conf: dict, p0, batches, steps_per_update: int,
                   precision: str = "float32", rows=None) -> dict:
    """The first three local AdamW steps from p0 on `batches`, with the
    optimizer state fresh at each update's first step as in FL local
    training: each step's loss, each leaf's norm of the first (clipped)
    gradient, and each leaf's change after the three.  `rows` keeps only
    that many rows of each batch (a fault: part of the batch left out)."""
    import jax
    import jax.numpy as jnp

    tr = conf["training"]
    loss_fn = functools.partial(LOSSES[conf["model_type"]], conf=conf,
                                cast=CASTS[precision])
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    norms = jax.jit(lambda t: [jnp.linalg.norm(l.ravel()) for l in leaves(t)])
    b1, b2, eps, lr, wd = (tr["b1"], tr["b2"], tr["eps"], tr["lr"],
                           tr["weight_decay"])

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, t, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, tr["clip_norm"] / gn), g)
        m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x,
                                   v, g)
        p = jax.tree_util.tree_map(
            lambda w, a, c: w - lr * ((a / (1 - b1 ** t))
                                      / (jnp.sqrt(c / (1 - b2 ** t)) + eps)
                                      + wd * w), p, m, v)
        return p, m, v, loss, [jnp.linalg.norm(x.ravel()) for x in leaves(g)]

    f32 = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.asarray(x, jnp.float32), t)
    p0 = f32(p0)
    p = jax.tree_util.tree_map(jnp.copy, p0)
    out = {"losses": []}
    with jax.default_matmul_precision("highest"):
        for k in range(3):
            if k % steps_per_update == 0:
                m = jax.tree_util.tree_map(jnp.zeros_like, p0)
                v = jax.tree_util.tree_map(jnp.zeros_like, p0)
                t = 0
            t += 1
            batch = {key: val[:rows] for key, val in batches[k].items()}
            p, m, v, loss, gnorms = step(p, m, v, jnp.float32(t), batch)
            out["losses"].append(float(loss))
            if k == 0:
                out["grad_norms"] = np.asarray(gnorms, np.float64)
        out["change_norms"] = np.asarray(
            norms(jax.tree_util.tree_map(lambda a, b: a - b, p, p0)),
            np.float64)
    out["losses"] = np.asarray(out["losses"])
    return out
