"""Plain reference of `bert_base_nodropout` as the cell trains it: loss,
gradients and AdamW in float32 at `highest`, in blocks of rows.

It follows the configuration file: the LM head and the softmax loss
over every position with labels = tokens, no dropout, AdamW with the
bias corrections folded into the step size and the decay applied to
every leaf. Each departure from published BERT is listed in the file
under `assumed`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_layers as rl
from benchmark import weights


def sizes(cfg):
    return {k: cfg[k] for k in ("hidden_size", "num_hidden_layers",
                                "intermediate_size", "vocab_size",
                                "num_attention_heads")}


def _loss_sum(p, toks, n_layers, n_heads, prec):
    h = rl.encoder(toks, p, n_layers, n_heads, False, prec, remat=True)
    logits = rl.lm_logits(h, p, prec).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, toks[..., None], axis=-1).sum()


def train_readings(cfg, seed, batches, prec=None, rows_block=8,
                   rows_used=None):
    """Drive the reference through `batches` (one [rows, T] array of
    token ids a step) from the weights of `seed`. Returns the loss of
    each step, the norm of each leaf's first gradient and the norm of
    each leaf's change after the last step.

    `rows_used` plants a fault: the mean is taken over the first
    `rows_used` rows only (half of the batch left out; one chip's
    shard, where the exchange between chips is left out).
    """
    prec = prec or rl.FLOAT32
    sz = sizes(cfg)
    opt = cfg["optimizer"]
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["lr"], opt["weight_decay"]
    L, H = sz["num_hidden_layers"], sz["num_attention_heads"]

    grad_block = jax.jit(jax.value_and_grad(
        lambda p, t: _loss_sum(p, t, L, H, prec)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    @jax.jit
    def adamw(p, m, v, g, t):
        step = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - step * m_ / (jnp.sqrt(v_) + eps)
            - lr * wd * p_, p, m, v)
        return p, m, v

    norms = weights.leaf_norms
    p0 = weights.make_weights(seed, sz)
    p = p0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norm = [], None
    for t, batch in enumerate(batches, start=1):
        batch = np.asarray(batch)[:rows_used]
        total, g = 0.0, None
        for r in range(0, batch.shape[0], rows_block):
            ls, gb = grad_block(p, jnp.asarray(batch[r:r + rows_block],
                                               jnp.int32))
            total = total + ls
            g = gb if g is None else add(g, gb)
        n_tok = batch.size
        g = jax.tree.map(lambda x: x / n_tok, g)
        losses.append(float(total) / n_tok)
        if t == 1:
            grad_norm = {k: float(x) for k, x in norms(g).items()}
        p, m, v = adamw(p, m, v, g, jnp.float32(t))
    delta = norms(jax.tree.map(jnp.subtract, p, p0))
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {k: float(x) for k, x in delta.items()}}
