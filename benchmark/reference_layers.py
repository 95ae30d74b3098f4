"""The shared encoder stack in plain jax.numpy, float32.

Written from the layer equations, importing nothing of the program:
post-layer-norm transformer blocks (attention, residual, layer norm,
gelu FFN, residual, layer norm), sinusoidal positions added to the
token embedding, an untied LM head. `Precision` is where a control
computes in something lower than float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
HIGHEST = jax.lax.Precision.HIGHEST


class Precision:
    """float32 at `highest`: what the reference computes in."""
    dtype = jnp.float32

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)


class BFloat16(Precision):
    """Everything in bfloat16: weights, activations, softmax and layer
    norm, the precision below float32 at the TPU's default."""
    dtype = jnp.bfloat16

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, a, b)


def _fake_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _int8_cotangent(y):
    return y


_int8_cotangent.defvjp(lambda y: (y, None),
                       lambda _, g: (_fake_int8(g),))


class Int8(Precision):
    """Every matrix product with both operands rounded to int8 (one
    scale a tensor), forward and backward: the precision below the
    bfloat16 that the AMP rewrite states."""

    def einsum(self, spec, a, b):
        y = jnp.einsum(spec, _fake_int8(a), _fake_int8(b),
                       precision=HIGHEST)
        return _int8_cotangent(y)


FLOAT32, BFLOAT16, INT8 = Precision(), BFloat16(), Int8()


def layer_norm(x, w, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + LN_EPS) * w + b


def position_table(n, d, dtype=jnp.float32):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)],
                           axis=-1).astype(dtype)


def dense(x, p, name, prec):
    return prec.einsum("btd,df->btf", x, p[f"{name}.w"]) + p[f"{name}.b"]


LAYER_LEAVES = tuple(f"{m}.{wb}" for m in ("att.q", "att.k", "att.v",
                                            "att.proj", "ffn.fc1", "ffn.fc2",
                                            "ln1", "ln2") for wb in "wb")


def block(x, lp, n_heads, causal, prec):
    """One layer; `lp` holds its leaves under the names of
    LAYER_LEAVES."""
    b, t, d = x.shape
    hd = d // n_heads

    def heads(z):
        return z.reshape(b, t, n_heads, hd)

    q = heads(dense(x, lp, "att.q", prec))
    k = heads(dense(x, lp, "att.k", prec))
    v = heads(dense(x, lp, "att.v", prec))
    s = prec.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    if causal:
        keep = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(keep, s, jnp.asarray(-1e30, s.dtype))
    a = jax.nn.softmax(s, axis=-1)
    ctx = prec.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, t, d)
    att = dense(ctx, lp, "att.proj", prec)
    x = layer_norm(x + att, lp["ln1.w"], lp["ln1.b"])
    h = jax.nn.gelu(dense(x, lp, "ffn.fc1", prec), approximate=False)
    ff = dense(h, lp, "ffn.fc2", prec)
    return layer_norm(x + ff, lp["ln2.w"], lp["ln2.b"])


def encoder(tokens, p, n_layers, n_heads, causal, prec, remat=False,
            scan=False):
    """tokens [b, t] -> hidden [b, t, d]. `scan` runs the layers as one
    scanned body over their stacked leaves: the same arithmetic, one
    layer to compile instead of n_layers."""
    d = p["word_emb"].shape[1]
    x = p["word_emb"][tokens] + position_table(tokens.shape[1], d,
                                               p["word_emb"].dtype)
    layers = [{k: p[f"layer_{i}.{k}"] for k in LAYER_LEAVES}
              for i in range(n_layers)]
    if scan:
        stacked = {k: jnp.stack([lp[k] for lp in layers])
                   for k in LAYER_LEAVES}
        x, _ = jax.lax.scan(
            lambda x, lp: (block(x, lp, n_heads, causal, prec), None),
            x, stacked)
        return x
    step = block
    if remat:
        step = jax.checkpoint(block, static_argnums=(2, 3, 4))
    for lp in layers:
        x = step(x, lp, n_heads, causal, prec)
    return x


def lm_logits(h, p, prec):
    return prec.einsum("btd,dv->btv", h, p["lm_head.w"])


def cast_params(p, prec):
    return {k: v.astype(prec.dtype) for k, v in p.items()}
