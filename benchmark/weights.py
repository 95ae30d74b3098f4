"""The weights of a cell, made from `--seed` by the benchmark.

One jitted call on the device makes every leaf in float32, the type
both configurations keep their master weights in. The program gets
them written into its scope; the plain reference makes them again from
the same seed, so it takes nothing that the program has made.

Names are the parameter names of the repo's shared encoder stack
(models/transformer.py); a family checks them against its program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STD = 0.02


def leaf_table(sizes):
    """name -> (shape, kind), kind in matrix / bias / scale / embedding.
    An embedding is a matrix with a deviation of its own where the
    configuration states one (`word_emb_std`)."""
    d, f, v = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["vocab_size"]
    table = {"word_emb": ((v, d), "embedding")}
    for i in range(sizes["num_hidden_layers"]):
        p = f"layer_{i}"
        for name, n_in, n_out in (("att.q", d, d), ("att.k", d, d),
                                  ("att.v", d, d), ("att.proj", d, d),
                                  ("ffn.fc1", d, f), ("ffn.fc2", f, d)):
            table[f"{p}.{name}.w"] = ((n_in, n_out), "matrix")
            table[f"{p}.{name}.b"] = ((n_out,), "bias")
        for ln in ("ln1", "ln2"):
            table[f"{p}.{ln}.w"] = ((d,), "scale")
            table[f"{p}.{ln}.b"] = ((d,), "bias")
    table["lm_head.w"] = ((d, v), "matrix")
    return table


def seed_key(seed):
    """A key from any whole number up to 2**62: the driver's seeds pass
    2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(table_items, emb_std):
    """The jitted maker of one table of leaves: traced once a process,
    however often the weights are made again."""
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(table_items):
            std = emb_std if kind == "embedding" else STD
            x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            out[name] = 1.0 + x if kind == "scale" else x
        return out
    return jax.jit(make)


def make_weights(seed, sizes):
    return _maker(tuple(leaf_table(sizes).items()),
                  float(sizes.get("word_emb_std", STD)))(seed_key(seed))


@jax.jit
def leaf_norms(tree):
    """{leaf: its Euclidean norm, in float32}."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}
