"""Plain reference of `gpt2_medium` as the cell serves it: one causal
forward pass in float32 at `highest` over a prompt with its served
tokens, giving the logits at every position.

It follows the configuration file, whose `assumed` lists where the
repo's block departs from published GPT-2.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_layers as rl
from benchmark import weights


def sizes(cfg):
    return {"hidden_size": cfg["n_embd"], "num_hidden_layers": cfg["n_layer"],
            "intermediate_size": cfg["assumed"]["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "num_attention_heads": cfg["n_head"],
            "word_emb_std": cfg["weights"]["word_emb_std"]}


@functools.lru_cache(maxsize=None)
def _forward(n_layers, n_heads, prec):
    def forward(p, toks):
        h = rl.encoder(toks, p, n_layers, n_heads, True, prec, scan=True)
        return rl.lm_logits(h, p, prec).astype(jnp.float32)[0]
    return jax.jit(forward)


def params(cfg, seed, prec=None):
    prec = prec or rl.FLOAT32
    return rl.cast_params(weights.make_weights(seed, sizes(cfg)), prec)


def logits(cfg, p, tokens, prec=None):
    """Logits [len(tokens), vocab] of one sequence. The row is padded
    at the end to the configuration's context, so that one compiled
    shape serves every request of every cell; under the causal mask
    the padding changes nothing before it."""
    prec = prec or rl.FLOAT32
    sz = sizes(cfg)
    n = len(tokens)
    padded = np.zeros((1, max(n, cfg["n_positions"])), np.int32)
    padded[0, :n] = tokens
    fwd = _forward(sz["num_hidden_layers"], sz["num_attention_heads"], prec)
    return np.asarray(fwd(p, jnp.asarray(padded)))[:n]
