"""Operations and bytes the algorithms need, from shapes alone.

The yardstick's arithmetic: a later PR cannot change it, so a share of
a peak means the same before and after. `cfg` is a configuration file's
dict (hidden_size, num_hidden_layers, intermediate_size, vocab_size).
"""
from __future__ import annotations


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_hidden_layers"],
            cfg["intermediate_size"], cfg["vocab_size"])


def matmul_params(cfg, head_frac=1.0):
    """Weights that sit in a matrix product: per layer q, k, v, proj
    (4 d^2) and the two FFN matrices (2 d d_ff), plus the LM head at
    the share of positions it runs on. Embedding lookups, biases and
    layer norms multiply nothing."""
    d, L, f, v = _sizes(cfg)
    return L * (4 * d * d + 2 * d * f) + head_frac * v * d


def train_flops_per_token(cfg, seq_len, head_frac=1.0):
    """Forward + backward matmul flops of one token at sequence length
    `seq_len`: 6 per weight, and attention's scores and context,
    12 L T d (bench.model_flops_per_token). Recomputation counts
    nothing."""
    d, L, _, _ = _sizes(cfg)
    return 6 * matmul_params(cfg, head_frac) + 12 * L * seq_len * d


def forward_flops(cfg, n_tokens, context_sum, head_tokens):
    """Forward flops of `n_tokens` tokens through the stack, of which
    `head_tokens` go through the LM head, attending to `context_sum`
    keys in total (the sum over tokens of the positions each one
    sees): 2 per weight, and 4 d per token-key pair and layer."""
    d, L, f, v = _sizes(cfg)
    return (2 * n_tokens * L * (4 * d * d + 2 * d * f)
            + 2 * head_tokens * v * d
            + 4 * L * d * context_sum)


def kv_read_bytes(cfg, tokens_held, kv_dtype_bytes=4):
    """Bytes a decode step has to read from the KV cache: keys and
    values of every token held, in every layer."""
    d, L, _, _ = _sizes(cfg)
    return tokens_held * 2 * d * kv_dtype_bytes * L


def matmul_flops_train_step(cfg, batch, seq_len, head_frac=1.0):
    return batch * seq_len * train_flops_per_token(cfg, seq_len, head_frac)
