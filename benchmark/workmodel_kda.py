"""Operations and bytes of a decoder of gated delta-rule linear
attention (KDA) beside position-free latent attention, a gated FFN and
gated routed experts, from shapes alone: `workmodel.py`'s arithmetic
for the `kda_serve` family. `sz` is what the configuration's reference
gives as `sizes(cfg)`. Whatever implements a layer, the count is the
algorithm's: a token reads a KDA layer's state once and writes it once.

The latent cache's row, its attention and the expert layers are
`workmodel_mla`'s, whose functions read the same keys of `sz`.
"""
from __future__ import annotations

from benchmark.workmodel_mla import (  # noqa: F401
    WEIGHT_BYTES, attn_bytes, attn_flops, dense_params, expected_held,
    expert_layer_params, expert_params, kv_token_bytes, moe_bytes,
    moe_flops)


def layer_counts(sz):
    """(KDA, latent-attention, dense-FFN, expert sub-layers) held here."""
    p = sz["pattern"]
    return p.count("K"), p.count("L"), p.count("D"), p.count("G")


def kda_inner(sz):
    return sz["kda_num_heads"] * sz["kda_head_dim"]


def kda_params(sz):
    """(matrix weights of one KDA mixer: W_q, W_k, W_v, both low-rank
    gates, W_b, W_o; the rest: the convolutions' taps, A_log, dt_bias,
    the per-head norm)."""
    d, h, hk = sz["hidden_size"], sz["kda_num_heads"], sz["kda_head_dim"]
    inner = kda_inner(sz)
    mats = 4 * d * inner + 2 * (d * hk + hk * inner) + d * h
    return mats, 3 * inner * sz["short_conv_kernel_size"] + h + inner + hk


def attention_params(sz):
    """The matrices of one latent attention without a query latent:
    W_q, W_kva, W_kvb, W_o."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    kr = sz["kv_lora_rank"]
    nope, rope, vd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    return d * h * (nope + rope) + d * (kr + rope) \
        + kr * h * (nope + vd) + h * vd * d


def stack_params(sz):
    """Parameters of the layers held here but the norm scales and the
    selection biases (no embedding, no head)."""
    n_k, n_l, n_d, n_g = layer_counts(sz)
    return n_k * sum(kda_params(sz)) + n_l * attention_params(sz) \
        + n_d * dense_params(sz) \
        + n_g * (expert_layer_params(sz)
                 + sz["experts_held"] * expert_params(sz))


def norm_params(sz):
    """Every sub-layer's pre-norm, a latent attention's latent norm,
    the final norm."""
    return (len(sz["pattern"]) + 1) * sz["hidden_size"] \
        + layer_counts(sz)[1] * sz["kv_lora_rank"]


def weight_bytes(sz):
    """Everything the chip holds of the model, bfloat16; `A_log` and
    `dt_bias` (counted in `kda_params`) and the selection bias (counted
    nowhere else) are float32."""
    n_k, _, _, n_g = layer_counts(sz)
    ends = 2 * sz["vocab_size"] * sz["hidden_size"]
    return WEIGHT_BYTES * (stack_params(sz) + norm_params(sz) + ends) \
        + (4 - WEIGHT_BYTES) * n_k * (sz["kda_num_heads"] + kda_inner(sz)) \
        + 4 * n_g * sz["router_width"]


def state_slot_bytes(sz):
    """Recurrent state of one slot: every KDA layer's float32 state (a
    head [keys, values]) and its bfloat16 window of the three
    convolutions."""
    inner = kda_inner(sz)
    return layer_counts(sz)[0] * (
        inner * sz["kda_head_dim"] * 4
        + (sz["short_conv_kernel_size"] - 1) * 3 * inner * WEIGHT_BYTES)


def kda_token_flops(sz):
    """The recurrence's own work a token and KDA layer: decay, what the
    state predicts for the key, the rank-one write and the query's read
    over [H, K, K], and the three convolutions."""
    inner = kda_inner(sz)
    return 7 * inner * sz["kda_head_dim"] \
        + 2 * sz["short_conv_kernel_size"] * 3 * inner


def kda_flops(sz, tokens):
    return layer_counts(sz)[0] * tokens * (2 * kda_params(sz)[0]
                                           + kda_token_flops(sz))


def kda_bytes(sz, live_rows):
    """What the KDA layers of one decode step have to move: the live
    rows' state and windows read and written once, and each mixer's
    weights."""
    mats, rest = kda_params(sz)
    return 2 * live_rows * state_slot_bytes(sz) \
        + layer_counts(sz)[0] * (WEIGHT_BYTES * mats + 4 * rest)


def forward_flops(sz, tokens, context_sum, head_tokens, held_selections):
    """Forward flops of `tokens` tokens through the stack, of which
    `head_tokens` go through the head, attending to `context_sum` keys
    in total in each latent layer, with `held_selections` selections on
    held experts summed over the expert layers."""
    _, n_l, n_d, _ = layer_counts(sz)
    return kda_flops(sz, tokens) \
        + 2 * tokens * (n_l * attention_params(sz)
                        + n_d * dense_params(sz)) \
        + attn_flops(sz, context_sum) \
        + moe_flops(sz, tokens, held_selections) \
        + 2 * head_tokens * sz["hidden_size"] * sz["vocab_size"]
