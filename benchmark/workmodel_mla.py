"""Operations and bytes of a latent-attention decoder with a gated FFN
and gated routed experts, from shapes alone: `workmodel.py`'s
arithmetic for the `mla_serve` family. `sz` is what the configuration's
reference gives as `sizes(cfg)`. Whatever implements a layer, the count
is the algorithm's: attention over a LATENT cache (one row a token for
all heads), so a query-key pair costs the row's width for the score and
the latent's width for the value, every head.
"""
from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16
LANES = 128          # a pool row takes whole lane tiles


def layer_counts(sz):
    """(latent-attention, dense-FFN, expert sub-layers) held here."""
    p = sz["pattern"]
    return p.count("L"), p.count("D"), p.count("G")


def attention_params(sz):
    """The matrices of one latent attention: W_qa, W_qb, W_kva, W_kvb,
    W_o."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    qr, kr = sz["q_lora_rank"], sz["kv_lora_rank"]
    nope, rope, vd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    return d * qr + qr * h * (nope + rope) + d * (kr + rope) \
        + kr * h * (nope + vd) + h * vd * d


def dense_params(sz):
    return 3 * sz["hidden_size"] * sz["intermediate_size"]


def expert_params(sz):
    """One routed expert: gate, up and down."""
    return 3 * sz["hidden_size"] * sz["moe_intermediate_size"]


def expert_layer_params(sz):
    """An expert layer beside its routed experts: router and shared
    expert(s)."""
    return sz["hidden_size"] * sz["router_width"] \
        + sz["n_shared_experts"] * expert_params(sz)


def norm_params(sz):
    """Norm scales and selection biases: every sub-layer's pre-norm, the
    two latent norms of an attention, the final norm."""
    n_l, n_d, n_g = layer_counts(sz)
    return (n_l + n_d + n_g + 1) * sz["hidden_size"] \
        + n_l * (sz["q_lora_rank"] + sz["kv_lora_rank"])


def stack_params(sz):
    """Matrix parameters of the layers held here (no embedding, head)."""
    n_l, n_d, n_g = layer_counts(sz)
    return n_l * attention_params(sz) + n_d * dense_params(sz) \
        + n_g * (expert_layer_params(sz)
                 + sz["experts_held"] * expert_params(sz))


def weight_bytes(sz):
    """Everything the chip holds of the model, bfloat16 (the float32
    selection bias counted at 4)."""
    ends = 2 * sz["vocab_size"] * sz["hidden_size"]
    return WEIGHT_BYTES * (stack_params(sz) + ends + norm_params(sz)) \
        + 4 * layer_counts(sz)[2] * sz["router_width"]


def row_width(sz):
    return sz["kv_lora_rank"] + sz["qk_rope_head_dim"]


def kv_token_bytes(sz):
    """Bytes a token holds in the paged pools: one latent row a layer,
    in whole lane tiles, bfloat16."""
    lanes = -(-row_width(sz) // LANES) * LANES
    return layer_counts(sz)[0] * lanes * WEIGHT_BYTES


def attn_pair_flops(sz):
    """One query token against one key, every head: the score over the
    row's width, the value over the latent's."""
    return 2 * (row_width(sz) + sz["kv_lora_rank"]) \
        * sz["num_attention_heads"]


def attn_flops(sz, context_sum):
    return layer_counts(sz)[0] * attn_pair_flops(sz) * context_sum


def attn_bytes(sz, pages, block_size):
    """What the attention of one step has to read of the pools: every
    held page of every layer, once."""
    return pages * block_size * kv_token_bytes(sz)


def expected_held(sz, tokens):
    """Selections that fall on held experts where they were not
    counted (prefill steps fetch no probe): the held share of top-k,
    every expert layer."""
    return layer_counts(sz)[2] * tokens * sz["num_experts_per_tok"] \
        * sz["experts_held"] / sz["router_width"]


def moe_flops(sz, tokens, held_selections):
    """All expert layers: 2 per weight beside the routed experts for
    every token, 2 per weight of an expert for every selection that
    fell on one held here."""
    return layer_counts(sz)[2] * 2 * expert_layer_params(sz) * tokens \
        + 2 * expert_params(sz) * held_selections


def moe_bytes(sz, experts_hit):
    """What the expert layers of one decode step have to read: every
    held expert that got a token, once (`experts_hit` summed over the
    layers), and each layer's router and shared expert."""
    return WEIGHT_BYTES * (experts_hit * expert_params(sz)
                           + layer_counts(sz)[2] * expert_layer_params(sz))


def forward_flops(sz, tokens, context_sum, head_tokens, held_selections):
    """Forward flops of `tokens` tokens through the stack, of which
    `head_tokens` go through the head, attending to `context_sum` keys
    in total, with `held_selections` selections on held experts summed
    over the expert layers."""
    n_l, n_d, _ = layer_counts(sz)
    return 2 * tokens * (n_l * attention_params(sz)
                         + n_d * dense_params(sz)) \
        + attn_flops(sz, context_sum) \
        + moe_flops(sz, tokens, held_selections) \
        + 2 * head_tokens * sz["hidden_size"] * sz["vocab_size"]
