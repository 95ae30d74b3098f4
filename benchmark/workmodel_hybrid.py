"""Operations and bytes of a pattern-string decoder (Mamba-2, latent
experts, grouped attention), from shapes alone: `workmodel.py`'s
arithmetic for the `hybrid_serve` family. `sz` is what the
configuration's reference gives as `sizes(cfg)`. Whatever implements a
layer, the count is the algorithm's.
"""
from __future__ import annotations

WEIGHT_BYTES = 2     # bfloat16


def mamba_params(sz):
    """(matrix weights: in_proj + out_proj, the rest: convolution,
    A_log, D, dt_bias, the gated norm, the block's norm)."""
    d, h = sz["hidden_size"], sz["mamba_num_heads"]
    inner = h * sz["mamba_head_dim"]
    conv = inner + 2 * sz["n_groups"] * sz["ssm_state_size"]
    mats = d * (inner + conv + h) + inner * d
    return mats, conv * sz["conv_kernel"] + conv + 3 * h + inner + d


def attention_params(sz):
    d, hd = sz["hidden_size"], sz["head_dim"]
    hq, hkv = sz["num_attention_heads"] * hd, sz["num_key_value_heads"] * hd
    return 2 * d * hq + 2 * d * hkv, d


def expert_params(sz):
    """One routed expert: latent -> intermediate -> latent."""
    return 2 * sz["moe_latent_size"] * sz["moe_intermediate_size"]


def expert_layer_params(sz):
    """An expert layer beside its routed experts: (matrices: router,
    latent down and up, the shared expert; the rest: selection bias,
    norm)."""
    d, lat = sz["hidden_size"], sz["moe_latent_size"]
    mats = d * sz["router_width"] + 2 * d * lat \
        + 2 * d * sz["moe_shared_expert_intermediate_size"]
    return mats, sz["router_width"] + d


def layer_counts(sz):
    p = sz["pattern"]
    return p.count("M"), p.count("E"), p.count("*")


def stack_params(sz):
    """Parameters of the layers held here (no embedding, no head)."""
    n_m, n_e, n_a = layer_counts(sz)
    return n_m * sum(mamba_params(sz)) + n_a * sum(attention_params(sz)) \
        + n_e * (sum(expert_layer_params(sz))
                 + sz["experts_held"] * expert_params(sz))


def kv_token_bytes(sz):
    """Bytes a token holds in the paged pools: K and V of the attention
    layers' KV heads, bfloat16."""
    return 2 * layer_counts(sz)[2] * sz["num_key_value_heads"] \
        * sz["head_dim"] * WEIGHT_BYTES


def state_slot_bytes(sz):
    """Recurrent state of one slot: float32 SSM state and the bfloat16
    convolution window, every Mamba-2 layer."""
    h, p, n = sz["mamba_num_heads"], sz["mamba_head_dim"], \
        sz["ssm_state_size"]
    conv = h * p + 2 * sz["n_groups"] * n
    return layer_counts(sz)[0] * (
        h * p * n * 4 + (sz["conv_kernel"] - 1) * conv * WEIGHT_BYTES)


def ssm_token_flops(sz):
    """The scan's own work a token and Mamba-2 layer: decay, the outer
    product's add and the read-out over [H, P, N], and the
    convolution."""
    h, p, n = sz["mamba_num_heads"], sz["mamba_head_dim"], \
        sz["ssm_state_size"]
    conv = h * p + 2 * sz["n_groups"] * n
    return 5 * h * p * n + 2 * sz["conv_kernel"] * conv


def expected_held(sz, tokens):
    """Selections that fall on held experts where they were not
    counted (prefill steps fetch no probe): the held share of top-k."""
    return tokens * sz["num_experts_per_tok"] * sz["experts_held"] \
        / sz["router_width"]


def moe_flops(sz, tokens, held_selections):
    """All expert layers: 2 per weight beside the routed experts for
    every token, 2 per weight of an expert for every selection that
    fell on one held here."""
    return layer_counts(sz)[1] * 2 * expert_layer_params(sz)[0] * tokens \
        + 2 * expert_params(sz) * held_selections


def ssm_flops(sz, tokens):
    return layer_counts(sz)[0] * tokens * (2 * mamba_params(sz)[0]
                                           + ssm_token_flops(sz))


def forward_flops(sz, tokens, context_sum, head_tokens, held_selections):
    """Forward flops of `tokens` tokens through the stack, of which
    `head_tokens` go through the head, attending to `context_sum` keys
    in total, with `held_selections` selections on held experts summed
    over the expert layers."""
    d = sz["hidden_size"]
    q_width = sz["num_attention_heads"] * sz["head_dim"]
    n_a = layer_counts(sz)[2]
    return ssm_flops(sz, tokens) + moe_flops(sz, tokens, held_selections) \
        + n_a * (2 * attention_params(sz)[0] * tokens
                 + 4 * q_width * context_sum) \
        + 2 * head_tokens * d * sz["vocab_size"]


def moe_bytes(sz, experts_hit):
    """What the expert layers of one decode step have to read: every
    held expert that got a token, once (`experts_hit` summed over the
    layers), and each layer's other weights."""
    return WEIGHT_BYTES * (experts_hit * expert_params(sz)
                           + layer_counts(sz)[1]
                           * sum(expert_layer_params(sz)))


def ssm_bytes(sz, live_rows):
    """What the Mamba-2 layers of one decode step have to move: the
    live rows' state read and written, and each mixer's weights."""
    return 2 * live_rows * state_slot_bytes(sz) \
        + WEIGHT_BYTES * layer_counts(sz)[0] * sum(mamba_params(sz))
