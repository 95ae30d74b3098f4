"""Plain reference of `kimi_linear_ep8_l8` as the cell serves it: one
causal forward pass over a prompt with its served tokens, float32 at
`highest`, giving the logits at every position. No cache, no kernels,
no batching, no chunk form: a layer at a time over the whole sequence,
linear attention as the token-by-token recurrence from a zero state,
latent attention in the NON-absorbed (per-head keys and values) form
over blocks of query positions, every held expert over every token
under a mask.

The equations (`u` is a sub-layer's input after its RMSNorm, eps 1e-5;
H heads, K = 128 numbers a head for keys and values alike):

    layer l   x <- x + Mixer(RMSNorm(x));  x <- x + FFN(RMSNorm(x)).
              The mixer of the layers `kda_layers` names (numbered from
              1) is K, of `full_attn_layers` L; the FFN of the first
              `first_k_dense_replace` layers is dense (D), of the
              others routed experts (G): a layer is two letters of the
              pattern string. After the last a final RMSNorm, logits =
              x . W_head.
    K         [q | k | v] = silu(conv4(u W_q | u W_k | u W_v)), each
              channel's causal convolution of 4 taps over its own past
              (zeros before the sequence), no bias;
              q_h <- q_h / |q_h| . K^-0.5,  k_h <- k_h / |k_h|;
              g = -exp(A_log_h) . softplus(W_f2 (W_f1 u) + dt_bias), a
              number a CHANNEL, alpha = exp(g);  beta_h = sigmoid(u W_b);
              S' = Diag(alpha_t) S_{t-1};  S_t = S' + beta_t k_t (v_t -
              S'^T k_t)^T, S a head [K, K] from zero;  o_t = S_t^T q_t;
              out = (RMSNorm_K(o_h; w) . sigmoid(W_g2 (W_g1 u))_h) W_o
    L         [q_nope | q_pe]_h = u W_q;  [c_kv | k_pe] = u W_kva;
              c_kv <- RMSNorm(c_kv);  [k_nope | v]_h = c_kv W_kvb;
              s_h(t, j) = (q_nope,h(t) . k_nope,h(j) + q_pe,h(t) .
              k_pe(j)) . (nope + pe)^-0.5: NOTHING is rotated (the model
              takes its positions from the K layers); causal softmax,
              o_h = sum_j p v_h(j), out = concat_h(o_h) W_o
    D, G      the gated FFN and the gated routed experts beside a
              shared expert of `kimi_k2_5_ep32_l5_reference`, whose
              functions they are: sigmoid scores over the published
              router, top-k by score + bias, weights = the selected
              scores over their sum times the scale, the sum over the
              selected experts THIS chip holds only.

Weights are made here from `--seed`, a sub-layer at a time, in the type
the configuration holds them in (bfloat16; float32 for `A_log`,
`dt_bias` and the selection bias) and computed with in float32;
`params` hands back the seed and not the leaves. The program's family
takes its weights from `layer_leaves` / `global_leaves` too.

Controls (the nearest precisions below what the configuration states):
`weights_int8` rounds every matrix to int8 (one scale a leaf),
`state_bf16` rounds the K layers' state to bfloat16 after every token:
what holding the state in the activations' type would do.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as _weights
from benchmark.configs.kimi_k2_5_ep32_l5_reference import (
    SELECT_STD, STD, _freeze, _mm, gated, rms_norm, routed_part,
    shared_part)
from benchmark.reference_layers import _fake_int8

HIGHEST = jax.lax.Precision.HIGHEST
CONTROLS = ("weights_int8", "state_bf16")
BF16, F32 = "bfloat16", "float32"
QUERY_BLOCK = 128     # query positions a block of the attention
L2_EPS = 1e-6
DT_RANGE = (0.001, 0.1)   # softplus(dt_bias) is log-uniform in it


def pattern(cfg):
    lin = cfg["linear_attn_config"]
    out = ""
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        if layer in lin["kda_layers"]:
            out += "K"
        elif layer in lin["full_attn_layers"]:
            out += "L"
        else:
            raise ValueError(f"layer {layer} is in neither list")
        out += "D" if layer <= cfg["first_k_dense_replace"] else "G"
    return out


def sizes(cfg):
    """The flat sizes the reference, the family and the work model read,
    under the configuration file's own key names where it has one."""
    dep, lin = cfg["deployment"], cfg["linear_attn_config"]
    return {
        "hidden_size": cfg["hidden_size"],
        "vocab_size": cfg["vocab_size"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "pattern": pattern(cfg),
        "num_attention_heads": cfg["num_attention_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "kda_num_heads": lin["num_heads"],
        "kda_head_dim": lin["head_dim"],
        "short_conv_kernel_size": lin["short_conv_kernel_size"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "n_shared_experts": cfg["num_shared_experts"],
        "router_width": dep["num_experts_published"],
        "experts_held": cfg["num_experts"],
        "expert_share": dep["expert_share"],
        "num_experts_per_tok": cfg["num_experts_per_token"],
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "max_seq": cfg["engine"]["max_seq"],
        "reference_positions": cfg["assumed"]["reference_positions"],
    }


# -- the leaves ------------------------------------------------------------

def layer_table(sz, kind):
    """[(leaf name inside `layer_<i>.`, shape, how it is drawn, type)]
    of one sub-layer of `kind` (a letter of the pattern)."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    rows = [("norm.w", (d,), "scale", BF16)]
    if kind == "K":
        kh, hk = sz["kda_num_heads"], sz["kda_head_dim"]
        inner = kh * hk
        rows += [("kda.q.w", (d, inner), "matrix", BF16),
                 ("kda.k.w", (d, inner), "matrix", BF16),
                 ("kda.v.w", (d, inner), "matrix", BF16),
                 ("kda.conv.w", (3 * inner, sz["short_conv_kernel_size"]),
                  "conv", BF16),
                 ("kda.f1.w", (d, hk), "matrix", BF16),
                 ("kda.f2.w", (hk, inner), "matrix", BF16),
                 ("kda.A_log", (kh,), "a_log", F32),
                 ("kda.dt_bias", (inner,), "dt_bias", F32),
                 ("kda.b.w", (d, kh), "matrix", BF16),
                 ("kda.g1.w", (d, hk), "matrix", BF16),
                 ("kda.g2.w", (hk, inner), "matrix", BF16),
                 ("kda.o_norm.w", (hk,), "scale", BF16),
                 ("kda.o.w", (inner, d), "matrix", BF16)]
    elif kind == "L":
        kr = sz["kv_lora_rank"]
        nope, rope, vd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
            sz["v_head_dim"]
        rows += [("att.q.w", (d, h * (nope + rope)), "matrix", BF16),
                 ("att.kv_a.w", (d, kr + rope), "matrix", BF16),
                 ("att.kv_norm.w", (kr,), "scale", BF16),
                 ("att.kv_b.w", (kr, h * (nope + vd)), "matrix", BF16),
                 ("att.o.w", (h * vd, d), "matrix", BF16)]
    elif kind == "D":
        f = sz["intermediate_size"]
        rows += [("ffn.w1", (d, 2 * f), "matrix", BF16),
                 ("ffn.w2", (f, d), "matrix", BF16)]
    elif kind == "G":
        f, eh = sz["moe_intermediate_size"], sz["experts_held"]
        sh = f * sz["n_shared_experts"]
        rows += [("moe.router.w", (d, sz["router_width"]), "matrix", BF16),
                 ("moe.router.bias", (sz["router_width"],), "select", F32),
                 ("moe.w1", (eh, d, 2 * f), "matrix", BF16),
                 ("moe.w2", (eh, f, d), "matrix", BF16),
                 ("moe.shared.w1", (d, 2 * sh), "matrix", BF16),
                 ("moe.shared.w2", (sh, d), "matrix", BF16)]
    else:
        raise ValueError(f"no layer kind {kind!r} in the pattern")
    return rows


def global_table(sz):
    d, v = sz["hidden_size"], sz["vocab_size"]
    return [("word_emb", (v, d), "matrix", BF16),
            ("final_norm.w", (d,), "scale", BF16),
            ("lm_head.w", (d, v), "matrix", BF16)]


def _draw(key, shape, how):
    if how in ("matrix", "scale", "select"):
        x = (SELECT_STD if how == "select" else STD) * jax.random.normal(
            key, shape, jnp.float32)
        return 1.0 + x if how == "scale" else x
    if how == "conv":
        # uniform within 1 / sqrt(taps), the source's framework default:
        # at the matrices' 0.02 silu(conv) is near 0 and the state unread
        bound = 1.0 / math.sqrt(shape[-1])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0))
    if how == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))      # inverse softplus
    raise ValueError(how)


@functools.lru_cache(maxsize=None)
def _maker(table):
    def make(key):
        return {name: _draw(jax.random.fold_in(key, j), shape,
                            how).astype(dtype)
                for j, (name, shape, how, dtype) in enumerate(table)}
    return jax.jit(make)


def layer_leaves(sz, seed, i):
    """{leaf name: array in the type the configuration holds it in} of
    sub-layer `i` (letter i of the pattern)."""
    key = jax.random.fold_in(_weights.seed_key(seed), i + 1)
    return _maker(tuple(layer_table(sz, sz["pattern"][i])))(key)


def global_leaves(sz, seed):
    key = jax.random.fold_in(_weights.seed_key(seed), 0)
    return _maker(tuple(global_table(sz)))(key)


# -- the layers, one sequence [T, d], float32 --------------------------------

def _held(leaves, control):
    """The leaves in float32, after the rounding a control adds."""
    out = {}
    for name, w in leaves.items():
        w = w.astype(jnp.float32)
        if control == "weights_int8" and w.ndim >= 2 \
                and not name.endswith("conv.w"):
            w = _fake_int8(w)
        out[name] = w
    return out


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_inputs(u, p, sz):
    """(q, k, v, g [T, H, K], beta [T, H]) of one sequence: what the
    recurrence takes, after convolution, norms and gates."""
    t = u.shape[0]
    h, hk, taps = sz["kda_num_heads"], sz["kda_head_dim"], \
        sz["short_conv_kernel_size"]
    qkv = jnp.concatenate([_mm(u, p[f"kda.{n}.w"]) for n in "qkv"], -1)
    full = jnp.concatenate(
        [jnp.zeros((taps - 1, qkv.shape[1]), u.dtype), qkv], 0)
    qkv = jax.nn.silu(sum(full[j:j + t] * p["kda.conv.w"][:, j]
                          for j in range(taps)))
    q, k, v = (x.reshape(t, h, hk) for x in jnp.split(qkv, 3, axis=-1))
    q, k = l2_normalise(q) * hk ** -0.5, l2_normalise(k)
    dt = jax.nn.softplus(_mm(_mm(u, p["kda.f1.w"]), p["kda.f2.w"])
                         + p["kda.dt_bias"]).reshape(t, h, hk)
    g = -jnp.exp(p["kda.A_log"])[:, None] * dt
    beta = jax.nn.sigmoid(_mm(u, p["kda.b.w"]))
    return q, k, v, g, beta


def kda_recurrence(q, k, v, g, beta, control=None):
    """o [T, H, K]: a token at a time from a zero state."""
    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, :, None] * s
        seen = jnp.einsum("hkv,hk->hv", s, k_t, precision=HIGHEST)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        if control == "state_bf16":
            # bfloat16's 8 exponent and 7 mantissa bits, by an op the
            # compiler may not elide (it drops a cast there and back)
            s = jax.lax.reduce_precision(s, 8, 7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    h, hk = q.shape[1:]
    _, out = jax.lax.scan(step, jnp.zeros((h, hk, hk), jnp.float32),
                          (q, k, v, g, beta))
    return out


def kda_mixer(u, p, sz, control=None):
    t = u.shape[0]
    o = kda_recurrence(*kda_inputs(u, p, sz), control)
    gate = jax.nn.sigmoid(_mm(_mm(u, p["kda.g1.w"]), p["kda.g2.w"]))
    o = rms_norm(o, p["kda.o_norm.w"], sz["norm_eps"]) \
        * gate.reshape(o.shape)
    return _mm(o.reshape(t, -1), p["kda.o.w"])


def attention(u, p, sz, control=None):
    t = u.shape[0]
    h, kr = sz["num_attention_heads"], sz["kv_lora_rank"]
    nope, rp, vd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    pos = jnp.arange(t, dtype=jnp.int32)
    q = _mm(u, p["att.q.w"]).reshape(t, h, nope + rp)
    kv = _mm(u, p["att.kv_a.w"])
    c_kv = rms_norm(kv[:, :kr], p["att.kv_norm.w"], sz["norm_eps"])
    k_pe = kv[:, kr:]
    kvb = _mm(c_kv, p["att.kv_b.w"]).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = (nope + rp) ** -0.5
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(args):
        qn, qp, at = args
        s = (jnp.einsum("thd,shd->hts", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("thr,sr->hts", qp, k_pe, precision=HIGHEST)
             ) * scale
        s = jnp.where(pos[None, None, :] <= at[None, :, None], s, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    ctx = jax.lax.map(block, (q[..., :nope].reshape(t // qb, qb, h, nope),
                              q[..., nope:].reshape(t // qb, qb, h, rp),
                              pos.reshape(t // qb, qb)))
    return _mm(ctx.reshape(t, h * vd), p["att.o.w"])


def gated_moe(u, p, sz, share=None):
    share = sz["expert_share"] if share is None else share
    return routed_part(u, p, sz, share) + shared_part(u, p)


MIXERS = {"K": kda_mixer, "L": attention,
          "D": lambda u, p, sz, control=None:
          gated(u, p["ffn.w1"], p["ffn.w2"]),
          "G": lambda u, p, sz, control=None: gated_moe(u, p, sz)}


@functools.lru_cache(maxsize=None)
def _block(kind, frozen, control):
    sz = dict(frozen)

    def block(x, leaves):
        p = _held(leaves, control)
        u = rms_norm(x, p["norm.w"], sz["norm_eps"])
        return x + MIXERS[kind](u, p, sz, control)
    return jax.jit(block)


@functools.lru_cache(maxsize=None)
def _ends(frozen, control):
    sz = dict(frozen)

    def embed(leaves, toks):
        return _held({"w": leaves["word_emb"]}, control)["w"][toks]

    def head(x, leaves):
        p = _held({k: leaves[k] for k in ("final_norm.w", "lm_head.w")},
                  control)
        return _mm(rms_norm(x, p["final_norm.w"], sz["norm_eps"]),
                   p["lm_head.w"])
    return jax.jit(embed), jax.jit(head)


# -- what run.served_numbers asks for ---------------------------------------

def params(cfg, seed, control=None):
    """The seed, not the leaves: `logits` makes a sub-layer's leaves,
    uses them and drops them."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"no control {control!r}: {CONTROLS}")
    return {"seed": int(seed), "control": control}


def logits(cfg, p, tokens, control=None):
    """Logits [len(tokens), vocab] of one sequence, padded at its end to
    the next multiple of `reference_positions` so that few compiled
    shapes serve every request: under causality the padding changes
    nothing before it."""
    if (control or p["control"]) != p["control"]:
        raise ValueError("params and logits disagree on the control")
    control = p["control"]
    sz = sizes(cfg)
    frozen = _freeze(sz)
    n = len(tokens)
    step = int(sz["reference_positions"])
    padded = np.zeros(-(-n // step) * step, np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        embed, head = _ends(frozen, control)
        ends = global_leaves(sz, p["seed"])
        x = embed(ends, jnp.asarray(padded))
        for i, kind in enumerate(sz["pattern"]):
            x = _block(kind, frozen, control)(
                x, layer_leaves(sz, p["seed"], i))
        return np.asarray(head(x, ends))[:n]
