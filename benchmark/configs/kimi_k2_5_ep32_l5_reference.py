"""Plain reference of `kimi_k2_5_ep32_l5` as the cell serves it: one
causal forward pass over a prompt with its served tokens, float32 at
`highest`, giving the logits at every position. No cache, no kernels,
no batching: a layer at a time over the whole sequence, attention in
the NON-absorbed (per-head keys and values) form over blocks of query
positions so that 9,216 positions fit, every held expert over every
token under a mask.

The equations (`u` is a sub-layer's input after its RMSNorm, eps 1e-5):

    layer l   x <- x + Attn(RMSNorm(x));  x <- x + FFN(RMSNorm(x)); the
              FFN of the first `first_k_dense_replace` layers is dense,
              of the others routed experts; after the last layer a final
              RMSNorm, logits = x . W_head. In the pattern string a
              layer is two letters: L D or L G.
    L         c_q = RMSNorm(u W_qa);  [q_nope | q_rope]_h = c_q W_qb;
              [c_kv | k_r] = u W_kva;  c_kv <- RMSNorm(c_kv);
              k_rope = RoPE(k_r), one for all heads;
              [k_nope | v]_h = c_kv W_kvb;
              s_h(t, j) = (q_nope,h(t) . k_nope,h(j)
                           + RoPE(q_rope,h(t)) . k_rope(j)) . scale,
              causal softmax, o_h = sum_j p v_h(j), out = concat_h(o_h) W_o
    RoPE      pairs (x[2i], x[2i+1]) turned by pos . inv_freq_i, YaRN:
              f_i = theta^(-2i/64); dim(r) = 64 ln(4096 / 2 pi r) / 2 ln
              theta; low = floor(dim(beta_fast)), high = ceil(dim(
              beta_slow)), in [0, 31]; m_i = 1 - clip((i - low) / (high
              - low), 0, 1); inv_freq_i = f_i / factor (1 - m_i) + f_i
              m_i; scale = 192^-0.5 (0.1 mscale_all_dim ln factor + 1)^2
    D         (silu(u W_gate) * u W_up) W_down
    G         s = sigmoid(u . W_r) (float32); the top_k experts by s + b;
              w_i = scale . s_i / sum_selected s_j; each expert and the
              shared expert a D at the expert width;
              out = shared(u) + sum_i w_i expert_{e_i}(u), the sum over
              the selected experts THIS chip holds only (share k holds
              experts [k E_held, (k + 1) E_held)).

Weights are made here from `--seed`, a sub-layer at a time, in the type
the configuration holds them in (bfloat16; float32 for the selection
bias, drawn at a tenth of the matrices' deviation) and computed with in
float32; `params` hands back the seed and
not the leaves. The program's family takes its weights from
`layer_leaves` / `global_leaves` too. Gate and up lie side by side in
one leaf (`w1` [.., d, 2 f]), as the program holds them.

Controls (the nearest precisions below what the configuration states):
`weights_int8` rounds every matrix to int8 (one scale a leaf),
`cache_int8` rounds the cache rows `[c_kv | k_rope]` of a sequence to
int8 (one scale a layer's rows, as a leaf has one) before keys and
values are made from them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as _weights
from benchmark.reference_layers import _fake_int8

HIGHEST = jax.lax.Precision.HIGHEST
STD = 0.02
# the selection bias: at the matrices' 0.02 it would move an expert's
# popularity by a factor of 2.3 (sigmoid scores saturate at the top 8 of
# 384: a step of 0.02 is 0.7 of a logit) and the work of a step with the
# seed; a trained bias of this family evens the load out
SELECT_STD = 0.002
CONTROLS = ("weights_int8", "cache_int8")
BF16, F32 = "bfloat16", "float32"
QUERY_BLOCK = 128     # query positions a block of the attention


def pattern(n_layers, first_dense):
    return "".join("L" + ("D" if i < first_dense else "G")
                   for i in range(n_layers))


def sizes(cfg):
    """The flat sizes the reference, the family and the work model read,
    under the configuration file's own key names where it has one."""
    dep = cfg["deployment"]
    return {
        "hidden_size": cfg["hidden_size"],
        "vocab_size": cfg["vocab_size"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "pattern": pattern(cfg["num_hidden_layers"],
                           cfg["first_k_dense_replace"]),
        "num_attention_heads": cfg["num_attention_heads"],
        "q_lora_rank": cfg["q_lora_rank"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "n_shared_experts": cfg["n_shared_experts"],
        "router_width": dep["n_routed_experts_published"],
        "experts_held": cfg["n_routed_experts"],
        "expert_share": dep["expert_share"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
        "rope_scaling": tuple(sorted(
            (k, v) for k, v in cfg["rope_scaling"].items() if k != "type")),
        "max_seq": cfg["engine"]["max_seq"],
        "reference_positions": cfg["assumed"]["reference_positions"],
    }


def _freeze(sz):
    return tuple(sorted(sz.items()))


def rope_attrs(sz):
    """The rotary attributes as the program's configuration takes them
    (`paddle_tpu/ops/latent_attention.py`)."""
    r = dict(sz["rope_scaling"])
    return {"theta": sz["rope_theta"], "factor": float(r["factor"]),
            "original": int(r["original_max_position_embeddings"]),
            "beta_fast": float(r["beta_fast"]),
            "beta_slow": float(r["beta_slow"]),
            "mscale": float(r["mscale"]),
            "mscale_all_dim": float(r["mscale_all_dim"])}


# -- the leaves ------------------------------------------------------------

def layer_table(sz, kind):
    """[(leaf name inside `layer_<i>.`, shape, how it is drawn, type)]
    of one sub-layer of `kind` (a letter of the pattern)."""
    d, h = sz["hidden_size"], sz["num_attention_heads"]
    rows = [("norm.w", (d,), "scale", BF16)]
    if kind == "L":
        qr, kr = sz["q_lora_rank"], sz["kv_lora_rank"]
        nope, rope, vd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
            sz["v_head_dim"]
        rows += [("att.q_a.w", (d, qr), "matrix", BF16),
                 ("att.q_norm.w", (qr,), "scale", BF16),
                 ("att.q_b.w", (qr, h * (nope + rope)), "matrix", BF16),
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


@functools.lru_cache(maxsize=None)
def _maker(table):
    def make(key):
        out = {}
        for j, (name, shape, how, dtype) in enumerate(table):
            x = (SELECT_STD if how == "select" else STD) * jax.random.normal(
                jax.random.fold_in(key, j), shape, jnp.float32)
            out[name] = (1.0 + x if how == "scale" else x).astype(dtype)
        return out
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

def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _held(leaves, control):
    """The leaves in float32, after the rounding a control adds."""
    out = {}
    for name, w in leaves.items():
        w = w.astype(jnp.float32)
        if control == "weights_int8" and w.ndim >= 2:
            w = _fake_int8(w)
        out[name] = w
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_inv_freq(sz):
    r = rope_attrs(sz)
    dim, theta = sz["qk_rope_head_dim"], r["theta"]
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def pair_of(turns):
        return dim * math.log(r["original"] / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(r["beta_fast"])), 0)
    high = min(math.ceil(pair_of(r["beta_slow"])), dim // 2 - 1)
    m = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / r["factor"] * (1.0 - m) + f * m


def sm_scale(sz):
    r = rope_attrs(sz)
    m = 0.1 * r["mscale_all_dim"] * math.log(r["factor"]) + 1.0 \
        if r["factor"] > 1 else 1.0
    return (sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, pos, sz):
    """x [T, ..., rope]: pairs (x[2i], x[2i+1]) turned by pos * f_i.
    mscale / mscale_all_dim scales cos and sin (1 as published)."""
    r = rope_attrs(sz)

    def ms(v):
        return 0.1 * v * math.log(r["factor"]) + 1.0 \
            if r["factor"] > 1 else 1.0
    amp = ms(r["mscale"]) / ms(r["mscale_all_dim"])
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(sz),
                                                         jnp.float32)
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def attention(u, p, sz, control=None):
    t = u.shape[0]
    h, kr = sz["num_attention_heads"], sz["kv_lora_rank"]
    nope, rp, vd = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], \
        sz["v_head_dim"]
    pos = jnp.arange(t, dtype=jnp.int32)
    c_q = rms_norm(_mm(u, p["att.q_a.w"]), p["att.q_norm.w"],
                   sz["norm_eps"])
    q = _mm(c_q, p["att.q_b.w"]).reshape(t, h, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], pos, sz)
    kv = _mm(u, p["att.kv_a.w"])
    c_kv = rms_norm(kv[:, :kr], p["att.kv_norm.w"], sz["norm_eps"])
    k_rope = rope(kv[:, kr:], pos, sz)
    if control == "cache_int8":
        row = _fake_int8(jnp.concatenate([c_kv, k_rope], -1))
        c_kv, k_rope = row[:, :kr], row[:, kr:]
    kvb = _mm(c_kv, p["att.kv_b.w"]).reshape(t, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = sm_scale(sz)
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def block(args):
        qn, qr, at = args
        s = (jnp.einsum("thd,shd->hts", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("thr,sr->hts", qr, k_rope, precision=HIGHEST)
             ) * scale
        s = jnp.where(pos[None, None, :] <= at[None, :, None], s, -jnp.inf)
        return jnp.einsum("hts,shv->thv", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    ctx = jax.lax.map(block, (q_nope.reshape(t // qb, qb, h, nope),
                              q_rope.reshape(t // qb, qb, h, rp),
                              pos.reshape(t // qb, qb)))
    return _mm(ctx.reshape(t, h * vd), p["att.o.w"])


def gated(u, w1, w2):
    g = _mm(u, w1)
    f = g.shape[-1] // 2
    return _mm(jax.nn.silu(g[:, :f]) * g[:, f:], w2)


def route(u, p, sz):
    """(selected experts [T, k], their weights [T, k]) over the whole
    published router: the bias selects and does not weigh."""
    s = jax.nn.sigmoid(_mm(u, p["moe.router.w"]))
    _, sel = jax.lax.top_k(s + p["moe.router.bias"],
                           sz["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, 1)
    return sel, sz["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)


def routed_part(u, p, sz, share):
    """What share `share`'s held experts add: the sum over the selected
    experts e in [share E_held, (share + 1) E_held) of w_e expert_e(u).
    `p["moe.w1"]` / `["moe.w2"]` are THAT share's experts."""
    t = u.shape[0]
    eh = p["moe.w1"].shape[0]
    sel, w = route(u, p, sz)
    dense = jnp.zeros((t, sz["router_width"]), jnp.float32).at[
        jnp.arange(t)[:, None], sel].set(w)
    mine = jax.lax.dynamic_slice_in_dim(dense, share * eh, eh, 1)

    def one(acc, e):
        w1, w2, we = e
        return acc + we[:, None] * gated(u, w1, w2), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (p["moe.w1"], p["moe.w2"], mine.T))
    return acc


def shared_part(u, p):
    return gated(u, p["moe.shared.w1"], p["moe.shared.w2"])


def gated_moe(u, p, sz, share=None):
    share = sz["expert_share"] if share is None else share
    return routed_part(u, p, sz, share) + shared_part(u, p)


MIXERS = {"L": attention,
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
