"""Plain reference of `nemotron3_super_ep4_l11` as the cell serves it:
one causal forward pass over a prompt with its served tokens, float32 at
`highest`, giving the logits at every position. No cache, no kernels:
the whole sequence at once, a sequential scan for the state-space
layers, every held expert over every token under a mask.

The equations (`u` is a block's input after its RMSNorm, eps 1e-5):

    block l   x <- x + Mixer_l(RMSNorm(x; w_l));  after the last block a
              final RMSNorm, logits = x . W_head. No position encoding.
    M         [z | xBC | dt] = u . W_in; xBC <- silu(causal depthwise
              conv, kernel 4, + bias) -> x [H, P], B [G, N], C [G, N];
              dt <- softplus(dt + dt_bias); A = -exp(A_log);
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;
              y_t = S_t . C_t + D x_t;  head h reads group h // (H / G);
              y <- GroupRMSNorm(y . silu(z); w) over G groups; out = y . W_out
    *         q = u W_q (H heads), k, v = u W_k, u W_v (KV heads), causal
              softmax(q k^T / sqrt(hd)) v, each KV head serving H / KV
              query heads, then W_o
    E         s = sigmoid(u . W_r) (float32); the top_k experts by s + b;
              w_i = scale . s_i / sum_selected s_j;  l = u . W_down;
              f_e(l) = relu(l W1_e)^2 W2_e;
              out = (sum_i w_i f_{e_i}(l)) . W_up + relu(u V1)^2 V2,
              the sum over the selected experts THIS chip holds only
              (share k holds experts [k E_held, (k + 1) E_held)).

Weights are made here from `--seed`, a layer at a time, in the type
the configuration holds them in (bfloat16 for matrices, convolution and
norm scales; float32 for A_log, D, dt_bias and the selection bias), and
computed with in float32: at the published widths a layer's leaves are
made, used and dropped (128 experts in float32 are 2.8 GB), which is
why `params` hands back the seed and not the leaves. The program's
family takes its weights from `layer_leaves` / `global_leaves` too, so
both sides hold the same numbers and share nothing else.

Controls (the nearest precisions below what the configuration states):
`ssm_bfloat16` keeps the state-space state in bfloat16 between tokens,
`weights_int8` rounds every matrix to int8 (one scale a leaf).
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
CONTROLS = ("ssm_bfloat16", "weights_int8")
BF16, F32 = "bfloat16", "float32"


def sizes(cfg):
    """The flat sizes the reference, the family and the work model read,
    under the configuration file's own key names where it has one."""
    dep = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return {
        "hidden_size": cfg["hidden_size"],
        "vocab_size": cfg["vocab_size"],
        "pattern": cfg["hybrid_override_pattern"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "num_attention_heads": cfg["num_attention_heads"],
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "mamba_num_heads": cfg["mamba_num_heads"],
        "mamba_head_dim": cfg["mamba_head_dim"],
        "ssm_state_size": cfg["ssm_state_size"],
        "n_groups": cfg["n_groups"],
        "conv_kernel": cfg["conv_kernel"],
        "router_width": dep["n_routed_experts_published"],
        "experts_held": held,
        "expert_share": dep["expert_share"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "moe_latent_size": cfg["moe_latent_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "moe_shared_expert_intermediate_size":
            cfg["moe_shared_expert_intermediate_size"],
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "norm_eps": float(cfg["norm_eps"]),
        "time_step_min": float(cfg["time_step_min"]),
        "time_step_max": float(cfg["time_step_max"]),
        "time_step_floor": float(cfg["time_step_floor"]),
        "max_seq": cfg["engine"]["max_seq"],
        "reference_positions": cfg["assumed"]["reference_positions"],
    }


def _freeze(sz):
    return tuple(sorted(sz.items()))


def mamba_dims(sz):
    """(inner channels, conv channels, in_proj width) of a mixer."""
    inner = sz["mamba_num_heads"] * sz["mamba_head_dim"]
    conv = inner + 2 * sz["n_groups"] * sz["ssm_state_size"]
    return inner, conv, inner + conv + sz["mamba_num_heads"]


# -- the leaves ------------------------------------------------------------

def layer_table(sz, kind):
    """[(leaf name inside `layer_<i>.`, shape, how it is drawn, type)]
    of one block of `kind` (a letter of the pattern)."""
    d = sz["hidden_size"]
    rows = [("norm.w", (d,), "scale", BF16)]
    if kind == "M":
        inner, conv, proj = mamba_dims(sz)
        h = sz["mamba_num_heads"]
        rows += [("mixer.in_proj.w", (d, proj), "matrix", BF16),
                 ("mixer.conv.w", (conv, sz["conv_kernel"]), "conv", BF16),
                 ("mixer.conv.b", (conv,), "conv", BF16),
                 ("mixer.dt_bias", (h,), "dt_bias", F32),
                 ("mixer.A_log", (h,), "a_log", F32),
                 ("mixer.D", (h,), "ones", F32),
                 ("mixer.norm.w", (inner,), "scale", BF16),
                 ("mixer.out_proj.w", (inner, d), "matrix", BF16)]
    elif kind == "*":
        hq = sz["num_attention_heads"] * sz["head_dim"]
        hkv = sz["num_key_value_heads"] * sz["head_dim"]
        rows += [("att.q.w", (d, hq), "matrix", BF16),
                 ("att.k.w", (d, hkv), "matrix", BF16),
                 ("att.v.w", (d, hkv), "matrix", BF16),
                 ("att.o.w", (hq, d), "matrix", BF16)]
    elif kind == "E":
        lat, mid = sz["moe_latent_size"], sz["moe_intermediate_size"]
        sh, eh = sz["moe_shared_expert_intermediate_size"], \
            sz["experts_held"]
        rows += [("moe.router.w", (d, sz["router_width"]), "matrix", BF16),
                 ("moe.router.bias", (sz["router_width"],), "select", F32),
                 ("moe.down.w", (d, lat), "matrix", BF16),
                 ("moe.w1", (eh, lat, mid), "matrix", BF16),
                 ("moe.w2", (eh, mid, lat), "matrix", BF16),
                 ("moe.up.w", (lat, d), "matrix", BF16),
                 ("moe.shared.w1", (d, sh), "matrix", BF16),
                 ("moe.shared.w2", (sh, d), "matrix", BF16)]
    else:
        raise ValueError(f"no layer kind {kind!r} in the pattern")
    return rows


def global_table(sz):
    d, v = sz["hidden_size"], sz["vocab_size"]
    return [("word_emb", (v, d), "matrix", BF16),
            ("final_norm.w", (d,), "scale", BF16),
            ("lm_head.w", (d, v), "matrix", BF16)]


def _draw(key, shape, how, sz):
    if how == "matrix":
        return STD * jax.random.normal(key, shape, jnp.float32)
    if how == "scale":
        return 1.0 + STD * jax.random.normal(key, shape, jnp.float32)
    if how == "select":
        return STD * jax.random.normal(key, shape, jnp.float32)
    if how == "conv":
        # the framework default of the source's depthwise convolution:
        # uniform within 1/sqrt(kernel); the Linear deviation 0.02 would
        # leave silu(conv) near 0 and the state unread
        bound = 1.0 / math.sqrt(sz["conv_kernel"])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if how == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0))
    if how == "dt_bias":
        lo, hi = math.log(sz["time_step_min"]), math.log(sz["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, sz["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))     # inverse softplus
    if how == "ones":
        return jnp.ones(shape, jnp.float32)
    raise ValueError(how)


@functools.lru_cache(maxsize=None)
def _maker(table, frozen):
    sz = dict(frozen)

    def make(key):
        return {name: _draw(jax.random.fold_in(key, j), shape, how,
                            sz).astype(dtype)
                for j, (name, shape, how, dtype) in enumerate(table)}
    return jax.jit(make)


def layer_leaves(sz, seed, i):
    """{leaf name: array in the type the configuration holds it in} of
    block `i`: one jitted call a kind of layer."""
    key = jax.random.fold_in(_weights.seed_key(seed), i + 1)
    table = tuple(layer_table(sz, sz["pattern"][i]))
    return _maker(table, _freeze(sz))(key)


def global_leaves(sz, seed):
    key = jax.random.fold_in(_weights.seed_key(seed), 0)
    return _maker(tuple(global_table(sz)), _freeze(sz))(key)


# -- the layers, one sequence [T, d], float32 --------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _held(leaves, control):
    """The leaves in float32, after the rounding a control adds."""
    out = {}
    for name, w in leaves.items():
        w = w.astype(jnp.float32)
        if control == "weights_int8" and w.ndim >= 2 \
                and not name.endswith("conv.w"):
            w = _fake_int8(w)   # one scale a leaf, as the other cells'
        out[name] = w
    return out


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mamba_mixer(u, p, sz, control=None):
    t = u.shape[0]
    h, hp = sz["mamba_num_heads"], sz["mamba_head_dim"]
    g, n, k = sz["n_groups"], sz["ssm_state_size"], sz["conv_kernel"]
    inner, conv_c, _ = mamba_dims(sz)
    zxbcdt = _mm(u, p["mixer.in_proj.w"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_c],
                  zxbcdt[:, inner + conv_c:])
    full = jnp.concatenate([jnp.zeros((k - 1, conv_c), u.dtype), xbc], 0)
    conv = p["mixer.conv.b"] + sum(
        full[j:j + t] * p["mixer.conv.w"][:, j] for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(t, h, hp)
    b = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n), h // g, 1)
    c = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), h // g, 1)
    dt = jax.nn.softplus(dt + p["mixer.dt_bias"])
    a = -jnp.exp(p["mixer.A_log"])
    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if control == "ssm_bfloat16":
            # bfloat16's 8 exponent and 7 mantissa bits, by an op the
            # compiler may not elide (it drops a cast there and back)
            s = jax.lax.reduce_precision(s, 8, 7)
        y = jnp.einsum("hpn,hn->hp", s, c_t, precision=HIGHEST) \
            + p["mixer.D"][:, None] * x_t
        return s, y

    _, y = jax.lax.scan(step, jnp.zeros((h, hp, n), jnp.float32),
                        (x, b, c, dt))
    y = y.reshape(t, inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(t, g, inner // g),
                 p["mixer.norm.w"].reshape(g, inner // g),
                 sz["norm_eps"]).reshape(t, inner)
    return _mm(y, p["mixer.out_proj.w"])


def attention(u, p, sz):
    t = u.shape[0]
    h, kv, hd = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    q = _mm(u, p["att.q.w"]).reshape(t, h, hd)
    k = jnp.repeat(_mm(u, p["att.k.w"]).reshape(t, kv, hd), h // kv, 1)
    v = jnp.repeat(_mm(u, p["att.v.w"]).reshape(t, kv, hd), h // kv, 1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v,
                     precision=HIGHEST)
    return _mm(ctx.reshape(t, h * hd), p["att.o.w"])


def route(u, p, sz):
    """(selected experts [T, k], their weights [T, k]) over the whole
    published router."""
    s = jax.nn.sigmoid(_mm(u, p["moe.router.w"]))
    _, sel = jax.lax.top_k(s + p["moe.router.bias"],
                           sz["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, sel, 1)
    return sel, sz["routed_scaling_factor"] * picked \
        / picked.sum(-1, keepdims=True)


def routed_part(u, p, sz, share):
    """What share `share`'s held experts add, in the latent space:
    sum over the selected experts e in [share E_held, (share+1) E_held)
    of w_e f_e(l). `p["moe.w1"]`/`["moe.w2"]` are THAT share's experts."""
    t = u.shape[0]
    eh = p["moe.w1"].shape[0]
    sel, w = route(u, p, sz)
    dense = jnp.zeros((t, sz["router_width"]), jnp.float32).at[
        jnp.arange(t)[:, None], sel].set(w)
    mine = jax.lax.dynamic_slice_in_dim(dense, share * eh, eh, 1)
    lat = _mm(u, p["moe.down.w"])

    def one(acc, e):
        w1, w2, we = e
        f = _mm(jnp.square(jax.nn.relu(_mm(lat, w1.astype(jnp.float32)))),
                w2.astype(jnp.float32))
        return acc + we[:, None] * f, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                          (p["moe.w1"], p["moe.w2"], mine.T))
    return acc


def shared_part(u, p):
    return _mm(jnp.square(jax.nn.relu(_mm(u, p["moe.shared.w1"]))),
               p["moe.shared.w2"])


def latent_moe(u, p, sz, share=None):
    share = sz["expert_share"] if share is None else share
    return _mm(routed_part(u, p, sz, share), p["moe.up.w"]) \
        + shared_part(u, p)


MIXERS = {"M": mamba_mixer, "*": lambda u, p, sz, control=None:
          attention(u, p, sz),
          "E": lambda u, p, sz, control=None: latent_moe(u, p, sz)}


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
    """The seed, not the leaves: `logits` makes a layer's leaves, uses
    them and drops them."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"no control {control!r}: {CONTROLS}")
    return {"seed": int(seed), "control": control}


def logits(cfg, p, tokens, control=None):
    """Logits [len(tokens), vocab] of one sequence, padded at its end to
    `reference_positions` (or the next multiple of 128 above a longer
    one) so that one compiled shape serves every request: under
    causality the padding changes nothing before it."""
    if (control or p["control"]) != p["control"]:
        raise ValueError("params and logits disagree on the control")
    control = p["control"]
    sz = sizes(cfg)
    frozen = _freeze(sz)
    n = len(tokens)
    room = max(int(sz["reference_positions"]), -(-n // 128) * 128)
    padded = np.zeros(room, np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        embed, head = _ends(frozen, control)
        ends = global_leaves(sz, p["seed"])
        x = embed(ends, jnp.asarray(padded))
        for i, kind in enumerate(sz["pattern"]):
            x = _block(kind, frozen, control)(
                x, layer_leaves(sz, p["seed"], i))
        return np.asarray(head(x, ends))[:n]
