"""Latent attention around the paged pool, YaRN rotary positions and
the gated FFN: the ops of a decoder whose cache row is a latent.

A token's cache row is `[c_kv | k_rope]`: the normalised key/value
latent (`kv_rank` numbers) and ONE rotated key (`rope_dim`) for all
heads. `mla_project` makes that row and the queries against it in the
ABSORBED form: head h's no-position query goes through the key half of
`W_kvb` into the latent space, so that its score is a plain dot with
the row and `paged_attention` (latent pool) needs no per-head keys.
`mla_output` takes the attention's latent outputs through the value
half of `W_kvb` and `W_o`. Identical to the per-head form in exact
arithmetic (tests/test_latent_model.py).

Norm statistics and the rotation are float32; matrix products take
their operands as they come (bfloat16 with float32 accumulation).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register_op
from .state_space import rms_norm


def yarn_inv_freq(dim, theta, factor, original, beta_fast, beta_slow):
    """[dim / 2] float64 rotation frequencies under YaRN: pair i turns
    at `theta^(-2i/dim)` where it makes more than `beta_fast` turns
    over the `original` positions, at 1 / `factor` of that where it
    makes fewer than `beta_slow`, and a linear blend between."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def pair_of(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim // 2 - 1)
    keep = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * (1.0 - keep) + f * keep


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_sm_scale(head_width, factor, mscale_all_dim):
    """The softmax scale of scores over keys `head_width` wide."""
    return head_width ** -0.5 * yarn_mscale(factor, mscale_all_dim) ** 2


def rotary(x, positions, inv_freq, amplitude=1.0):
    """Rotate the pairs (x[2i], x[2i+1]) of the last axis by
    `positions * inv_freq[i]`. x [B, T, ..., dim], positions [B, T]."""
    angle = positions.astype(jnp.float32)[..., None] * \
        jnp.asarray(inv_freq, jnp.float32)
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3)
                          + angle.shape[-1:])
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _yarn(attrs, dim):
    """(inv_freq, cos/sin amplitude) from an op's rope attributes."""
    factor = float(attrs.get("factor", 1.0))
    inv = yarn_inv_freq(dim, float(attrs["theta"]), factor,
                        int(attrs.get("original", 1)),
                        float(attrs.get("beta_fast", 32)),
                        float(attrs.get("beta_slow", 1)))
    return inv, yarn_mscale(factor, float(attrs.get("mscale", 1.0))) \
        / yarn_mscale(factor, float(attrs.get("mscale_all_dim", 0.0)))


def _positions(start, t):
    return start.astype(jnp.int32)[:, None] + \
        jnp.arange(t, dtype=jnp.int32)[None, :]


def _mm(x, w):
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def mla_project(u, w, start, heads, nope, rope, inv_freq, amplitude, eps):
    """u [B, T, d] -> (queries [B, H, T, kv_rank + rope], the tokens'
    cache rows [B, T, kv_rank + rope]). `w`: q_a [d, q_rank], q_norm
    [q_rank], q_b [q_rank, H (nope + rope)], kv_a [d, kv_rank + rope],
    kv_norm [kv_rank], kv_b [kv_rank, H (nope + v)]. A model without a
    query latent has no q_a and no q_norm, and its q_b [d, H (nope +
    rope)] takes `u` itself; with `inv_freq` None nothing is rotated
    (a model that takes its positions from other layers)."""
    b, t, _ = u.shape
    dt = u.dtype
    pos = None if inv_freq is None else _positions(start, t)
    rank = w["kv_norm"].shape[0]

    def turn(x):
        return x if pos is None else rotary(x, pos, inv_freq, amplitude)
    c_q = u if w.get("q_a") is None else \
        rms_norm(_mm(u, w["q_a"]).astype(dt), w["q_norm"], eps)
    q = _mm(c_q, w["q_b"]).astype(dt).reshape(b, t, heads, nope + rope)
    q_rope = turn(q[..., nope:])
    # absorption: q_nope through the key half of W_kvb, a head at a time
    w_k = w["kv_b"].reshape(rank, heads, -1)[:, :, :nope]
    q_lat = jnp.einsum("bthn,lhn->bthl", q[..., :nope], w_k,
                       preferred_element_type=jnp.float32).astype(dt)
    queries = jnp.concatenate([q_lat, q_rope], -1).transpose(0, 2, 1, 3)
    kv = _mm(u, w["kv_a"]).astype(dt)
    row = jnp.concatenate(
        [rms_norm(kv[..., :rank], w["kv_norm"], eps),
         turn(kv[..., rank:])], -1)
    return queries, row


def mla_output(o_lat, kv_b, w_o, nope):
    """o_lat [B, H, T, kv_rank] -> [B, T, d]: each head's latent output
    through the value half of `kv_b`, the heads side by side through
    `w_o`."""
    b, h, t, rank = o_lat.shape
    w_v = kv_b.reshape(rank, h, -1)[:, :, nope:]
    o = jnp.einsum("bhtl,lhv->bthv", o_lat, w_v,
                   preferred_element_type=jnp.float32).astype(o_lat.dtype)
    return _mm(o.reshape(b, t, -1), w_o).astype(o_lat.dtype)


def gated_ffn(x, w1, w2):
    """`(silu(x W_gate) * x W_up) W_down`: gate and up side by side in
    w1 [d, 2 f], w2 [f, d]."""
    h = _mm(x, w1)
    f = h.shape[-1] // 2
    h = (jax.nn.silu(h[..., :f]) * h[..., f:]).astype(x.dtype)
    return _mm(h, w2).astype(x.dtype)


@register_op("yarn_rotary", nondiff_inputs=("StartPos",))
def _yarn_rotary_op(ctx, ins, attrs):
    """X [B, T, ..., dim], token t of row b at StartPos[b] + t."""
    x = ins["X"][0]
    inv, amp = _yarn(attrs, x.shape[-1])
    return {"Out": [rotary(x, _positions(ins["StartPos"][0], x.shape[1]),
                           inv, amp)]}


@register_op("mla_project", nondiff_inputs=("StartPos",))
def _mla_project_op(ctx, ins, attrs):
    w = {"q_b": ins["QB"][0], "kv_a": ins["KVA"][0],
         "kv_norm": ins["KVNorm"][0], "kv_b": ins["KVB"][0]}
    if ins.get("QA"):
        w.update(q_a=ins["QA"][0], q_norm=ins["QNorm"][0])
    rope = int(attrs["rope_dim"])
    # no rotary attributes: a latent attention without positions
    inv, amp = _yarn(attrs, rope) if "theta" in attrs else (None, 1.0)
    q, row = mla_project(ins["X"][0], w, ins["StartPos"][0],
                         int(attrs["heads"]), int(attrs["nope_dim"]), rope,
                         inv, amp, float(attrs.get("epsilon", 1e-5)))
    return {"Q": [q], "Row": [row]}


@register_op("mla_output")
def _mla_output_op(ctx, ins, attrs):
    return {"Out": [mla_output(ins["X"][0], ins["KVB"][0], ins["WO"][0],
                               int(attrs["nope_dim"]))]}


@register_op("gated_ffn")
def _gated_ffn_op(ctx, ins, attrs):
    return {"Out": [gated_ffn(ins["X"][0], ins["W1"][0], ins["W2"][0])]}
