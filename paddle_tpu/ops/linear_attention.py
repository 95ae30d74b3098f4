"""Kimi Delta Attention: gated delta-rule linear attention, the mixer of
a decoder whose per-slot memory is a MATRIX a head and not a cache row
a token.

A head keeps a state `S` [keys, values] in float32. A token decays it a
key CHANNEL at a time, takes out what the state already predicts for
its key, and writes the rest back:

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T,        o_t = S_t^T q_t

`kda_mixer` is one whole mixer as the serving engine steps it: the
query/key/value projections, each behind its own causal depthwise
convolution, L2-normalised queries and keys, the per-channel decay and
per-head write strength, the recurrence, a gated per-head RMSNorm and
the out-projection, with the convolution window and the state of every
slot carried in and out, for T = 1 (decode) and T = block_size (a
prefill chunk) alike, by `mamba2_mixer`'s rules for fresh, muted and
partly valid rows.

The chunk form reads the carried state once and writes it once. With
`G_t` the running sum of `g = log alpha` inside the chunk and `gamma_t
= exp(G_t)`: `A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] -
G_s[c])` for s < t; `(I + A) U = beta (V - (K gamma) S_0)`; `o_t =
S_0^T (gamma_t q_t) + sum_{s<=t} (sum_c q_t[c] k_s[c] exp(G_t[c] -
G_s[c])) u_s`; `S_C = Diag(gamma_C) S_0 + sum_s (k_s gamma_C / gamma_s)
u_s^T`. Every exponent is a difference of a later from an earlier
running sum, masked to that order BEFORE it is taken: none exceeds 0.

The state, the decay, `beta`, the L2 norms and the solve are float32
whatever the activations are, and every product that touches the state
is exact float32 (the MXU's default would round the state to bfloat16
where it is read); the projections take their operands as they come
(bfloat16 weights and activations accumulate in float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .state_space import _conv_window, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6
ROWS_A_PASS = 8       # rows of a prefill step the mixer takes at once


def l2_normalise(x):
    """x / |x| over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_step(q, k, v, g, beta, state):
    """One token. q, k, v, g [B, H, K], beta [B, H], state [B, H, K, V],
    all float32. Both reads of the decayed state (what it predicts for
    the key, what it gives the query) are one pass over it; the
    query's read of the NEW state follows from them:
    `S_t^T q = S'^T q + (k . q) delta`."""
    decayed = jnp.exp(g)[..., None] * state
    seen = jnp.sum(decayed * k[..., None], axis=-2)            # S'^T k
    read = jnp.sum(decayed * q[..., None], axis=-2)            # S'^T q
    delta = beta[..., None] * (v - seen)
    new = decayed + k[..., None] * delta[..., None, :]
    out = read + jnp.sum(k * q, -1, keepdims=True) * delta
    return out, new


def kda_scan(q, k, v, g, beta, state):
    """The recurrence a token at a time over a chunk: q, k, v, g
    [B, H, T, K], beta [B, H, T]. The state is read and written once a
    TOKEN; `kda_chunk` is the form the mixer runs."""
    def step(s, inp):
        out, s = kda_step(*inp, s)
        return s, out
    seq = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta))
    new, out = jax.lax.scan(step, state, seq)
    return jnp.moveaxis(out, 0, 2), new


def _unit_lower_inverse(a):
    """(I + a)^-1 for `a` [..., T, T] strictly lower triangular, by
    forward substitution a row at a time: with X = I + N, N[i] = -a[i]
    - sum_{j<i} a[i, j] N[j]. Elementwise float32: nothing rounds."""
    t = a.shape[-1]
    rows = [-a[..., 0, :]]
    for i in range(1, t):
        prev = jnp.stack(rows, axis=-2)                         # [.., i, T]
        rows.append(-a[..., i, :] - jnp.sum(
            a[..., i, :i, None] * prev, axis=-2))
    return jnp.stack(rows, axis=-2) + jnp.eye(t, dtype=a.dtype)


def kda_chunk(q, k, v, g, beta, state):
    """The closed form over a chunk of T tokens from the carried state,
    which is read once and written once. q, k, v, g [B, H, T, K], beta
    [B, H, T], state [B, H, K, V]; float32. Returns (o [B, H, T, V], the
    state after the chunk)."""
    t = q.shape[2]
    run = jnp.cumsum(g, axis=2)                                 # G_t
    order = jnp.tril(jnp.ones((t, t), bool))                    # s <= t
    # keys then queries against the keys, in ONE reduction over the
    # channels: the [T, T, K] decays live inside it and nowhere else
    rows = jnp.concatenate([k, q], axis=2)
    run2 = jnp.concatenate([run, run], axis=2)
    order2 = jnp.concatenate([order, order], axis=0)
    exponent = jnp.where(order2[:, :, None],
                         run2[:, :, :, None, :] - run[:, :, None, :, :],
                         -jnp.inf)
    pairs = jnp.sum(rows[:, :, :, None, :] * k[:, :, None, :, :]
                    * jnp.exp(exponent), axis=-1)               # [B,H,2T,T]
    kk, qk = pairs[:, :, :t], pairs[:, :, t:]
    strict = jnp.tril(jnp.ones((t, t), bool), -1)
    inv = _unit_lower_inverse(
        jnp.where(strict, beta[..., None] * kk, 0.0))
    # what the carried state gives the decayed keys and queries: one
    # product, one read of the state
    gamma = jnp.exp(run)
    through = jnp.einsum("bhtk,bhkv->bhtv",
                         jnp.concatenate([k * gamma, q * gamma], axis=2),
                         state, precision=HIGHEST)
    u = jnp.einsum("bhts,bhsv->bhtv", inv,
                   beta[..., None] * (v - through[:, :, :t]),
                   precision=HIGHEST)
    out = through[:, :, t:] + jnp.einsum("bhts,bhsv->bhtv", qk, u,
                                         precision=HIGHEST)
    tail = jnp.exp(run[:, :, -1:] - run)              # gamma_C / gamma_s
    new = gamma[:, :, -1, :, None] * state + jnp.einsum(
        "bhtk,bhtv->bhkv", k * tail, u, precision=HIGHEST)
    return out, new


def kda_gates(u, w, valid):
    """(g = log alpha [B, T, H, K] <= 0, beta [B, T, H] in (0, 1)),
    float32, both 0 where a token is not valid: decay 1, nothing
    written."""
    b, t, _ = u.shape
    h = w["a_log"].shape[0]
    low = jnp.matmul(u, w["f1"], preferred_element_type=jnp.float32)
    dt = jnp.matmul(low, w["f2"].astype(jnp.float32), precision=HIGHEST) \
        + w["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(w["a_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(dt).reshape(b, t, h, -1)
    beta = jax.nn.sigmoid(jnp.matmul(u, w["b"],
                                     preferred_element_type=jnp.float32))
    return (jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0))


def _mixer_rows(u, w, conv_state, kda_state, start, nvalid, eps):
    """`kda_mixer` over the rows it is given, all in one pass."""
    bsz, t, _ = u.shape
    _, h, hk, _ = kda_state.shape
    inner = h * hk
    qkv = jnp.concatenate(
        [jnp.matmul(u, w[name], preferred_element_type=jnp.float32)
         for name in ("q", "k", "v")], axis=-1)
    fresh = start == 0
    qkv, conv_new = _conv_window(qkv, conv_state, w["conv_w"],
                                 jnp.zeros((), jnp.float32), fresh, nvalid)

    def heads(x):                                     # [B, H, T, K]
        return x.reshape(bsz, t, h, hk).transpose(0, 2, 1, 3)
    q = l2_normalise(heads(qkv[..., :inner])) * hk ** -0.5
    k = l2_normalise(heads(qkv[..., inner:2 * inner]))
    v = heads(qkv[..., 2 * inner:])
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < nvalid[:, None]
    g, beta = kda_gates(u, w, valid)
    g, beta = g.transpose(0, 2, 1, 3), beta.transpose(0, 2, 1)
    s0 = jnp.where(fresh[:, None, None, None], 0.0, kda_state)
    if t == 1:
        o, s1 = kda_step(q[:, :, 0], k[:, :, 0], v[:, :, 0], g[:, :, 0],
                         beta[:, :, 0], s0)
        o = o[:, :, None]
    else:
        o, s1 = kda_chunk(q, k, v, g, beta, s0)
    # a muted row (every slot the step does not advance is fed
    # start 0, n_valid 0) keeps both states as they were
    live = nvalid > 0
    kda_new = jnp.where(live[:, None, None, None], s1, kda_state)
    conv_new = jnp.where(live[:, None, None], conv_new, conv_state)
    gate = jnp.matmul(
        jnp.matmul(u, w["g1"], preferred_element_type=jnp.float32
                   ).astype(u.dtype),
        w["g2"], preferred_element_type=jnp.float32)
    o = rms_norm(o.transpose(0, 2, 1, 3), w["o_norm"], eps) \
        * jax.nn.sigmoid(gate).reshape(bsz, t, h, hk)
    out = jnp.matmul(o.reshape(bsz, t, inner).astype(u.dtype), w["o"],
                     preferred_element_type=jnp.float32).astype(u.dtype)
    return out, conv_new, kda_new


def kda_mixer(u, w, conv_state, kda_state, start, nvalid, *, eps):
    """One KDA mixer over a chunk `u` [B, T, d] of each row's sequence.
    `w`: q, k, v [d, H K], conv_w [3 H K, taps], f1 [d, K], f2 [K, H K],
    a_log [H], dt_bias [H K], b [d, H], g1 [d, K], g2 [K, H K], o_norm
    [K], o [H K, d]. `conv_state` [B, taps - 1, 3 H K] and `kda_state`
    [B, H, K, K] are row b's own (row = slot). A row with `start == 0`
    starts from zero state and a zero window; a row with `nvalid == 0`
    gets both back untouched; tokens at t >= nvalid leave the state
    alone and the window moves by `nvalid`. Returns (out [B, T, d],
    conv_state, kda_state).

    A decode step (T = 1) advances nearly every row, and takes them all
    in one pass. A prefill step feeds a page to the few slots still
    reading their prompts and mutes the rest: there the rows that
    advance are taken `ROWS_A_PASS` at a time, first to last, by a loop
    of as many passes as they need, and a muted row is neither computed
    nor its state read or written (its output is zero)."""
    b, t, _ = u.shape
    if t == 1:
        return _mixer_rows(u, w, conv_state, kda_state, start, nvalid, eps)
    r = math.gcd(b, ROWS_A_PASS)
    live = nvalid > 0
    order = jnp.argsort(~live, stable=True)           # live rows first

    def one_pass(i, carry):
        out, conv, state = carry
        rows = jax.lax.dynamic_slice_in_dim(order, i * r, r)
        # the last pass may reach past the live rows: `_mixer_rows`
        # hands a muted row's states back as they were
        o, c, s = _mixer_rows(u[rows], w, conv[rows], state[rows],
                              start[rows], nvalid[rows], eps)
        return out.at[rows].set(o), conv.at[rows].set(c), \
            state.at[rows].set(s)

    passes = (jnp.sum(live.astype(jnp.int32)) + r - 1) // r
    return jax.lax.fori_loop(0, passes, one_pass,
                             (jnp.zeros_like(u), conv_state, kda_state))


@register_op("kda_mixer",
             nondiff_inputs=("ConvState", "KdaState", "StartPos", "NValid"))
def _kda_mixer_op(ctx, ins, attrs):
    """Program-IR face of `kda_mixer`: X [B, T, d]; Q, K, V, ConvW, F1,
    F2, ALog, DtBias, B, G1, G2, ONorm, O; ConvState / KdaState the
    per-slot persistables (row b of the batch is slot b); StartPos,
    NValid [B] as `paged_attention` takes them."""
    w = {"q": ins["Q"][0], "k": ins["K"][0], "v": ins["V"][0],
         "conv_w": ins["ConvW"][0], "f1": ins["F1"][0], "f2": ins["F2"][0],
         "a_log": ins["ALog"][0], "dt_bias": ins["DtBias"][0],
         "b": ins["B"][0], "g1": ins["G1"][0], "g2": ins["G2"][0],
         "o_norm": ins["ONorm"][0], "o": ins["O"][0]}
    out, conv_new, kda_new = kda_mixer(
        ins["X"][0], w, ins["ConvState"][0], ins["KdaState"][0],
        ins["StartPos"][0].astype(jnp.int32),
        ins["NValid"][0].astype(jnp.int32),
        eps=float(attrs.get("epsilon", 1e-5)))
    return {"Out": [out], "ConvStateOut": [conv_new],
            "KdaStateOut": [kda_new]}
