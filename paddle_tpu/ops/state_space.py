"""RMSNorm and the Mamba-2 mixer: the ops of a state-space decoder.

`rms_norm` is the pre-norm of every block, and, grouped and gated, the
norm inside the mixer. `mamba2_mixer` is one whole mixer as the serving
engine steps it: in-projection, causal depthwise convolution, the
selective scan, gated norm, out-projection, with the convolution window
and the SSM state of every slot carried in and out, for T = 1 (decode)
and T = block_size (a prefill chunk) alike. `rows_in_passes` is how a
recurrent mixer (this one and `linear_attention.kda_mixer`) takes a
step's rows: all at once in a decode step, only those that advance in
a prefill step.

Statistics, the scan and the state are float32 whatever the activations
are; matrix products take their operands as they come (bfloat16 weights
and activations accumulate in float32 on the MXU).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.registry import register_op

HIGHEST = jax.lax.Precision.HIGHEST
ROWS_A_PASS = 8       # rows of a prefill step a mixer takes at once


def rms_norm(x, scale, eps, groups=1, gate=None):
    """`x / sqrt(mean(x^2) + eps) * scale` over the last axis, in
    float32, returned in x's type. `gate` multiplies x by silu(gate)
    first; `groups` > 1 takes the mean over each of that many equal
    slices of the axis."""
    out_dtype = x.dtype
    x = x.astype(jnp.float32)
    if gate is not None:
        x = x * jax.nn.silu(gate.astype(jnp.float32))
    d = x.shape[-1]
    xg = x.reshape(x.shape[:-1] + (groups, d // groups))
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, -1, keepdims=True) + eps)
    return (xg.reshape(x.shape)
            * scale.astype(jnp.float32)).astype(out_dtype)


@register_op("rms_norm")
def _rms_norm_op(ctx, ins, attrs):
    gate = ins["Gate"][0] if ins.get("Gate") else None
    return {"Out": [rms_norm(ins["X"][0], ins["Scale"][0],
                             attrs.get("epsilon", 1e-5),
                             attrs.get("groups", 1), gate)]}


def _conv_window(xbc, conv_state, conv_w, conv_b, fresh, nvalid):
    """Causal depthwise convolution of a chunk behind each row's window
    of the last K-1 inputs. Returns (silu(conv) [B, T, C], the window
    moved on by `nvalid` inputs)."""
    k = conv_w.shape[1]
    t = xbc.shape[1]
    window = jnp.where(fresh[:, None, None], 0.0,
                       conv_state.astype(jnp.float32))
    full = jnp.concatenate([window, xbc], axis=1)          # [B, K-1+T, C]
    w = conv_w.astype(jnp.float32)
    conv = conv_b.astype(jnp.float32) + sum(
        full[:, j:j + t] * w[:, j] for j in range(k))
    at = nvalid[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    moved = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return jax.nn.silu(conv), moved.astype(conv_state.dtype)


def selective_scan(x, dt, a, b, c, d_skip, state):
    """The chunk form of the Mamba-2 recurrence, state read once and
    written once:

        S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t,  y_t = S_t C_t + D x_t

    x [B, T, H, P], dt [B, T, H] (0 where a token is not valid: decay 1,
    input 0), a [H], b and c [B, T, G, N] (head h reads group
    h // (H / G)), d_skip [H], state [B, H, P, N]; all float32. Returns
    (y [B, T, H, P], the state after the chunk). Every product that
    touches the state is exact float32: the MXU's default would round
    the state to bfloat16 where it is read."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    rep = h // g
    if t == 1:
        # one fused pass over the state: decay, add, read
        bh = jnp.repeat(b[:, 0], rep, axis=1)                # [B, H, N]
        ch = jnp.repeat(c[:, 0], rep, axis=1)
        dt0, x0 = dt[:, 0], x[:, 0]
        new = jnp.exp(dt0 * a)[:, :, None, None] * state \
            + (dt0[:, :, None] * x0)[:, :, :, None] * bh[:, :, None, :]
        y = jnp.sum(new * ch[:, :, None, :], axis=-1) \
            + d_skip[None, :, None] * x0
        return y[:, None], new
    la = jnp.cumsum(dt * a, axis=1)                          # [B, T, H]
    # within the chunk: y_t += sum_{s<=t} exp(la_t - la_s) dt_s (B_s.C_t) x_s
    cb = jnp.einsum("btgn,bsgn->btsg", c, b, precision=HIGHEST)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.where(causal,
                      jnp.exp(la[:, :, None, :] - la[:, None, :, :]), 0.0)
    mix = jnp.repeat(cb, rep, axis=3) * decay * dt[:, None, :, :]
    y = jnp.einsum("btsh,bshp->bthp", mix, x, precision=HIGHEST)
    # what the carried state adds: exp(la_t) S_0 C_t
    ch = jnp.repeat(c, rep, axis=2)                          # [B, T, H, N]
    y = y + jnp.exp(la)[..., None] * jnp.einsum(
        "bhpn,bthn->bthp", state, ch, precision=HIGHEST)
    y = y + d_skip[None, None, :, None] * x
    # the state after the chunk
    tail = jnp.exp(la[:, -1:, :] - la) * dt                  # [B, T, H]
    bh = jnp.repeat(b, rep, axis=2)
    new = jnp.exp(la[:, -1])[:, :, None, None] * state + jnp.einsum(
        "bthp,bthn->bhpn", tail[..., None] * x, bh, precision=HIGHEST)
    return y, new


def _rows_a_pass(batch):
    return math.gcd(batch, ROWS_A_PASS)


def rows_computed(batch, t, advancing):
    """The rows `rows_in_passes` computes in a step of `batch` rows of
    `t` tokens of which `advancing` are fed: all of them at t = 1, else
    the advancing rows rounded up to whole passes."""
    if t == 1:
        return batch
    r = _rows_a_pass(batch)
    return -(-advancing // r) * r


def rows_in_passes(mix_rows, u, conv_state, state, start, nvalid):
    """A recurrent mixer over a step's rows, by `mix_rows(u, conv_state,
    state, start, nvalid) -> (out, conv_state, state)`, which computes
    the rows it is given in one pass and hands a muted row's states
    back as they were.

    A decode step (T = 1) advances nearly every row, and takes them all
    in one pass. A prefill step feeds a page to the few slots still
    reading their prompts and mutes the rest: there the rows that
    advance are taken `ROWS_A_PASS` at a time (the largest divisor of it
    that divides the batch), first to last, by a loop of as many passes as they need,
    and a muted row is neither computed nor its state read or written
    (its output is zero)."""
    b, t, _ = u.shape
    if t == 1:
        return mix_rows(u, conv_state, state, start, nvalid)
    r = _rows_a_pass(b)
    live = nvalid > 0
    order = jnp.argsort(~live, stable=True)           # live rows first

    def one_pass(i, carry):
        out, conv, st = carry
        rows = jax.lax.dynamic_slice_in_dim(order, i * r, r)
        # the last pass may reach past the live rows: `mix_rows` hands
        # a muted row's states back as they were, and its output is
        # zeroed as a row outside every pass has it
        o, c, s = mix_rows(u[rows], conv[rows], st[rows], start[rows],
                           nvalid[rows])
        o = jnp.where(live[rows][:, None, None], o, 0)
        return out.at[rows].set(o), conv.at[rows].set(c), \
            st.at[rows].set(s)

    passes = (jnp.sum(live.astype(jnp.int32)) + r - 1) // r
    return jax.lax.fori_loop(0, passes, one_pass,
                             (jnp.zeros_like(u), conv_state, state))


def _mixer_rows(u, w, conv_state, ssm_state, start, nvalid, groups, eps):
    """`mamba2_mixer` over the rows it is given, all in one pass."""
    bsz, t, _ = u.shape
    _, h, p, n = ssm_state.shape
    inner = h * p
    conv_c = inner + 2 * groups * n
    zxbcdt = jnp.matmul(u, w["in_proj"],
                        preferred_element_type=jnp.float32)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:inner + conv_c]
    dt = zxbcdt[..., inner + conv_c:]
    fresh = start == 0
    xbc, conv_new = _conv_window(xbc, conv_state, w["conv_w"], w["conv_b"],
                                 fresh, nvalid)
    x = xbc[..., :inner].reshape(bsz, t, h, p)
    b = xbc[..., inner:inner + groups * n].reshape(bsz, t, groups, n)
    c = xbc[..., inner + groups * n:].reshape(bsz, t, groups, n)
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < nvalid[:, None]
    dt = jnp.where(valid[..., None],
                   jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32)),
                   0.0)
    a = -jnp.exp(w["a_log"].astype(jnp.float32))
    s0 = jnp.where(fresh[:, None, None, None], 0.0, ssm_state)
    y, s1 = selective_scan(x, dt, a, b, c, w["d"].astype(jnp.float32), s0)
    # a muted row (every slot the step does not advance is fed
    # start 0, n_valid 0) keeps both states as they were
    live = nvalid > 0
    ssm_new = jnp.where(live[:, None, None, None], s1, ssm_state)
    conv_new = jnp.where(live[:, None, None], conv_new, conv_state)
    y = rms_norm(y.reshape(bsz, t, inner), w["norm_w"], eps, groups,
                 gate=z).astype(u.dtype)
    out = jnp.matmul(y, w["out_proj"],
                     preferred_element_type=jnp.float32).astype(u.dtype)
    return out, conv_new, ssm_new


def mamba2_mixer(u, w, conv_state, ssm_state, start, nvalid, *, groups,
                 eps):
    """One Mamba-2 mixer over a chunk `u` [B, T, d] of each row's
    sequence. `w`: in_proj [d, 2 inner + 2 G N + H], conv_w [C, K],
    conv_b [C], dt_bias, a_log, d [H], norm_w [inner], out_proj
    [inner, d]. `conv_state` [B, K-1, C] and `ssm_state` [B, H, P, N]
    are row b's own (row = slot). A row with `start == 0` starts from
    zero state; a row with `nvalid == 0` gets its state back untouched
    (in a prefill step it is not computed at all: `rows_in_passes`);
    tokens at t >= nvalid leave the state alone and the window moves by
    `nvalid`. Returns (out [B, T, d], conv_state, ssm_state)."""
    return rows_in_passes(
        lambda x, conv, ssm, s, n: _mixer_rows(x, w, conv, ssm, s, n,
                                               groups, eps),
        u, conv_state, ssm_state, start, nvalid)


@register_op("mamba2_mixer",
             nondiff_inputs=("ConvState", "SsmState", "StartPos", "NValid"))
def _mamba2_mixer_op(ctx, ins, attrs):
    """Program-IR face of `mamba2_mixer`: X [B, T, d]; InProj, ConvW,
    ConvB, DtBias, ALog, D, NormW, OutProj; ConvState / SsmState the
    per-slot persistables (row b of the batch is slot b); StartPos,
    NValid [B] as `paged_attention` takes them."""
    w = {"in_proj": ins["InProj"][0], "conv_w": ins["ConvW"][0],
         "conv_b": ins["ConvB"][0], "dt_bias": ins["DtBias"][0],
         "a_log": ins["ALog"][0], "d": ins["D"][0],
         "norm_w": ins["NormW"][0], "out_proj": ins["OutProj"][0]}
    out, conv_new, ssm_new = mamba2_mixer(
        ins["X"][0], w, ins["ConvState"][0], ins["SsmState"][0],
        ins["StartPos"][0].astype(jnp.int32),
        ins["NValid"][0].astype(jnp.int32),
        groups=int(attrs["groups"]), eps=float(attrs.get("epsilon", 1e-5)))
    return {"Out": [out], "ConvStateOut": [conv_new],
            "SsmStateOut": [ssm_new]}
