"""The two recurrent mixers in a prefill step: `mamba2_mixer` and
`kda_mixer` take only the rows that advance, a few at a time
(`state_space.rows_in_passes`), and their result is the one pass over
every row that each mixer's own per-rows function makes; a muted row
is neither computed nor touched. At the small sizes of the two models'
rehearsals."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.configs import kimi_linear_ep8_l8_reference as kda_ref
from benchmark.configs import nemotron3_super_ep4_l11_reference as mamba_ref
from paddle_tpu.ops import linear_attention, state_space

SEED = 2**31 + 40
T = 16


def sizes(cell, ref):
    _, cfg, _, _ = manifest.cell(cell, rehearsal=True)
    return ref.sizes(cfg)


def f32_leaves(ref, sz, i):
    return {k: v.astype(jnp.float32)
            for k, v in ref.layer_leaves(sz, SEED, i).items()}


def mamba2():
    """(width, window shape, state shape, the mixer, its per-rows
    function) of layer 1 (an `M`) of the hybrid's rehearsal."""
    sz = sizes("nemotron3_super_ep4_l11.batch_reason", mamba_ref)
    p = f32_leaves(mamba_ref, sz, 1)
    w = {"in_proj": p["mixer.in_proj.w"], "conv_w": p["mixer.conv.w"],
         "conv_b": p["mixer.conv.b"], "dt_bias": p["mixer.dt_bias"],
         "a_log": p["mixer.A_log"], "d": p["mixer.D"],
         "norm_w": p["mixer.norm.w"], "out_proj": p["mixer.out_proj.w"]}
    _, conv_c, _ = mamba_ref.mamba_dims(sz)
    g, eps = sz["n_groups"], sz["norm_eps"]
    return (sz["hidden_size"], (sz["conv_kernel"] - 1, conv_c),
            (sz["mamba_num_heads"], sz["mamba_head_dim"],
             sz["ssm_state_size"]),
            lambda x, c, s, st, n: state_space.mamba2_mixer(
                x, w, c, s, st, n, groups=g, eps=eps),
            lambda x, c, s, st, n: state_space._mixer_rows(
                x, w, c, s, st, n, g, eps))


def kda():
    """The same of layer 0 (a `K`) of Kimi-Linear's rehearsal."""
    sz = sizes("kimi_linear_ep8_l8.batch_rollout", kda_ref)
    p = f32_leaves(kda_ref, sz, 0)
    w = {"q": p["kda.q.w"], "k": p["kda.k.w"], "v": p["kda.v.w"],
         "conv_w": p["kda.conv.w"], "f1": p["kda.f1.w"],
         "f2": p["kda.f2.w"], "a_log": p["kda.A_log"],
         "dt_bias": p["kda.dt_bias"], "b": p["kda.b.w"],
         "g1": p["kda.g1.w"], "g2": p["kda.g2.w"],
         "o_norm": p["kda.o_norm.w"], "o": p["kda.o.w"]}
    h, hk = sz["kda_num_heads"], sz["kda_head_dim"]
    eps = sz["norm_eps"]
    return (sz["hidden_size"], (sz["short_conv_kernel_size"] - 1, 3 * h * hk),
            (h, hk, hk),
            lambda x, c, s, st, n: linear_attention.kda_mixer(
                x, w, c, s, st, n, eps=eps),
            lambda x, c, s, st, n: linear_attention._mixer_rows(
                x, w, c, s, st, n, eps))


def live_rows(b, count, rng):
    """`count` of `b` rows: a middle one, the first and the last as
    far as `count` goes, the rest scattered."""
    ends = [b // 2, 0, b - 1][:count] if count < b else list(range(b))
    rest = [i for i in range(b) if i not in ends]
    return sorted(ends + rng.choice(rest, count - len(ends),
                                    replace=False).tolist())


@pytest.mark.parametrize("b,count", [(24, 0), (24, 1), (24, 9), (24, 24),
                                     (12, 5)])
@pytest.mark.parametrize("mixer", [mamba2, kda])
def test_the_passes_over_live_rows_are_one_pass_over_all(mixer, b, count):
    """A T = 16 step of `b` rows of which `count` advance (B = 12 takes
    passes of gcd(12, 8) = 4 rows): the looped step against the
    per-rows function over every row. The live rows agree to float32
    rounding; a muted row's window and state come back bitwise and its
    output is zero. A decode step (one pass over all rows) after either
    gives the same result."""
    d, conv_shape, state_shape, looped, one_pass = mixer()
    rng = np.random.default_rng(b * 100 + count)
    live = live_rows(b, count, rng)
    muted = [i for i in range(b) if i not in live]
    u = jnp.asarray(rng.normal(size=(b, T, d)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(b,) + conv_shape), jnp.float32)
    state = jnp.asarray(rng.normal(size=(b,) + state_shape), jnp.float32)
    # a live row starts fresh or carries on, over a whole page or a
    # part of one; a muted row is fed start 0, n_valid 0
    start = np.zeros(b, np.int32)
    nvalid = np.zeros(b, np.int32)
    for j, i in enumerate(live):
        start[i] = (j % 2) * 32
        nvalid[i] = T if j % 3 else 5
    start, nvalid = jnp.asarray(start), jnp.asarray(nvalid)
    got = jax.jit(looped)(u, conv, state, start, nvalid)
    want = jax.jit(one_pass)(u, conv, state, start, nvalid)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g)[live], np.asarray(w_)[live],
                                   rtol=1e-5, atol=1e-5)
    out, conv1, state1 = (np.asarray(x) for x in got)
    assert (out[muted] == 0).all()
    np.testing.assert_array_equal(conv1[muted], np.asarray(conv)[muted])
    np.testing.assert_array_equal(state1[muted], np.asarray(state)[muted])
    # then a decode step of every row from either path's states
    x = jnp.asarray(rng.normal(size=(b, 1, d)), jnp.float32)
    start, nvalid = jnp.full(b, 48, jnp.int32), jnp.ones(b, jnp.int32)
    step = jax.jit(looped)
    after_loop = step(x, got[1], got[2], start, nvalid)
    after_one = step(x, want[1], want[2], start, nvalid)
    for g, w_ in zip(after_loop, after_one):
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-5)
    for g, w_ in zip(after_loop, jax.jit(one_pass)(x, got[1], got[2], start,
                                                   nvalid)):
        np.testing.assert_array_equal(g, w_)
