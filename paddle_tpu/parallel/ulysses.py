"""Ulysses-style all-to-all sequence parallelism.

The second long-context scheme next to [[ring attention]]
(parallel/ring_attention.py): instead of rotating K/V blocks around a
ring, TWO all-to-alls re-partition the work — the first trades the
sequence sharding for a HEAD sharding (each device receives the full
sequence for h/sp of the heads), exact local attention runs per head
group, and the second all-to-all restores the sequence sharding.

Communication is 2 x all-to-all of the activations (O(b·t·d/sp) per
device over ICI) vs the ring's (sp-1) k/v ppermutes; attention math is
exact in both. Requires sp | n_heads.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name="sp", causal=False,
                      sm_scale=None):
    """Inside shard_map: q/k/v are LOCAL sequence chunks
    [b, h, t_local, d] with h divisible by the axis size. Returns the
    local output chunk [b, h, t_local, d]."""
    n = jax.lax.axis_size(axis_name)
    h = q.shape[1]
    if h % n:
        raise ValueError(
            f"ulysses_attention: heads ({h}) must divide by the "
            f"sequence-parallel degree ({n}); use ring attention for "
            f"head counts below the mesh axis size")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])

    def scatter_heads(x):
        # [b, h, t/n, d] -> [b, h/n, t, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def gather_heads(x):
        # [b, h/n, t, d] -> [b, h, t/n, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qf, kf, vf = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    # exact attention over the full sequence for the local head group,
    # computed blockwise over K/V (online log-sum-exp merge) so per-
    # device memory is O(T·block), not the O(T^2) score matrix — dense
    # softmax would OOM at exactly the long-context lengths sequence
    # parallelism targets. Math shared with the ring scheme (positions
    # are global after the scatter, so offsets are 0).
    o = _blockwise_full_attn(qf, kf, vf, sm_scale, causal)
    return gather_heads(o)


def _blockwise_full_attn(q, k, v, sm_scale, causal, block_k=512):
    """Exact attention of q against the FULL k/v, scanning k/v in
    blocks with the same online-lse merge as the ring forward
    (ring_attention._block_attn). q/k/v: [b, h, T, d]."""
    from .ring_attention import NEG_INF, _block_attn

    t = k.shape[2]
    if t <= block_k:
        o, _ = _block_attn(q, k, v, sm_scale, 0, 0, causal)
        return o.astype(q.dtype)
    nb = -(-t // block_k)
    pad = nb * block_k - t
    if pad:
        # padded keys are masked out of the merge via -inf scores
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        kp, vp = k, v

    from .ring_attention import _lse_merge

    def step(i, carry):
        o, lse = carry
        k_blk = jax.lax.dynamic_slice_in_dim(kp, i * block_k, block_k, 2)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, i * block_k, block_k, 2)
        # mask padded keys out of the final block's softmax
        live = (i * block_k + jnp.arange(block_k) < t) if pad else None
        o_i, lse_i = _block_attn(q, k_blk, v_blk, sm_scale, 0,
                                 i * block_k, causal, live=live)
        return _lse_merge(o, lse, o_i, lse_i)

    b, h, tq, d = q.shape
    o0 = jnp.zeros((b, h, tq, d), jnp.float32)
    lse0 = jnp.full((b, h, tq, 1), NEG_INF, jnp.float32)
    o, _ = jax.lax.fori_loop(0, nb, step, (o0, lse0))
    return o.astype(q.dtype)


def ulysses_attention_sharded(q, k, v, mesh, seq_axis, causal=False,
                              sm_scale=None, batch_axis=None):
    """Global [b, h, T, d] arrays -> shard_map over the mesh seq axis
    (same contract as ring_attention_sharded)."""
    spec = P(batch_axis, None, seq_axis, None)
    fn = functools.partial(ulysses_attention, axis_name=seq_axis,
                           causal=causal, sm_scale=sm_scale)
    sm = jax.shard_map(lambda q_, k_, v_: fn(q_, k_, v_), mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return sm(q, k, v)


# ---------------------------------------------------------------------------
# Program-IR op (same contract as the ring_attention op)
# ---------------------------------------------------------------------------

def _register():
    from ..core.registry import register_op
    from .ring_attention import seq_parallel_attention_op
    register_op("ulysses_attention")(
        seq_parallel_attention_op(ulysses_attention_sharded))


_register()
