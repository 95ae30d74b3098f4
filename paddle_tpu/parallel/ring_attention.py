"""Ring attention: sequence/context parallelism for long sequences.

Absent from the 2019 reference (SURVEY.md §2.7 'not present') — its sequence
story was LoD ragged tensors. Here long context is first-class: Q/K/V are
sharded over the sequence axis of the mesh; each device holds one sequence
chunk and K/V blocks rotate around the ring via lax.ppermute (XLA
CollectivePermute over ICI), overlapping transfer with the block-attention
compute. Softmax is combined across blocks with the online log-sum-exp
merge, so the result is bit-comparable to full attention.

Layers on jax shard_map; usable three ways:
- `ring_attention(q, k, v, axis_name=...)` inside an existing shard_map;
- `ring_attention_sharded(q, k, v, mesh, axis)` — wraps itself in
  shard_map over global arrays (what the `ring_attention` op lowering
  uses, nestable under the Executor's jit);
- the `ring_attention` op in a Program (ops registered below).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _masked_scores(q, k_blk, sm_scale, q_off, k_off, causal):
    """Scaled qk^T scores with the causal mask applied — shared by the
    forward block attention and the blockwise ring backward so the two
    can never desynchronize."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = q.shape[2], k_blk.shape[2]
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, NEG_INF)
    return s


def _block_attn(q, k, v, sm_scale, q_off, k_off, causal, live=None):
    """Attention of local q against one k/v block, returning (o, lse).
    q: [b, h, tq, d]; k/v: [b, h, tk, d]. `live` (optional [tk] bool)
    masks padded keys out of the block softmax."""
    s = _masked_scores(q, k, sm_scale, q_off, k_off, causal)
    if live is not None:
        s = jnp.where(live[None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # avoid -inf - -inf
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-20)
    o = jnp.einsum("bhqk,bhkd->bhqd", (p / l).astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    lse = m + jnp.log(l)
    return o, lse  # o normalised within the block; merge by lse weights


def _lse_merge(o, lse, o_i, lse_i):
    """Online softmax merge over the union of seen keys — the single
    home for this math (used by the ring forward and the ulysses
    blockwise path; the ring backward recomputes from saved lse)."""
    new_lse = jnp.logaddexp(lse, lse_i)
    o = (o * jnp.exp(lse - new_lse).astype(o.dtype)
         + o_i * jnp.exp(lse_i - new_lse).astype(o.dtype))
    return o, new_lse


def _ring_fwd_loop(q, k, v, axis_name, causal, sm_scale):
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[2]
    q_off = idx * t_local
    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(i, carry):
        o, lse, kv = carry
        k_blk, v_blk = kv
        src = (idx - i) % n  # whose chunk we hold at step i
        k_off = src * t_local
        o_i, lse_i = _block_attn(q, k_blk, v_blk, sm_scale, q_off, k_off,
                                 causal)
        o, new_lse = _lse_merge(o, lse, o_i, lse_i)
        kv = jax.lax.ppermute((k_blk, v_blk), axis_name, perm)
        return o, new_lse, kv

    b, h, t, d = q.shape
    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    lse0 = jnp.full((b, h, t, 1), NEG_INF, jnp.float32)
    o, lse, _ = jax.lax.fori_loop(0, n, step, (o0, lse0, (k, v)))
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring(q, k, v, axis_name, causal, sm_scale):
    o, _ = _ring_fwd_loop(q, k, v, axis_name, causal, sm_scale)
    return o


def _ring_vjp_fwd(q, k, v, axis_name, causal, sm_scale):
    o, lse = _ring_fwd_loop(q, k, v, axis_name, causal, sm_scale)
    # after n rotations k/v are home again: residuals are the originals
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, sm_scale, res, do):
    """FlashAttention-2-style blockwise backward around the ring: each
    step recomputes p = exp(s - lse) for the currently-held k/v chunk,
    accumulates dq locally, and accumulates dk/dv into buffers that
    ROTATE WITH the chunk — after the full ring the buffers land back on
    the chunk's owner. All dots take bf16 operands with f32 accumulation
    (a custom-vjp backward is safe from jax's dot-transpose f32
    poisoning; see ops/math.py:_mul)."""
    q, k, v, o, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[2]
    q_off = idx * t_local
    perm = [(j, (j + 1) % n) for j in range(n)]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [b, h, tq, 1]

    def step(i, carry):
        dq, kv, dkv = carry
        k_blk, v_blk = kv
        dk_acc, dv_acc = dkv
        src = (idx - i) % n
        k_off = src * t_local
        s = _masked_scores(q, k_blk, sm_scale, q_off, k_off, causal)
        p = jnp.exp(s - lse)                       # [b, h, tq, tk] f32
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        ds_l = ds.astype(q.dtype)
        p_l = p.astype(q.dtype)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds_l, k_blk,
                             preferred_element_type=jnp.float32)
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds_l, q,
                                     preferred_element_type=jnp.float32)
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p_l, do,
                                     preferred_element_type=jnp.float32)
        kv, dkv = jax.lax.ppermute(
            ((k_blk, v_blk), (dk_acc, dv_acc)), axis_name, perm)
        return dq, kv, dkv

    b, h, t, d = q.shape
    zeros = jnp.zeros((b, h, t, d), jnp.float32)
    dq, _, (dk, dv) = jax.lax.fori_loop(
        0, n, step, (zeros, (k, v), (zeros, zeros)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Inside shard_map: q,k,v are the LOCAL sequence chunks
    [b, h, t_local, d]. Returns local attention output chunk.
    Differentiable via a blockwise ring backward (custom vjp)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if not isinstance(sm_scale, (int, float)):
        # custom_vjp nondiff args must be static; fail with the contract
        # spelled out instead of a ConcretizationTypeError deep inside
        raise TypeError(
            "ring_attention: sm_scale must be a static python number "
            f"(got {type(sm_scale).__name__}); close over the value "
            "instead of passing it as a traced array")
    return _ring(q, k, v, axis_name, causal, float(sm_scale))


def ring_attention_sharded(q, k, v, mesh, seq_axis, causal=False,
                           sm_scale=None, batch_axis=None):
    """Global [b, h, T, d] arrays -> shard_map over the mesh seq axis
    (+ optional batch axis on dim 0)."""
    spec = P(batch_axis, None, seq_axis, None)

    fn = functools.partial(ring_attention, axis_name=seq_axis,
                           causal=causal, sm_scale=sm_scale)
    sm = jax.shard_map(lambda q_, k_, v_: fn(q_, k_, v_), mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec,
                       check_vma=False)
    return sm(q, k, v)


# ---------------------------------------------------------------------------
# Program-IR op
# ---------------------------------------------------------------------------

def seq_parallel_attention_op(sharded_fn):
    """Shared Program-IR op body for the sequence-parallel attention
    schemes (ring / Ulysses): attrs parsing, single-device flash
    fallback (also used when the mesh lacks the seq axis — the inputs
    are then unsharded on it, so exact attention is the same math),
    and graceful batch-axis degradation."""

    def _op(ctx, ins, attrs):
        q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
        seq_axis = attrs.get("seq_axis", "sp")
        if ctx.mesh is None or seq_axis not in ctx.mesh.axis_names:
            from ..ops.pallas.flash_attention import flash_attention
            return {"Out": [flash_attention(
                q, k, v, causal=attrs.get("causal", False),
                sm_scale=attrs.get("sm_scale"))]}
        batch_axis = attrs.get("batch_axis", "dp")
        if batch_axis not in ctx.mesh.axis_names:
            batch_axis = None
        out = sharded_fn(
            q, k, v, ctx.mesh, seq_axis,
            causal=attrs.get("causal", False),
            sm_scale=attrs.get("sm_scale"), batch_axis=batch_axis)
        return {"Out": [out]}
    return _op


_ring_attention_op = seq_parallel_attention_op(ring_attention_sharded)


def _register():
    from ..core.registry import register_op
    register_op("ring_attention")(_ring_attention_op)


_register()
