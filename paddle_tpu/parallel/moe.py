"""Expert parallelism: a switch-style MoE FFN sharded over the `ep`
mesh axis.

Absent from the 2019 reference (its scale story was PS sharding +
NCCL data parallelism); here expert parallelism is a first-class mesh
axis alongside dp/tp/pp/sp. Expert weights live sharded over `ep`
(each device holds E/ep experts); every device computes its local
experts' contribution for all tokens and a psum over `ep` combines
them — the dense-dispatch formulation, exact and static-shape. The
capacity-based sparse all-to-all dispatch is the optimization on top;
at equal expert count it changes cost, not numerics.

Gating is top-1 (Switch Transformer): the selected expert's output is
scaled by its softmax probability, so the router is trained through
the prob factor while the hard selection is a stop-gradient mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "moe_ffn_sharded", "moe_ffn_sparse",
           "moe_ffn_sparse_sharded", "init_moe_params"]


def init_moe_params(rng, n_experts, d_model, d_ff, dtype=jnp.float32):
    """{gate_w [d, E], w1 [E, d, f], b1 [E, f], w2 [E, f, d], b2 [E, d]}"""
    import numpy as np
    r = np.random.RandomState(rng)
    s1 = (2.0 / d_model) ** 0.5
    s2 = (2.0 / d_ff) ** 0.5
    return {
        "gate_w": jnp.asarray(
            r.randn(d_model, n_experts).astype(np.float32) * 0.02, dtype),
        "w1": jnp.asarray(
            r.randn(n_experts, d_model, d_ff).astype(np.float32) * s1,
            dtype),
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "w2": jnp.asarray(
            r.randn(n_experts, d_ff, d_model).astype(np.float32) * s2,
            dtype),
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _route_top1(x, gate_w, e_global):
    """Top-1 switch routing, shared by every formulation: returns
    (probs [.., E], coef [.., E] = prob on the selected expert under a
    stop-grad mask, load = mean top-1 prob)."""
    logits = jnp.einsum("btd,de->bte", x, gate_w)
    probs = jax.nn.softmax(logits, axis=-1)
    mask = jax.nn.one_hot(jnp.argmax(probs, -1), e_global,
                          dtype=probs.dtype)
    coef = probs * jax.lax.stop_gradient(mask)
    return probs, coef, jnp.mean(jnp.max(probs, axis=-1))


def _expert_eval_all(x, params):
    """Every expert over every token: [B, E, T, d] outputs (the dense
    formulation's compute; also the exact single-device evaluation)."""
    h = jnp.einsum("btd,edf->betf", x, params["w1"]) \
        + params["b1"][None, :, None, :]
    h = jax.nn.gelu(h)
    return jnp.einsum("betf,efd->betd", h, params["w2"]) \
        + params["b2"][None, :, None, :]


def moe_ffn(x, params, axis_name="ep", n_experts_global=None,
            batch_axis=None):
    """Inside shard_map: x [B, T, d] (replicated or dp-sharded on B);
    params' expert arrays hold the LOCAL expert shard [E_local, ...];
    gate_w is replicated [d, E_global]. Returns y [B, T, d] (summed
    over the ep axis) and the router's mean top-1 prob (a load metric).
    """
    gate_w = params["gate_w"]
    w1, b1 = params["w1"], params["b1"]
    w2, b2 = params["w2"], params["b2"]
    e_local = w1.shape[0]
    e_global = n_experts_global or gate_w.shape[-1]
    idx = jax.lax.axis_index(axis_name)

    _, coef, local_load = _route_top1(x, gate_w, e_global)

    # local slice of the combine coefficients
    start = idx * e_local
    coef_local = jax.lax.dynamic_slice_in_dim(coef, start, e_local,
                                              axis=-1)  # [B, T, E_local]

    # every local expert computes all tokens; combine weighted
    out = _expert_eval_all(x, params)  # extra gate_w key is unused
    y = jnp.einsum("betd,bte->btd", out, coef_local)
    y = jax.lax.psum(y, axis_name)
    load = jax.lax.pmean(local_load, axis_name)
    if batch_axis is not None:
        # the metric is declared replicated (out_specs P()): reduce over
        # the batch axis too so every shard returns the GLOBAL mean
        load = jax.lax.pmean(load, batch_axis)
    return y, load


def _moe_shard_map(inner, x, params, mesh, ep_axis, batch_axis,
                   seq_axis=None, **kw):
    """Shared shard_map wrapper for the dense and sparse formulations:
    one place owns the spec layout (expert arrays sharded on dim 0 over
    ep, gate replicated, x optionally batch- and/or sequence-sharded).

    seq_axis composes MoE with sequence parallelism (dp x sp x ep):
    routing and expert compute are per-token, so sharding T changes
    which tokens each shard routes, not the math; only the load metric
    needs the extra pmean to stay global."""
    x_spec = P(batch_axis, seq_axis, None)
    param_specs = {"gate_w": P(None, None),
                   "w1": P(ep_axis, None, None), "b1": P(ep_axis, None),
                   "w2": P(ep_axis, None, None), "b2": P(ep_axis, None)}
    reduce_axes = tuple(a for a in (batch_axis, seq_axis) if a)
    fn = functools.partial(inner, axis_name=ep_axis,
                           n_experts_global=params["gate_w"].shape[-1],
                           batch_axis=reduce_axes or None, **kw)
    sm = jax.shard_map(fn, mesh=mesh, in_specs=(x_spec, param_specs),
                   out_specs=(x_spec, P()), check_vma=False)
    return sm(x, params)


def moe_ffn_sharded(x, params, mesh, ep_axis="ep", batch_axis=None,
                    seq_axis=None):
    """Global arrays -> shard_map over the mesh: expert arrays sharded
    on dim 0 over `ep_axis`, x replicated (or batch-sharded over
    `batch_axis` / sequence-sharded over `seq_axis`), output matching
    x."""
    return _moe_shard_map(moe_ffn, x, params, mesh, ep_axis, batch_axis,
                          seq_axis=seq_axis)


def moe_ffn_sparse(x, params, axis_name="ep", capacity=None,
                   n_experts_global=None, batch_axis=None):
    """Capacity-based sparse dispatch (the performance formulation):
    instead of every expert computing every token, tokens are packed
    into per-expert capacity buffers and exchanged with two all-to-alls
    over `ep`, so each expert computes only (up to) ep * capacity
    tokens. Tokens beyond an expert's capacity are DROPPED (output 0 +
    residual upstream), the standard Switch trade; capacity defaults to
    2x the even-load share. Numerics match moe_ffn exactly whenever no
    token is dropped (capacity >= tokens routed per expert).

    x [B, T, d] local; expert params local shards as in moe_ffn.
    Returns (y [B, T, d], load metric)."""
    gate_w = params["gate_w"]
    w1, b1 = params["w1"], params["b1"]
    w2, b2 = params["w2"], params["b2"]
    e_local = w1.shape[0]
    e_global = n_experts_global or gate_w.shape[-1]
    n_shards = jax.lax.axis_size(axis_name)
    b, t, d = x.shape
    n = b * t
    if capacity is None:
        capacity = max(1, (2 * n + e_global - 1) // e_global)

    xt = x.reshape(n, d)
    logits = xt @ gate_w                                # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top = jnp.argmax(probs, axis=-1)                    # [N]
    coef = jnp.take_along_axis(probs, top[:, None], axis=-1)[:, 0]

    onehot = jax.nn.one_hot(top, e_global, dtype=jnp.int32)  # [N, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1       # [N, E]
    pos = jnp.max(pos, axis=-1)                         # [N] slot in expert
    keep = pos < capacity

    # dispatch buffers [E, C, d]: scatter kept tokens
    disp = jnp.zeros((e_global, capacity, d), x.dtype)
    safe_e = jnp.where(keep, top, 0)
    safe_p = jnp.where(keep, pos, 0)
    contrib = jnp.where(keep[:, None], xt, 0.0)
    disp = disp.at[safe_e, safe_p].add(contrib)

    # exchange: [ep, E_local, C, d] -> each shard holds its experts'
    # buffers from EVERY shard: [E_local, ep*C, d]
    disp = disp.reshape(n_shards, e_local, capacity, d)
    recv = jax.lax.all_to_all(disp, axis_name, split_axis=0,
                              concat_axis=2, tiled=True)
    recv = recv.reshape(e_local, n_shards * capacity, d)

    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", recv, w1)
                    + b1[:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]

    # exchange back: [E_local, ep, C, d] -> [E(=ep*E_local), C, d]
    out = out.reshape(e_local, n_shards, capacity, d)
    back = jax.lax.all_to_all(out, axis_name, split_axis=1,
                              concat_axis=0, tiled=True)
    back = back.reshape(e_global, capacity, d)

    y = back[safe_e, safe_p] * coef[:, None]
    y = jnp.where(keep[:, None], y, 0.0)
    load = jax.lax.pmean(jnp.mean(jnp.max(probs, axis=-1)), axis_name)
    if batch_axis is not None:
        load = jax.lax.pmean(load, batch_axis)
    return y.reshape(b, t, d), load


def moe_ffn_sparse_sharded(x, params, mesh, ep_axis="ep", capacity=None,
                           batch_axis=None, seq_axis=None):
    """Global-array wrapper for moe_ffn_sparse (same specs as
    moe_ffn_sharded)."""
    return _moe_shard_map(moe_ffn_sparse, x, params, mesh, ep_axis,
                          batch_axis, seq_axis=seq_axis, capacity=capacity)


# ---------------------------------------------------------------------------
# Program-IR op + fluid.layers front-end
# ---------------------------------------------------------------------------

def _moe_ffn_op(ctx, ins, attrs):
    """Program-IR face: inputs X [B,T,d], GateW [d,E], W1 [E,d,f],
    B1 [E,f], W2 [E,f,d], B2 [E,d]. With a mesh carrying the `ep` axis
    the sharded (dense or capacity-sparse) formulation runs; otherwise
    a single-device dense evaluation with identical routing math."""
    x = ins["X"][0]
    params = {"gate_w": ins["GateW"][0], "w1": ins["W1"][0],
              "b1": ins["B1"][0], "w2": ins["W2"][0], "b2": ins["B2"][0]}
    ep_axis = attrs.get("ep_axis", "ep")
    if ctx.mesh is not None and ep_axis in ctx.mesh.axis_names:
        batch_axis = attrs.get("batch_axis", "dp")
        if batch_axis not in ctx.mesh.axis_names:
            batch_axis = None
        if attrs.get("capacity"):
            y, load = moe_ffn_sparse_sharded(
                x, params, ctx.mesh, ep_axis=ep_axis,
                capacity=attrs["capacity"], batch_axis=batch_axis)
        else:
            y, load = moe_ffn_sharded(x, params, ctx.mesh,
                                      ep_axis=ep_axis,
                                      batch_axis=batch_axis)
        return {"Out": [y], "Load": [load]}
    # single-device exact evaluation: the SAME routing/expert helpers
    # the sharded formulations use
    e = params["gate_w"].shape[-1]
    _, coef, load = _route_top1(x, params["gate_w"], e)
    out = _expert_eval_all(x, params)
    y = jnp.einsum("betd,bte->btd", out, coef)
    return {"Out": [y], "Load": [load]}


def _register():
    from ..core.registry import register_op
    register_op("moe_ffn", nondiff_outputs=("Load",))(_moe_ffn_op)


_register()
