"""Expert parallelism: mixture-of-experts layers sharded over the `ep`
mesh axis.

Absent from the 2019 reference (its scale story was PS sharding +
NCCL data parallelism); here expert parallelism is a first-class mesh
axis alongside dp/tp/pp/sp. Expert weights live sharded over `ep`
(each device holds E/ep experts). Three formulations share one router
(`route_top_k`) and, where tokens are grouped by expert, one evaluation
of the held experts (`experts_apply`):

* `moe_ffn`: dense dispatch. Every device computes its local experts
  for ALL tokens and a psum over `ep` combines them: exact, static
  shapes, cost tokens x experts. Top-1 (Switch Transformer): the
  selected expert's output is scaled by its softmax probability, so the
  router is trained through the prob factor while the hard selection is
  a stop-gradient mask.
* `moe_ffn_sparse`: top-1 with per-expert capacity buffers exchanged by
  two all-to-alls. Tokens past an expert's capacity are dropped: a
  trade of THAT formulation for static buffers, not of routed experts
  as such.
* `latent_moe`: top-k over a router of the model's full width with
  sigmoid scores, a selection bias and normalised, scaled weights;
  experts in a latent space beside a shared expert. The layer is TOLD
  which experts it holds (`share`), routes over all, sorts the
  selections that fell on its own experts by expert and evaluates them
  with grouped products whose cost follows the tokens routed: no
  capacity, and no token is ever dropped. What the experts of other
  shares would add is theirs to add: under an `ep` mesh axis a psum
  sums the shares, on one chip nothing stands in for them.
  `gated_moe` is the same layer without the latent projections and
  with gated experts (`(silu(u W_gate) * u W_up) W_down`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.pallas.grouped_matmul import grouped_matmul

__all__ = ["moe_ffn", "moe_ffn_sharded", "moe_ffn_sparse",
           "moe_ffn_sparse_sharded", "init_moe_params", "route_top_k",
           "experts_apply", "latent_moe", "latent_moe_sharded", "gated_moe",
           "gated_moe_sharded"]


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


def route_top_k(x, gate_w, k, score="softmax", select_bias=None,
                normalise=False, scale=1.0):
    """The router of every formulation. x [N, d], gate_w [d, E] ->
    (scores [N, E] float32, selected experts [N, k] int32, their
    weights [N, k] float32). `score` is softmax or sigmoid over the
    float32 logits; `select_bias` [E] is added for the SELECTION only
    and does not weigh; `normalise` divides the selected scores by
    their sum; `scale` multiplies the weights. The selection is
    discrete: gradients reach the router through the weights."""
    logits = jnp.matmul(x, gate_w, preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    pick = scores if select_bias is None \
        else scores + select_bias.astype(jnp.float32)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(pick), k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if normalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return scores, sel, w * scale


def experts_apply(rows, group_sizes, w1, w2, act, b1=None, b2=None):
    """The held experts over rows grouped by expert: `rows` [M, d_in]
    hold expert 0's rows first, then expert 1's, `group_sizes` [E_held]
    of each; rows past the groups' sum belong to no expert and their
    result is undefined (the caller masks it). w1 [E_held, d_in, f],
    w2 [E_held, f, d_out], optional biases [E_held, f] / [E_held,
    d_out]. Grouped products (`ops/pallas/grouped_matmul.py`): an
    expert with no row moves no weight, and what they cost follows the
    rows that are led, not M and not rows x experts. Returns
    [M, d_out] float32."""
    m = rows.shape[0]

    def bias(b):
        return jnp.repeat(b, group_sizes, axis=0, total_repeat_length=m)

    h = grouped_matmul(rows, w1, group_sizes)
    if b1 is not None:
        h = h + bias(b1)
    h = act(h).astype(rows.dtype)
    out = grouped_matmul(h, w2, group_sizes)
    return out if b2 is None else out + bias(b2)


def _route_top1(x, gate_w, e_global):
    """Top-1 switch routing of the dense formulations: returns
    (probs [.., E], coef [.., E] = prob on the selected expert, zero
    elsewhere, load = mean top-1 prob)."""
    b, t, d = x.shape
    probs, sel, w = route_top_k(x.reshape(b * t, d), gate_w, 1)
    coef = jax.nn.one_hot(sel[:, 0], e_global, dtype=probs.dtype) * w
    return (probs.reshape(b, t, e_global), coef.reshape(b, t, e_global),
            jnp.mean(w))


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
    probs, sel, w = route_top_k(xt, gate_w, 1)
    top, coef = sel[:, 0], w[:, 0]                      # [N]

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

    # every buffer whole: padding rows are computed and then unread
    out = experts_apply(
        recv.reshape(e_local * n_shards * capacity, d),
        jnp.full((e_local,), n_shards * capacity, jnp.int32),
        w1, w2, jax.nn.gelu, b1, b2).astype(x.dtype)

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
# latent experts: top-k over the full router, the held share computed
# ---------------------------------------------------------------------------

def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _swiglu(h):
    """Gate and up side by side: silu(first half) * second half."""
    f = h.shape[-1] // 2
    return jax.nn.silu(h[:, :f]) * h[:, f:]


def _routed_share(x2, rows_in, p, top_k, scale, share, valid, act,
                  axis_name):
    """What this share's held experts add for tokens `x2` [N, d]: route
    over the whole router, sort the selections that fell on held
    experts by expert, evaluate them over `rows_in` [N, d_in] (the
    experts' input a token) with grouped products, weigh and sum each
    token's. Returns (routed [N, d_out] float32, probe [4] int32)."""
    n = x2.shape[0]
    eh = p["w1"].shape[0]
    _, sel, w = route_top_k(x2, p["router_w"], top_k, score="sigmoid",
                            select_bias=p["router_bias"], normalise=True,
                            scale=scale)
    local = sel - share * eh
    held = (local >= 0) & (local < eh) & valid[:, None]
    # selections sorted by held expert; those of other shares go last,
    # in a group of their own that no product visits
    eid = jnp.where(held, local, eh).reshape(n * top_k)
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.zeros((eh + 1,), jnp.int32).at[eid].add(1)[:eh]
    f = experts_apply(rows_in[order // top_k], sizes, p["w1"], p["w2"], act)
    wf = jnp.where(held, w, 0.0).reshape(n * top_k)[order]
    f = jnp.where((eid[order] < eh)[:, None], f * wf[:, None], 0.0)
    # back to token order: a gather by the inverse permutation, then
    # each token's k selections summed
    back = jnp.zeros((n * top_k,), jnp.int32).at[order].set(
        jnp.arange(n * top_k, dtype=jnp.int32))
    routed = f[back].reshape(n, top_k, -1).sum(axis=1)
    hit = jnp.sum(sizes > 0).astype(jnp.int32)
    busiest = jnp.max(sizes)
    n_held = jnp.sum(sizes)
    if axis_name is not None:
        routed = jax.lax.psum(routed, axis_name)
        hit = jax.lax.psum(hit, axis_name)
        n_held = jax.lax.psum(n_held, axis_name)
        busiest = jax.lax.pmax(busiest, axis_name)
    probe = jnp.stack([jnp.sum(valid).astype(jnp.int32) * top_k,
                       n_held, hit, busiest])
    return routed, probe


def _shared_expert(x2, p, act):
    """The expert every share computes alike, float32 out."""
    h = act(jnp.matmul(x2, p["shared_w1"],
                       preferred_element_type=jnp.float32)).astype(x2.dtype)
    return jnp.matmul(h, p["shared_w2"], preferred_element_type=jnp.float32)


def _valid_tokens(b, t, n_valid):
    if n_valid is None:
        return jnp.ones((b * t,), bool)
    return (jnp.arange(t, dtype=jnp.int32)[None, :]
            < n_valid.astype(jnp.int32)[:, None]).reshape(b * t)


def latent_moe(x, p, top_k, scale, share=0, n_valid=None, axis_name=None):
    """x [B, T, d]; p: router_w [d, E], router_bias [E], down [d, L],
    w1 [E_held, L, f], w2 [E_held, f, L], up [L, d], shared_w1 [d, s],
    shared_w2 [s, d]. This shard holds experts [share E_held,
    (share + 1) E_held) of the router's E. `n_valid` [B] leaves tokens
    t >= n_valid[b] out (routed nowhere, counted nowhere; their output
    is the shared expert's and is don't-care). Inside shard_map,
    `axis_name` sums the shares' routed parts.

    Returns (out [B, T, d], probe [4] int32: selections made, those
    that fell on held experts, held experts with at least one token,
    tokens on the busiest held expert)."""
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    lat = jnp.matmul(x2, p["down"],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    routed, probe = _routed_share(x2, lat, p, top_k, scale, share,
                                  _valid_tokens(b, t, n_valid), _relu2,
                                  axis_name)
    out = jnp.matmul(routed.astype(x.dtype), p["up"],
                     preferred_element_type=jnp.float32) \
        + _shared_expert(x2, p, _relu2)
    return out.astype(x.dtype).reshape(b, t, d), probe


def gated_moe(x, p, top_k, scale, share=0, n_valid=None, axis_name=None):
    """`latent_moe` without the latent projections and with gated
    experts: p: router_w [d, E], router_bias [E], w1 [E_held, d, 2 f]
    (gate and up side by side), w2 [E_held, f, d], shared_w1 [d, 2 s],
    shared_w2 [s, d]; an expert is `(silu(u W_gate) * u W_up) W_down`.
    Told its share, counted and summed as `latent_moe` is."""
    b, t, d = x.shape
    x2 = x.reshape(b * t, d)
    routed, probe = _routed_share(x2, x2, p, top_k, scale, share,
                                  _valid_tokens(b, t, n_valid), _swiglu,
                                  axis_name)
    out = routed + _shared_expert(x2, p, _swiglu)
    return out.astype(x.dtype).reshape(b, t, d), probe


def _expert_shard_map(layer, x, p, mesh, top_k, scale, ep_axis, n_valid):
    """Global arrays -> shard_map: w1 / w2 sharded on their expert
    dimension over `ep_axis`, everything else replicated; shard i is
    share i, and a psum over the axis sums the shares."""
    specs = {k: P() for k in p}
    specs["w1"] = specs["w2"] = P(ep_axis, None, None)

    def inner(x, p, n_valid):
        return layer(x, p, top_k, scale, share=jax.lax.axis_index(ep_axis),
                     n_valid=n_valid, axis_name=ep_axis)

    if n_valid is None:
        n_valid = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    return jax.shard_map(inner, mesh=mesh, in_specs=(P(), specs, P()),
                         out_specs=(P(), P()), check_vma=False)(
        x, p, n_valid)


def latent_moe_sharded(x, p, mesh, top_k, scale, ep_axis="ep",
                       n_valid=None):
    return _expert_shard_map(latent_moe, x, p, mesh, top_k, scale, ep_axis,
                             n_valid)


def gated_moe_sharded(x, p, mesh, top_k, scale, ep_axis="ep", n_valid=None):
    return _expert_shard_map(gated_moe, x, p, mesh, top_k, scale, ep_axis,
                             n_valid)


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


def _expert_layer_op(layer, sharded, p, ctx, ins, attrs):
    n_valid = ins["NValid"][0] if ins.get("NValid") else None
    top_k, scale = int(attrs["top_k"]), float(attrs.get("scale", 1.0))
    ep_axis = attrs.get("ep_axis", "ep")
    if ctx.mesh is not None and ep_axis in ctx.mesh.axis_names:
        out, probe = sharded(ins["X"][0], p, ctx.mesh, top_k, scale,
                             ep_axis, n_valid)
    else:
        out, probe = layer(ins["X"][0], p, top_k, scale,
                           share=int(attrs.get("share", 0)),
                           n_valid=n_valid)
    return {"Out": [out], "Probe": [probe]}


def _latent_moe_op(ctx, ins, attrs):
    """Program-IR face of `latent_moe`: X [B, T, d], RouterW,
    RouterBias, Down, W1, W2, Up, SharedW1, SharedW2, optional NValid
    [B]; attrs top_k, scale, share, ep_axis. Outputs Out and Probe [4]
    int32. With the `ep` axis in the mesh the experts are sharded over
    it and the shares summed; otherwise this chip's `share` alone."""
    p = {"router_w": ins["RouterW"][0], "router_bias": ins["RouterBias"][0],
         "down": ins["Down"][0], "w1": ins["W1"][0], "w2": ins["W2"][0],
         "up": ins["Up"][0], "shared_w1": ins["SharedW1"][0],
         "shared_w2": ins["SharedW2"][0]}
    return _expert_layer_op(latent_moe, latent_moe_sharded, p, ctx, ins,
                            attrs)


def _gated_moe_op(ctx, ins, attrs):
    """Program-IR face of `gated_moe`: X [B, T, d], RouterW, RouterBias,
    W1, W2, SharedW1, SharedW2, optional NValid [B]; attrs and outputs
    as `latent_moe`."""
    p = {"router_w": ins["RouterW"][0], "router_bias": ins["RouterBias"][0],
         "w1": ins["W1"][0], "w2": ins["W2"][0],
         "shared_w1": ins["SharedW1"][0], "shared_w2": ins["SharedW2"][0]}
    return _expert_layer_op(gated_moe, gated_moe_sharded, p, ctx, ins, attrs)


def _register():
    from ..core.registry import register_op
    register_op("moe_ffn", nondiff_outputs=("Load",))(_moe_ffn_op)
    register_op("latent_moe", nondiff_inputs=("NValid",),
                nondiff_outputs=("Probe",))(_latent_moe_op)
    register_op("gated_moe", nondiff_inputs=("NValid",),
                nondiff_outputs=("Probe",))(_gated_moe_op)


_register()
