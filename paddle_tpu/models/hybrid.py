"""Pattern-string decoders: state-space, expert, FFN and attention
layers in one stack, served by `serving.GenerationEngine`.

The layer list is a string, one letter a block: `M` a Mamba-2 mixer
(`ops/state_space.py`), `E` a latent-expert layer
(`parallel/moe.py:latent_moe`), `*` attention with grouped KV heads over
the paged pool (`ops/attention.py:paged_attention`), no positions; `L`
latent attention with YaRN rotary positions over a paged LATENT pool
(`ops/latent_attention.py`: one row a token for all heads; without a
query latent where `q_rank` is 0, without positions where `rope` is
empty), `D` a gated FFN, `G` gated experts
(`parallel/moe.py:gated_moe`), `K` gated delta-rule linear attention
(`ops/linear_attention.py`: a matrix state a head). Every
block is ONE mixer behind a pre-RMSNorm with a residual, so a layer of
attention AND an FFN is two letters (`LD`, `LG`); a final RMSNorm and
an untied head follow the last.

`HybridConfig.build_paged_step` yields the engine's decode (T = 1, with
logits) and chunk-prefill (T = block_size, a health probe) programs
with the feeds of `gpt.PagedDecodeStep`. Beside the paged KV pools of
its attention layers the step names a second kind of per-slot state,
`state_names`: each Mamba-2 layer's convolution window and SSM state,
each `K` layer's convolution window and matrix state,
`[max_slots, ...]` persistables that are not paged (row b of the batch
is slot b: why the engine packs several pages of one request into a
prefill step only for a model that names no such state), and
`probe_var`, four int32 a latent-expert layer that the decode step
fetches beside its logits.
"""
from __future__ import annotations

import math

from .. import layers
from .gpt import PagedDecodeStep

__all__ = ["HybridConfig", "build_paged_step"]


class HybridConfig:
    """Sizes of a pattern-string decoder. `experts_held` of the router's
    `n_experts` live here, share `expert_share` of them (expert
    parallelism: `parallel/moe.py`)."""

    def __init__(self, vocab_size, d_model, pattern, n_heads, n_kv_heads=1,
                 head_dim=0, mamba_heads=0, mamba_head_dim=0, ssm_state=0,
                 ssm_groups=1, conv_kernel=0, n_experts=1, experts_held=0,
                 top_k=0, moe_latent=0, moe_inter=0, shared_inter=0,
                 routed_scale=1.0, expert_share=0, eps=1e-5,
                 dtype="bfloat16", max_seq_len=2048, q_rank=0, kv_rank=0,
                 nope_dim=0, rope_dim=0, v_dim=0, dense_inter=0, rope=None,
                 kda_heads=0, kda_head_dim=0, kda_conv_kernel=0):
        """`L` layers: `q_rank`, `kv_rank` the latents' widths (`q_rank`
        0: queries straight from the hidden state), a head's `nope_dim`
        + `rope_dim` query/key and `v_dim` value; `rope` the rotary
        attributes of `ops/latent_attention.py` (theta, factor,
        original, beta_fast, beta_slow, mscale, mscale_all_dim), empty
        for a layer that rotates nothing. `D`: `dense_inter`. `G`:
        `moe_inter`, `shared_inter` and the router as `E`. `K`:
        `kda_heads` heads of `kda_head_dim` keys and values, behind
        convolutions of `kda_conv_kernel` taps."""
        for kind in pattern:
            if kind not in "ME*LDGK":
                raise ValueError(
                    f"pattern letter {kind!r}: M, E, *, L, D, G or K")
        if n_heads % n_kv_heads or mamba_heads % ssm_groups:
            raise ValueError("heads must divide into their KV heads / "
                             "state groups")
        if not 0 <= expert_share * experts_held < n_experts:
            raise ValueError("the held share lies outside the router")
        self.vocab_size, self.d_model, self.pattern = \
            vocab_size, d_model, pattern
        self.n_heads, self.n_kv_heads, self.head_dim = \
            n_heads, n_kv_heads, head_dim
        self.mamba_heads, self.mamba_head_dim = mamba_heads, mamba_head_dim
        self.ssm_state, self.ssm_groups = ssm_state, ssm_groups
        self.conv_kernel = conv_kernel
        self.n_experts, self.experts_held = n_experts, experts_held
        self.expert_share, self.top_k = expert_share, top_k
        self.moe_latent, self.moe_inter = moe_latent, moe_inter
        self.shared_inter, self.routed_scale = shared_inter, routed_scale
        self.eps, self.dtype, self.max_seq_len = eps, dtype, max_seq_len
        self.q_rank, self.kv_rank = q_rank, kv_rank
        self.nope_dim, self.rope_dim, self.v_dim = nope_dim, rope_dim, v_dim
        self.dense_inter, self.rope = dense_inter, dict(rope or {})
        self.kda_heads, self.kda_head_dim = kda_heads, kda_head_dim
        self.kda_conv_kernel = kda_conv_kernel

    @property
    def n_layers(self):
        return len(self.pattern)

    @property
    def mamba_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self):
        return self.mamba_inner + 2 * self.ssm_groups * self.ssm_state

    def kv_token_bytes(self):
        """Bytes a token holds in the paged pools, all layers, in the
        model's type: K and V of a `*` layer's KV heads, the one latent
        row of an `L` layer."""
        from ..core.dtypes import as_np_dtype
        from ..ops.pallas.paged_attention import pool_lanes
        lanes = 2 * self.pattern.count("*") * \
            pool_lanes(self.n_kv_heads * self.head_dim) + \
            self.pattern.count("L") * pool_lanes(self.kv_rank
                                                 + self.rope_dim)
        return lanes * as_np_dtype(self.dtype).itemsize

    @property
    def kda_inner(self):
        return self.kda_heads * self.kda_head_dim

    def state_slot_bytes(self):
        """Bytes of recurrent state a slot holds: the float32 SSM state
        and the convolution window of every Mamba-2 layer, the float32
        matrix state (keys x values a head) and the window of the three
        convolutions of every `K` layer."""
        from ..core.dtypes import as_np_dtype
        item = as_np_dtype(self.dtype).itemsize
        ssm = self.mamba_heads * self.mamba_head_dim * self.ssm_state * 4
        conv = (self.conv_kernel - 1) * self.conv_channels * item
        kda = self.kda_inner * self.kda_head_dim * 4
        window = (self.kda_conv_kernel - 1) * 3 * self.kda_inner * item
        return self.pattern.count("M") * (ssm + conv) \
            + self.pattern.count("K") * (kda + window)

    def build_paged_step(self, **kw):
        return build_paged_step(self, **kw)


def _attr(name, init):
    from ..framework import ParamAttr
    return ParamAttr(name=name, initializer=init)


def _param(name, shape, dtype, init):
    return layers.create_parameter(shape, dtype, attr=_attr(name, init))


def _op(op_type, inputs, outputs, attrs):
    """Append one op; `outputs` maps a slot to its dtype, and the new
    variables come back by slot."""
    from ..layer_helper import LayerHelper
    helper = LayerHelper(op_type)
    outs = {slot: helper.create_variable_for_type_inference(dtype)
            for slot, dtype in outputs.items()}
    helper.append_op(type=op_type,
                     inputs={k: [v.name] for k, v in inputs.items()},
                     outputs={k: [v.name] for k, v in outs.items()},
                     attrs=attrs)
    return outs


def build_paged_step(cfg, batch, max_seq, block_size, num_blocks,
                     seq_tokens=1, state_prefix="", with_logits=True):
    """The paged step of a pattern-string decoder: `seq_tokens` tokens a
    row a step (1: decode; `block_size`: a prefill chunk, for which
    `with_logits=False` returns the `[batch]` health probe). Weight
    names are `word_emb`, `layer_<i>.norm.w`, `layer_<i>.mixer.*` /
    `.att.*` / `.moe.*`, `final_norm.w`, `lm_head.w`; only the pools
    and the recurrent state carry `state_prefix`, and both programs
    name them alike, so one scope carries one set. `cache_names` are
    the pools as the model has them, in layer order: K then V of a `*`
    layer, the one `kv_pool` of an `L` layer."""
    from ..initializer import Constant, Normal
    from ..ops.latent_attention import yarn_sm_scale
    from ..ops.pallas.paged_attention import pool_lanes

    d, dt = cfg.d_model, cfg.dtype
    T = int(seq_tokens)
    max_blocks = -(-int(max_seq) // int(block_size))
    token = layers.data("step_token", shape=[batch, T], dtype="int64",
                        append_batch_size=False)
    table = layers.data("block_table", shape=[batch, max_blocks],
                        dtype="int64", append_batch_size=False)
    start = layers.data("start_pos", shape=[batch], dtype="int64",
                        append_batch_size=False)
    nvalid = layers.data("n_valid", shape=[batch], dtype="int64",
                         append_batch_size=False)
    mat, one = Normal(0.0, 0.02), Constant(1.0)

    def norm(x, name):
        return _op("rms_norm",
                   {"X": x, "Scale": _param(name, [d], dt, one)},
                   {"Out": dt}, {"epsilon": cfg.eps})["Out"]

    def dense(x, name, n_in, n_out):
        w = _param(name, [n_in, n_out], dt, mat)
        return _op("mul", {"X": x, "Y": w}, {"Out": dt},
                   {"x_num_col_dims": 2, "y_num_col_dims": 1})["Out"]

    def state_var(name, shape, dtype):
        return layers.create_global_var(shape, 0.0, dtype, persistable=True,
                                        name=f"{state_prefix}{name}")

    x = layers.embedding(token, size=[cfg.vocab_size, d], dtype=dt,
                         param_attr=_attr("word_emb", mat))
    x = layers.reshape(x, [batch, T, d])
    cache_names, state_names, probes = [], [], []
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    for i, kind in enumerate(cfg.pattern):
        pre = f"layer_{i}"
        u = norm(x, f"{pre}.norm.w")
        if kind == "M":
            inner, conv_c = cfg.mamba_inner, cfg.conv_channels
            mh = cfg.mamba_heads
            conv_s = state_var(f"{pre}.conv_state",
                               [batch, cfg.conv_kernel - 1, conv_c], dt)
            ssm_s = state_var(
                f"{pre}.ssm_state",
                [batch, mh, cfg.mamba_head_dim, cfg.ssm_state], "float32")
            state_names += [conv_s.name, ssm_s.name]
            m = f"{pre}.mixer"
            outs = _op("mamba2_mixer", {
                "X": u,
                "InProj": _param(f"{m}.in_proj.w",
                                 [d, inner + conv_c + mh], dt, mat),
                "ConvW": _param(f"{m}.conv.w", [conv_c, cfg.conv_kernel],
                                dt, Normal(0.0, 0.3)),
                "ConvB": _param(f"{m}.conv.b", [conv_c], dt, Constant(0.0)),
                "DtBias": _param(f"{m}.dt_bias", [mh], "float32",
                                 Constant(-4.6)),
                "ALog": _param(f"{m}.A_log", [mh], "float32",
                               Constant(1.0)),
                "D": _param(f"{m}.D", [mh], "float32", one),
                "NormW": _param(f"{m}.norm.w", [inner], dt, one),
                "OutProj": _param(f"{m}.out_proj.w", [inner, d], dt, mat),
                "ConvState": conv_s, "SsmState": ssm_s,
                "StartPos": start, "NValid": nvalid},
                {"Out": dt, "ConvStateOut": dt, "SsmStateOut": "float32"},
                {"groups": cfg.ssm_groups, "epsilon": cfg.eps})
            layers.assign(outs["ConvStateOut"], output=conv_s)
            layers.assign(outs["SsmStateOut"], output=ssm_s)
            y = outs["Out"]
        elif kind == "K":
            inner, hk = cfg.kda_inner, cfg.kda_head_dim
            conv_s = state_var(f"{pre}.conv_state",
                               [batch, cfg.kda_conv_kernel - 1, 3 * inner],
                               dt)
            kda_s = state_var(f"{pre}.kda_state",
                              [batch, cfg.kda_heads, hk, hk], "float32")
            state_names += [conv_s.name, kda_s.name]
            m = f"{pre}.kda"
            outs = _op("kda_mixer", {
                "X": u,
                "Q": _param(f"{m}.q.w", [d, inner], dt, mat),
                "K": _param(f"{m}.k.w", [d, inner], dt, mat),
                "V": _param(f"{m}.v.w", [d, inner], dt, mat),
                "ConvW": _param(f"{m}.conv.w",
                                [3 * inner, cfg.kda_conv_kernel], dt,
                                Normal(0.0, 0.3)),
                "F1": _param(f"{m}.f1.w", [d, hk], dt, mat),
                "F2": _param(f"{m}.f2.w", [hk, inner], dt, mat),
                "ALog": _param(f"{m}.A_log", [cfg.kda_heads], "float32",
                               Constant(1.0)),
                "DtBias": _param(f"{m}.dt_bias", [inner], "float32",
                                 Constant(-4.6)),
                "B": _param(f"{m}.b.w", [d, cfg.kda_heads], dt, mat),
                "G1": _param(f"{m}.g1.w", [d, hk], dt, mat),
                "G2": _param(f"{m}.g2.w", [hk, inner], dt, mat),
                "ONorm": _param(f"{m}.o_norm.w", [hk], dt, one),
                "O": _param(f"{m}.o.w", [inner, d], dt, mat),
                "ConvState": conv_s, "KdaState": kda_s,
                "StartPos": start, "NValid": nvalid},
                {"Out": dt, "ConvStateOut": dt, "KdaStateOut": "float32"},
                {"epsilon": cfg.eps})
            layers.assign(outs["ConvStateOut"], output=conv_s)
            layers.assign(outs["KdaStateOut"], output=kda_s)
            y = outs["Out"]
        elif kind == "*":
            def heads(z, n):
                return layers.transpose(
                    layers.reshape(z, [batch, T, n, hd]), [0, 2, 1, 3])
            q = heads(dense(u, f"{pre}.att.q.w", d, h * hd), h)
            k = heads(dense(u, f"{pre}.att.k.w", d, kv * hd), kv)
            v = heads(dense(u, f"{pre}.att.v.w", d, kv * hd), kv)
            pool_shape = [num_blocks, block_size, pool_lanes(kv * hd)]
            ckp = state_var(f"{pre}.kv_pool_k", pool_shape, dt)
            cvp = state_var(f"{pre}.kv_pool_v", pool_shape, dt)
            cache_names += [ckp.name, cvp.name]
            outs = _op("paged_attention", {
                "Q": q, "K": k, "V": v, "CacheK": ckp, "CacheV": cvp,
                "BlockTable": table, "StartPos": start, "NValid": nvalid},
                {"Out": dt, "CacheKOut": dt, "CacheVOut": dt},
                {"sm_scale": 1.0 / math.sqrt(hd)})
            layers.assign(outs["CacheKOut"], output=ckp)
            layers.assign(outs["CacheVOut"], output=cvp)
            ctx = layers.reshape(
                layers.transpose(outs["Out"], [0, 2, 1, 3]),
                [batch, T, h * hd])
            y = dense(ctx, f"{pre}.att.o.w", h * hd, d)
        elif kind == "L":
            a = f"{pre}.att"
            row_w = cfg.kv_rank + cfg.rope_dim
            kv_b = _param(f"{a}.kv_b.w",
                          [cfg.kv_rank, h * (cfg.nope_dim + cfg.v_dim)], dt,
                          mat)
            q_w = h * (cfg.nope_dim + cfg.rope_dim)
            # with a query latent two products and a norm between
            # them, without one the one product from the hidden state
            queries = {
                "QA": _param(f"{a}.q_a.w", [d, cfg.q_rank], dt, mat),
                "QNorm": _param(f"{a}.q_norm.w", [cfg.q_rank], dt, one),
                "QB": _param(f"{a}.q_b.w", [cfg.q_rank, q_w], dt, mat),
            } if cfg.q_rank else {
                "QB": _param(f"{a}.q.w", [d, q_w], dt, mat)}
            proj = _op("mla_project", {
                "X": u, **queries,
                "KVA": _param(f"{a}.kv_a.w", [d, row_w], dt, mat),
                "KVNorm": _param(f"{a}.kv_norm.w", [cfg.kv_rank], dt, one),
                "KVB": kv_b, "StartPos": start},
                {"Q": dt, "Row": dt},
                {"heads": h, "nope_dim": cfg.nope_dim,
                 "rope_dim": cfg.rope_dim, "epsilon": cfg.eps, **cfg.rope})
            pool = state_var(f"{pre}.kv_pool",
                             [num_blocks, block_size, pool_lanes(row_w)], dt)
            cache_names.append(pool.name)
            outs = _op("paged_attention", {
                "Q": proj["Q"], "K": proj["Row"], "CacheK": pool,
                "BlockTable": table, "StartPos": start, "NValid": nvalid},
                {"Out": dt, "CacheKOut": dt},
                {"sm_scale": yarn_sm_scale(
                    cfg.nope_dim + cfg.rope_dim,
                    cfg.rope.get("factor", 1.0),
                    cfg.rope.get("mscale_all_dim", 0.0)),
                 "value_lanes": cfg.kv_rank})
            layers.assign(outs["CacheKOut"], output=pool)
            y = _op("mla_output", {
                "X": outs["Out"], "KVB": kv_b,
                "WO": _param(f"{a}.o.w", [h * cfg.v_dim, d], dt, mat)},
                {"Out": dt}, {"nope_dim": cfg.nope_dim})["Out"]
        elif kind == "D":
            f = cfg.dense_inter
            y = _op("gated_ffn", {
                "X": u,
                "W1": _param(f"{pre}.ffn.w1", [d, 2 * f], dt, mat),
                "W2": _param(f"{pre}.ffn.w2", [f, d], dt, mat)},
                {"Out": dt}, {})["Out"]
        elif kind == "G":
            e = f"{pre}.moe"
            eh, mid, sh = cfg.experts_held, cfg.moe_inter, cfg.shared_inter
            outs = _op("gated_moe", {
                "X": u,
                "RouterW": _param(f"{e}.router.w", [d, cfg.n_experts], dt,
                                  mat),
                "RouterBias": _param(f"{e}.router.bias", [cfg.n_experts],
                                     "float32", Constant(0.0)),
                "W1": _param(f"{e}.w1", [eh, d, 2 * mid], dt, mat),
                "W2": _param(f"{e}.w2", [eh, mid, d], dt, mat),
                "SharedW1": _param(f"{e}.shared.w1", [d, 2 * sh], dt, mat),
                "SharedW2": _param(f"{e}.shared.w2", [sh, d], dt, mat),
                "NValid": nvalid},
                {"Out": dt, "Probe": "int32"},
                {"top_k": cfg.top_k, "scale": cfg.routed_scale,
                 "share": cfg.expert_share})
            probes.append(outs["Probe"])
            y = outs["Out"]
        else:
            e = f"{pre}.moe"
            eh, lat, mid = cfg.experts_held, cfg.moe_latent, cfg.moe_inter
            outs = _op("latent_moe", {
                "X": u,
                "RouterW": _param(f"{e}.router.w", [d, cfg.n_experts], dt,
                                  mat),
                "RouterBias": _param(f"{e}.router.bias", [cfg.n_experts],
                                     "float32", Constant(0.0)),
                "Down": _param(f"{e}.down.w", [d, lat], dt, mat),
                "W1": _param(f"{e}.w1", [eh, lat, mid], dt, mat),
                "W2": _param(f"{e}.w2", [eh, mid, lat], dt, mat),
                "Up": _param(f"{e}.up.w", [lat, d], dt, mat),
                "SharedW1": _param(f"{e}.shared.w1", [d, cfg.shared_inter],
                                   dt, mat),
                "SharedW2": _param(f"{e}.shared.w2", [cfg.shared_inter, d],
                                   dt, mat),
                "NValid": nvalid},
                {"Out": dt, "Probe": "int32"},
                {"top_k": cfg.top_k, "scale": cfg.routed_scale,
                 "share": cfg.expert_share})
            probes.append(outs["Probe"])
            y = outs["Out"]
        x = layers.elementwise_add(x, y)

    probe_var = None
    if with_logits:
        x = norm(x, "final_norm.w")
        # the logits are fetched and compared in float32: the head's
        # bfloat16 operands widen exactly, the product accumulates in
        # float32 and nothing rounds it after
        head = _param("lm_head.w", [d, cfg.vocab_size], dt, mat)
        out = _op("mul", {"X": layers.cast(x, "float32"),
                          "Y": layers.cast(head, "float32")},
                  {"Out": "float32"},
                  {"x_num_col_dims": 2, "y_num_col_dims": 1})["Out"]
        if probes:
            probe_var = layers.stack(probes, axis=0)
    else:
        out = layers.reduce_mean(layers.cast(x, "float32"), dim=[1, 2])
    step = PagedDecodeStep(token, out, cache_names, table, start, nvalid,
                           batch, max_seq, block_size, num_blocks, T,
                           state_prefix)
    step.state_names = state_names
    step.probe_var = probe_var
    return step

