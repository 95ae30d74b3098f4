"""`kda_serve`: a decoder of gated delta-rule linear attention (a matrix
state a head and a slot, no cache row) beside position-free latent
attention over a paged LATENT pool, a gated FFN and gated routed
experts (`paddle_tpu/models/hybrid.py`, letters K, L, D, G) served by
the program's own path, `serving.GenerationEngine` with per-slot
recurrent state beside the paged pool, every engine flag at its
default.

The cell is `hybrid_serve.HybridServeCell` (itself `gpt_serve.ServeCell`
with another model in it) with this model in it: weights written
straight into the scope a sub-layer at a time, the same tap on the
engine's step call, the same step log.
"""
from __future__ import annotations

from benchmark import manifest
from benchmark.families import hybrid_serve

KIND = "serve"


def sizes(cfg):
    return manifest.reference(cfg["name"]).sizes(cfg)


def model_config(sz, dtype):
    from paddle_tpu.models.hybrid import HybridConfig
    return HybridConfig(
        vocab_size=sz["vocab_size"], d_model=sz["hidden_size"],
        pattern=sz["pattern"], n_heads=sz["num_attention_heads"],
        kv_rank=sz["kv_lora_rank"], nope_dim=sz["qk_nope_head_dim"],
        rope_dim=sz["qk_rope_head_dim"], v_dim=sz["v_head_dim"],
        kda_heads=sz["kda_num_heads"], kda_head_dim=sz["kda_head_dim"],
        kda_conv_kernel=sz["short_conv_kernel_size"],
        dense_inter=sz["intermediate_size"],
        n_experts=sz["router_width"], experts_held=sz["experts_held"],
        expert_share=sz["expert_share"], top_k=sz["num_experts_per_tok"],
        moe_inter=sz["moe_intermediate_size"],
        shared_inter=sz["moe_intermediate_size"] * sz["n_shared_experts"],
        routed_scale=sz["routed_scaling_factor"], eps=sz["norm_eps"],
        dtype=dtype, max_seq_len=sz["max_seq"])


class KdaServeCell(hybrid_serve.HybridServeCell):
    def __init__(self, cfg, mix, chips, seed):
        import paddle_tpu as fluid
        from paddle_tpu.serving import GenerationEngine

        if chips != 1:
            raise ValueError("kda_serve runs on one chip")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.sizes = sizes(cfg)
        eng = cfg["engine"]
        self.tcfg = model_config(self.sizes, eng["dtype"])
        self.scope = fluid.Scope()
        self.engine = GenerationEngine(
            self.tcfg, self.scope, max_slots=eng["max_slots"],
            max_seq=eng["max_seq"], paged=eng["paged"],
            queue_capacity=eng.get("queue_capacity"))
        self.set_weights(seed)
        self.max_slots = eng["max_slots"]
        self.tapped, self.step_log, self.wrapped = {}, None, False
        self._wrap_step_call()


def build(cfg, mix, chips, seed):
    return KdaServeCell(cfg, mix, chips, seed)
