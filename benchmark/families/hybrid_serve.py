"""`hybrid_serve`: a pattern-string decoder (state-space, latent-expert
and attention layers: `paddle_tpu/models/hybrid.py`) served by the
program's own path, `serving.GenerationEngine` with paged KV beside
per-slot recurrent state, every engine flag at its default.

The cell is `gpt_serve.ServeCell` with another model in it: the same
tap on the engine's step call, the same step log, so the serving
readers read this family as they read that one.
"""
from __future__ import annotations

from benchmark import manifest
from benchmark.families import gpt_serve

KIND = "serve"


def sizes(cfg):
    return manifest.reference(cfg["name"]).sizes(cfg)


def model_config(sz, dtype):
    from paddle_tpu.models.hybrid import HybridConfig
    return HybridConfig(
        vocab_size=sz["vocab_size"], d_model=sz["hidden_size"],
        pattern=sz["pattern"], n_heads=sz["num_attention_heads"],
        n_kv_heads=sz["num_key_value_heads"], head_dim=sz["head_dim"],
        mamba_heads=sz["mamba_num_heads"],
        mamba_head_dim=sz["mamba_head_dim"],
        ssm_state=sz["ssm_state_size"], ssm_groups=sz["n_groups"],
        conv_kernel=sz["conv_kernel"], n_experts=sz["router_width"],
        experts_held=sz["experts_held"], expert_share=sz["expert_share"],
        top_k=sz["num_experts_per_tok"], moe_latent=sz["moe_latent_size"],
        moe_inter=sz["moe_intermediate_size"],
        shared_inter=sz["moe_shared_expert_intermediate_size"],
        routed_scale=sz["routed_scaling_factor"], eps=sz["norm_eps"],
        dtype=dtype, max_seq_len=sz["max_seq"])


class HybridServeCell(gpt_serve.ServeCell):
    def __init__(self, cfg, mix, chips, seed):
        import paddle_tpu as fluid
        from paddle_tpu.serving import GenerationEngine

        if chips != 1:
            raise ValueError("hybrid_serve runs on one chip")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.sizes = sizes(cfg)
        eng = cfg["engine"]
        self.tcfg = model_config(self.sizes, eng["dtype"])
        self.scope = fluid.Scope()
        self.engine = GenerationEngine(
            self.tcfg, self.scope, max_slots=eng["max_slots"],
            max_seq=eng["max_seq"], paged=eng["paged"])
        self.set_weights(seed)
        self.max_slots = eng["max_slots"]
        self.tapped, self.step_log, self.wrapped = {}, None, False
        self._wrap_step_call()

    def set_weights(self, seed):
        """Every parameter of the engine's programs, a layer at a time
        from the reference's own maker and straight into the scope: the
        start-up program is never run, so set-up holds the weights once
        (the largest layer's leaves are 1.5 GB)."""
        ref = manifest.reference(self.cfg["name"])
        block = self.engine._prog.global_block()
        want = {p.name for p in block.all_parameters()}

        def put(name, arr):
            var = block.var(name)
            if tuple(var.shape) != tuple(arr.shape):
                raise RuntimeError(f"{name}: the program has {var.shape}, "
                                   f"the benchmark makes {arr.shape}")
            self.scope.set(name, arr.astype(str(var.dtype)))
            want.discard(name)

        for name, arr in ref.global_leaves(self.sizes, seed).items():
            put(name, arr)
        for i in range(len(self.sizes["pattern"])):
            for name, arr in ref.layer_leaves(self.sizes, seed, i).items():
                put(f"layer_{i}.{name}", arr)
        if want:
            raise RuntimeError(f"parameters the benchmark does not make: "
                               f"{sorted(want)}")

    def executables(self):
        import paddle_tpu as fluid
        with fluid.scope_guard(self.scope):
            return [(name, self.engine.exe.compiled(
                prog, feed=feed, fetch_list=self.engine.fetch_list(prog)))
                for name, prog, feed, _ in self.engine.executables()]


def build(cfg, mix, chips, seed):
    return HybridServeCell(cfg, mix, chips, seed)
