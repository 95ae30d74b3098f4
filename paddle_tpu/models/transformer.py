"""Transformer encoder LM — the flagship NLP workload.

Reference configs: Transformer-big NMT / BERT-base pretraining
(BASELINE.json configs 2-3; reference attention assembled from
matmul/softmax/layer_norm in models/PaddleNLP). Here the model is built
from the layers API so the whole step is one XLA computation; optional
Megatron-style tensor parallelism + sequence parallelism arrive via
shard_hint annotations (GSPMD inserts the collectives over ICI):

- QKV/FFN-in weights: column-sharded over 'tp'; proj/FFN-out: row-sharded
- activations between blocks: sharded [dp, sp, None] for sequence
  parallelism (the 2019 reference has no SP at all — SURVEY.md §2.7)
"""
from __future__ import annotations

import math

from .. import layers
from ..framework import ParamAttr
from ..initializer import Normal


class TransformerConfig:
    def __init__(self, vocab_size=30522, d_model=768, n_heads=12,
                 n_layers=12, d_ff=3072, max_seq_len=512, dropout=0.1,
                 tp=False, sp=False, dp_axis="dp", tp_axis="tp",
                 sp_axis="sp", use_flash="auto", causal=False,
                 attn_dropout=None, flash_block_q=None,
                 flash_block_k=None):
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.d_ff = d_ff
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.tp = tp  # annotate weights for tensor parallelism
        self.sp = sp  # annotate activations for sequence parallelism
        # fused Pallas attention kernel (ops/pallas/flash_attention.py);
        # falls back to composed matmul+softmax when False. Dropout on
        # attention WEIGHTS is a separate knob: the flash kernel does not
        # implement it, so attn_dropout > 0 forces the composed path
        # (keeping the trained model identical across kernel choices).
        # "auto" = flash only from ops/attention.py:FLASH_AUTO_MIN_SEQ
        # (4096) up; where the flip belongs is not measured on the chip
        # (the comment there).
        if use_flash == "auto":
            from ..ops.attention import FLASH_AUTO_MIN_SEQ
            use_flash = max_seq_len >= FLASH_AUTO_MIN_SEQ
        self.use_flash = use_flash
        # Explicit Pallas tile override (op attrs). None = leave the
        # attrs unset so FLAGS_flash_attention_block_{q,k} and the
        # autotune cache (FLAGS_flash_autotune) govern at lowering time.
        self.flash_block_q = flash_block_q
        self.flash_block_k = flash_block_k
        self.causal = causal
        self.attn_dropout = dropout if attn_dropout is None else \
            attn_dropout
        # Mesh axis names the hints refer to; Megatron-style SP shards the
        # sequence over the TP group (set sp_axis=tp_axis).
        self.dp_axis = dp_axis
        self.tp_axis = tp_axis
        self.sp_axis = sp_axis

    # What `serving.GenerationEngine` asks of a model's configuration
    # (models/hybrid.HybridConfig answers the same three).
    def build_paged_step(self, **kw):
        """The paged decode / chunk-prefill / verify program of this
        model (`gpt.build_paged_decode_step`)."""
        from . import gpt
        return gpt.build_paged_decode_step(self, **kw)

    def kv_token_bytes(self):
        """Bytes a token holds in the paged pools, all layers, K and V:
        float32, in whole lane tiles (`pool_lanes`)."""
        from ..ops.pallas.paged_attention import pool_lanes
        return 2 * self.n_layers * pool_lanes(self.d_model) * 4

    def state_slot_bytes(self):
        """No recurrent state beside the KV."""
        return 0


def bert_base(**kw):
    return TransformerConfig(**kw)


def bert_large(**kw):
    kw.setdefault("d_model", 1024)
    kw.setdefault("n_heads", 16)
    kw.setdefault("n_layers", 24)
    kw.setdefault("d_ff", 4096)
    return TransformerConfig(**kw)


def transformer_big(**kw):
    """Transformer-big NMT scale (reference config 2)."""
    kw.setdefault("vocab_size", 32000)
    kw.setdefault("d_model", 1024)
    kw.setdefault("n_heads", 16)
    kw.setdefault("n_layers", 6)
    kw.setdefault("d_ff", 4096)
    return TransformerConfig(**kw)


def _dense(x, size, name, cfg, act=None, tp_axis=None):
    """fc with optional tp annotation on the weight via shard_hint on the
    output (GSPMD propagates to the weight)."""
    init = Normal(0.0, 0.02)
    out = layers.fc(x, size=size, num_flatten_dims=2, act=act,
                    param_attr=ParamAttr(name=f"{name}.w", initializer=init),
                    bias_attr=ParamAttr(name=f"{name}.b"))
    if cfg.tp and tp_axis == "col":
        out = layers.shard_hint(out, [cfg.dp_axis, None, cfg.tp_axis])
    return out


def _flash_block_attrs(cfg):
    """block_q/block_k kwargs for layers.flash_attention: 0/0 forces the
    exact composed path when flash is off; explicit config tiles pin the
    kernel; otherwise empty, leaving tile choice to the flags/autotuner
    at lowering time."""
    if not cfg.use_flash:
        return {"block_q": 0, "block_k": 0}
    kw = {}
    if cfg.flash_block_q is not None:
        kw["block_q"] = int(cfg.flash_block_q)
    if cfg.flash_block_k is not None:
        kw["block_k"] = int(cfg.flash_block_k)
    return kw


def _attention(x, cfg, prefix):
    b, t, d = x.shape[0], x.shape[1], cfg.d_model
    h = cfg.n_heads
    hd = d // h
    q = _dense(x, d, f"{prefix}.q", cfg, tp_axis="col")
    k = _dense(x, d, f"{prefix}.k", cfg, tp_axis="col")
    v = _dense(x, d, f"{prefix}.v", cfg, tp_axis="col")

    def split_heads(z):
        z = layers.reshape(z, [b, t, h, hd])
        return layers.transpose(z, [0, 2, 1, 3])  # [b, h, t, hd]

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    if cfg.tp:
        q = layers.shard_hint(q, [cfg.dp_axis, cfg.tp_axis, None, None])
        k = layers.shard_hint(k, [cfg.dp_axis, cfg.tp_axis, None, None])
        v = layers.shard_hint(v, [cfg.dp_axis, cfg.tp_axis, None, None])
    # Single op either way: the lowering picks the Pallas tiled kernel or
    # the exact fallback (dropout on / bad tile divisor) — causal mask and
    # numerics are identical across paths (ops/attention.py). Tile attrs
    # are only written when the config pins them; otherwise they stay
    # unset so the flag/autotune defaults govern (no hard-coded tile).
    ctxv = layers.flash_attention(
        q, k, v, causal=cfg.causal, sm_scale=1.0 / math.sqrt(hd),
        attn_dropout=cfg.attn_dropout,
        **_flash_block_attrs(cfg))
    ctxv = layers.transpose(ctxv, [0, 2, 1, 3])
    ctxv = layers.reshape(ctxv, [b, t, d])
    return _dense(ctxv, d, f"{prefix}.proj", cfg, tp_axis="row")


def _ffn(x, cfg, prefix):
    h = _dense(x, cfg.d_ff, f"{prefix}.fc1", cfg, act="gelu",
               tp_axis="col")
    return _dense(h, cfg.d_model, f"{prefix}.fc2", cfg, tp_axis="row")


def _block(x, cfg, i):
    att = _attention(x, cfg, f"layer_{i}.att")
    if cfg.dropout:
        att = layers.dropout(att, cfg.dropout,
                             dropout_implementation="upscale_in_train")
    # explicit param names: cross-program weight sharing (decode-step
    # graphs, checkpoint stability) must not depend on build order
    x = layers.layer_norm(layers.elementwise_add(x, att),
                          begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"layer_{i}.ln1.w"),
                          bias_attr=ParamAttr(name=f"layer_{i}.ln1.b"))
    ff = _ffn(x, cfg, f"layer_{i}.ffn")
    if cfg.dropout:
        ff = layers.dropout(ff, cfg.dropout,
                            dropout_implementation="upscale_in_train")
    x = layers.layer_norm(layers.elementwise_add(x, ff), begin_norm_axis=2,
                          param_attr=ParamAttr(name=f"layer_{i}.ln2.w"),
                          bias_attr=ParamAttr(name=f"layer_{i}.ln2.b"))
    if cfg.sp:
        x = layers.shard_hint(x, [cfg.dp_axis, cfg.sp_axis, None])
    return x


def encoder(tokens, cfg: TransformerConfig):
    """tokens: int64 [batch, seq]. Returns hidden states [b, t, d]."""
    emb = layers.embedding(
        tokens, size=[cfg.vocab_size, cfg.d_model],
        param_attr=ParamAttr(name="word_emb",
                             initializer=Normal(0.0, 0.02)))
    x = layers.add_position_encoding(emb, alpha=1.0, beta=1.0)
    if cfg.dropout:
        x = layers.dropout(x, cfg.dropout,
                           dropout_implementation="upscale_in_train")
    if cfg.sp:
        x = layers.shard_hint(x, [cfg.dp_axis, cfg.sp_axis, None])
    for i in range(cfg.n_layers):
        x = _block(x, cfg, i)
    return x


def lm_logits(hidden, cfg: TransformerConfig):
    """LM head projection to vocab logits."""
    return layers.fc(hidden, size=cfg.vocab_size, num_flatten_dims=2,
                     param_attr=ParamAttr(name="lm_head.w",
                                          initializer=Normal(0.0, 0.02)),
                     bias_attr=False)


def lm_loss(hidden, labels, cfg: TransformerConfig, logits=None):
    """LM head tied projection + per-token softmax CE. Pass precomputed
    `logits` to avoid a second head projection when the caller also
    exposes them (gpt.build_train)."""
    if logits is None:
        logits = lm_logits(hidden, cfg)
    # single -1: robust to dynamic batch/time dims (sliced inputs)
    logits2 = layers.reshape(logits, [-1, cfg.vocab_size])
    labels2 = layers.reshape(labels, [-1, 1])
    loss = layers.softmax_with_cross_entropy(logits2, labels2)
    return layers.mean(loss)


def build_train(cfg: TransformerConfig, batch, seq_len, lr=1e-4,
                optimizer_cls=None, amp=False):
    """Full training graph; returns (loss, feed vars). amp=True runs the
    MXU work in bf16 via the mixed-precision rewrite (contrib/)."""
    from .. import optimizer as opt
    tokens = layers.data("tokens", shape=[batch, seq_len], dtype="int64",
                         append_batch_size=False)
    labels = layers.data("labels", shape=[batch, seq_len], dtype="int64",
                         append_batch_size=False)
    hidden = encoder(tokens, cfg)
    loss = lm_loss(hidden, labels, cfg)
    optimizer_cls = optimizer_cls or opt.AdamW
    opt_inst = optimizer_cls(learning_rate=lr)
    if amp:
        from ..contrib import mixed_precision as mp
        opt_inst = mp.decorate(opt_inst)
    opt_inst.minimize(loss)
    return loss, [tokens, labels]


def build_train_mlm(cfg: TransformerConfig, batch, seq_len, n_mask,
                    lr=1e-4, optimizer_cls=None, amp=False):
    """BERT-style masked-LM pretraining graph: the vocab projection and
    softmax CE run only at the `n_mask` masked positions per sequence
    (gathered via `mask_pos`), not all T positions — the actual MLM
    objective (BERT gathers mask positions the same way; the full-T
    lm head in build_train is the GPT-shaped objective). At 15% masking
    this removes ~85% of the lm-head matmul + vocab-wide CE + their
    backward, the single largest cost block of the step in a profile
    of 2026-08-02 (a removed setup; ROADMAP queue 1 item 6).

    Feeds: tokens [b, T] int64; mask_pos [b*n_mask] int32 (flattened
    row-major indices into [b*T]); mask_label [b*n_mask, 1] int64.
    """
    from .. import optimizer as opt
    tokens = layers.data("tokens", shape=[batch, seq_len], dtype="int64",
                         append_batch_size=False)
    mask_pos = layers.data("mask_pos", shape=[batch * n_mask],
                           dtype="int32", append_batch_size=False)
    mask_label = layers.data("mask_label", shape=[batch * n_mask, 1],
                             dtype="int64", append_batch_size=False)
    hidden = encoder(tokens, cfg)
    flat = layers.reshape(hidden, [-1, cfg.d_model])
    picked = layers.gather(flat, mask_pos)
    logits = layers.fc(picked, size=cfg.vocab_size,
                       param_attr=ParamAttr(name="lm_head.w",
                                            initializer=Normal(0.0, 0.02)),
                       bias_attr=False)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        logits, mask_label))
    optimizer_cls = optimizer_cls or opt.AdamW
    opt_inst = optimizer_cls(learning_rate=lr)
    if amp:
        from ..contrib import mixed_precision as mp
        opt_inst = mp.decorate(opt_inst)
    opt_inst.minimize(loss)
    return loss, [tokens, mask_pos, mask_label]
