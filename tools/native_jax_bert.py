#!/usr/bin/env python3
"""Hand-written pure-JAX twin of bench.py's BERT config — the control
experiment that splits the measured MFU into "framework overhead" vs
"chip/shape ceiling".

Same math as paddle_tpu.models.transformer.build_train (BERT-base
post-LN encoder, sinusoidal position add, gelu FFN, dropout 0.1
upscale_in_train, untied LM head, full-vocab softmax CE, AdamW 1e-4,
AMP-style bf16 matmuls with f32 masters/softmax/layer_norm) but written
directly against jax.numpy with no Program IR, no Executor, no op
registry. If this twin and bench.py measure the same step time on the
same chip, the framework lowering is at parity with native JAX and the
remaining MFU gap is model/shape/chip-bound; if the twin is faster, the
delta IS the framework's lowering overhead, op by op.

Reference analogue for the isolate-the-layer discipline:
paddle/fluid/operators/benchmark/op_tester.cc (it benches ops outside
the full executor for the same reason).

Usage: python tools/native_jax_bert.py   (env: BENCH_BATCH, BENCH_SEQ,
BENCH_STEPS as in bench.py). One process; fails without an accelerator.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

import bench  # noqa: E402 — flops/peak helpers


class _Cfg:
    vocab_size = 30522
    d_model = 768
    n_heads = 12
    n_layers = 12
    d_ff = 3072


def init_params(rng, cfg):
    p = {}
    r = np.random.RandomState(rng)

    def nrm(*shape):
        return np.asarray(r.normal(0.0, 0.02, shape), np.float32)

    p["word_emb"] = nrm(cfg.vocab_size, cfg.d_model)
    for i in range(cfg.n_layers):
        L = {}
        for nm in ("q", "k", "v", "proj"):
            L[f"{nm}.w"] = nrm(cfg.d_model, cfg.d_model)
            L[f"{nm}.b"] = np.zeros(cfg.d_model, np.float32)
        L["fc1.w"] = nrm(cfg.d_model, cfg.d_ff)
        L["fc1.b"] = np.zeros(cfg.d_ff, np.float32)
        L["fc2.w"] = nrm(cfg.d_ff, cfg.d_model)
        L["fc2.b"] = np.zeros(cfg.d_model, np.float32)
        for ln in ("ln1", "ln2"):
            L[f"{ln}.w"] = np.ones(cfg.d_model, np.float32)
            L[f"{ln}.b"] = np.zeros(cfg.d_model, np.float32)
        p[f"layer_{i}"] = L
    p["lm_head.w"] = nrm(cfg.d_model, cfg.vocab_size)
    return p


def _build_step(cfg, seq_len, lr=1e-4, wd=0.01, dropout=0.1):
    import jax
    import jax.numpy as jnp

    def dense(x, w, b, act=None):
        y = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        y = y + b
        if act == "gelu":
            y = jax.nn.gelu(y, approximate=False)
        return y

    def layer_norm(x, w, b):
        x = x.astype(jnp.float32)
        m = jnp.mean(x, -1, keepdims=True)
        v = jnp.var(x, -1, keepdims=True)
        return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b

    def drop(x, key, i):
        if not dropout:
            return x
        keep = jax.random.bernoulli(jax.random.fold_in(key, i),
                                    1.0 - dropout, x.shape)
        return jnp.where(keep, x / (1.0 - dropout), 0.0).astype(x.dtype)

    def pos_encoding(t, d):
        pos = np.arange(t)[:, None]
        dim = np.arange(d // 2)[None, :]
        ang = pos / np.power(10000.0, 2 * dim / d)
        pe = np.concatenate([np.sin(ang), np.cos(ang)], axis=1)
        return jnp.asarray(pe, jnp.float32)

    pe = pos_encoding(seq_len, cfg.d_model)
    hd = cfg.d_model // cfg.n_heads
    scale = 1.0 / np.sqrt(hd)

    def forward(p, toks, key):
        x = jnp.take(p["word_emb"], toks, axis=0) + pe
        x = drop(x, key, 0)
        for i in range(cfg.n_layers):
            L = p[f"layer_{i}"]
            b, t = x.shape[0], x.shape[1]
            q = dense(x, L["q.w"], L["q.b"])
            k = dense(x, L["k.w"], L["k.b"])
            v = dense(x, L["v.w"], L["v.b"])

            def heads(z):
                return z.reshape(b, t, cfg.n_heads, hd).transpose(
                    0, 2, 1, 3)

            q, k, v = heads(q), heads(k), heads(v)
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.bfloat16),
                           k.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) * scale
            a = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", a.astype(jnp.bfloat16),
                             v.astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, cfg.d_model)
            att = dense(ctx, L["proj.w"], L["proj.b"])
            att = drop(att, key, 10 * i + 1)
            x = layer_norm(x + att, L["ln1.w"], L["ln1.b"])
            ff = dense(dense(x, L["fc1.w"], L["fc1.b"], act="gelu"),
                       L["fc2.w"], L["fc2.b"])
            ff = drop(ff, key, 10 * i + 2)
            x = layer_norm(x + ff, L["ln2.w"], L["ln2.b"])
        logits = jnp.dot(x.astype(jnp.bfloat16),
                         p["lm_head.w"].astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        return logits

    def loss_fn(p, toks, labels, key):
        logits = forward(p, toks, key)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(lse - gold)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, toks, labels):
        p, m, v, t, key = state
        key, sub = jax.random.split(key)
        loss, g = jax.value_and_grad(loss_fn)(p, toks, labels, sub)
        t = t + 1
        b1, b2, eps = 0.9, 0.999, 1e-8
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def upd(p_, g_, m_, v_):
            m2 = b1 * m_ + (1 - b1) * g_
            v2 = b2 * v_ + (1 - b2) * g_ * g_
            step_ = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            return p_ - lr * (step_ + wd * p_), m2, v2

        import jax.tree_util as jtu
        flat = jtu.tree_map(upd, p, g, m, v)
        p2 = jtu.tree_map(lambda x: x[0], flat,
                          is_leaf=lambda x: isinstance(x, tuple))
        m2 = jtu.tree_map(lambda x: x[1], flat,
                          is_leaf=lambda x: isinstance(x, tuple))
        v2 = jtu.tree_map(lambda x: x[2], flat,
                          is_leaf=lambda x: isinstance(x, tuple))
        return (p2, m2, v2, t, key), loss

    return step


def main():
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    seq_len = int(os.environ.get("BENCH_SEQ", "512"))
    steps = int(os.environ.get("BENCH_STEPS", "30"))
    import jax
    import jax.numpy as jnp
    import jax.tree_util as jtu
    # an unknown device (the CPU included) raises here, before any work
    peak = bench.peak_flops_per_chip()
    cfg = _Cfg()
    p = jtu.tree_map(jnp.asarray, init_params(0, cfg))
    zeros = jtu.tree_map(jnp.zeros_like, p)
    state = (p, zeros, jtu.tree_map(jnp.zeros_like, p),
             jnp.zeros((), jnp.int32), jax.random.PRNGKey(0))
    step = _build_step(cfg, seq_len)
    r = np.random.RandomState(0)
    toks = jnp.asarray(r.randint(0, cfg.vocab_size, (batch, seq_len)),
                       jnp.int32)
    state, lv = step(state, toks, toks)  # compile + warm
    jax.block_until_ready(lv)

    # identical timing discipline to bench.py _timed_steps: async
    # windows ended by block_until_ready — the bench-vs-twin comparison
    # is only meaningful if both sides measure the same way
    def window(n):
        nonlocal state
        t0 = time.perf_counter()
        lv = None
        for _ in range(n):
            state, lv = step(state, toks, toks)
        jax.block_until_ready(lv)
        return (time.perf_counter() - t0) / n, float(np.asarray(lv))

    n1 = max(1, steps // 2)
    n2 = max(1, steps - n1)
    dt1, _ = window(n1)
    dt2, lv = window(n2)
    dt = (dt1 * n1 + dt2 * n2) / (n1 + n2)
    flops = bench.model_flops_per_token(cfg, seq_len) * batch * seq_len
    mfu = flops / dt / peak
    print(json.dumps({
        "metric": "bert_base_native_jax_tokens_per_sec_per_chip",
        "value": round(batch * seq_len / dt, 1), "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.50, 4),
        "extra": {"step_ms": round(dt * 1000, 2), "mfu": round(mfu, 4),
                  "batch": batch, "seq_len": seq_len, "loss": lv,
                  **bench.device_stamp(),
                  "windows_ms": [round(dt1 * 1000, 2),
                                 round(dt2 * 1000, 2)],
                  "window_spread": round(abs(dt1 - dt2) / dt, 4)}}),
        flush=True)


if __name__ == "__main__":
    main()
