"""Fused attention ops: the Pallas flash kernel and paged decode.

Reference analogue: operators/fused/multihead_matmul (the fused attention
target of the multihead fusion pass). Here fusion is explicit: one op, one
Pallas kernel, with custom-vjp backward. `paged_attention` is the
serving-side sibling: incremental attention over a block-table paged KV
pool (vLLM's PagedAttention model). Its write is an XLA scatter, its
read a Pallas kernel (ops/pallas/paged_attention.py) that follows each
row's own length; on the CPU the kernel runs interpreted, so tier-1
runs the code the chip runs. Parity with the contiguous path is
token-exact (tests/test_generation.py), not bit-for-bit: the kernel
sums in another order and multiplies at full float32 precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .pallas.flash_attention import flash_attention, reference_attention
from .pallas.paged_attention import pad_lanes, paged_attention_read

# use_flash="auto" crossover (models/transformer.py consults this):
# enable the tiled kernel only at max_seq_len >= this many tokens.
# Where the flip belongs is not measured on the chip: the timings that
# set it (docs/attention_tuning.md) are of a setup that is gone, no
# cell of the benchmark runs the kernel, and ROADMAP.md queue 1, item
# 11 (b) settles it or deletes it.
FLASH_AUTO_MIN_SEQ = 4096


@register_op("flash_attention", stateful=True)
def _flash_attention_op(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    causal = attrs.get("causal", False)
    sm_scale = attrs.get("sm_scale", None)
    dropout = 0.0 if ctx.is_test else attrs.get("attn_dropout", 0.0)
    # tile sizes: an explicit op attr wins; absent attrs stay None so
    # the kernel-level default applies — autotuned tiles when the cache
    # knows this shape, else FLAGS_flash_attention_block_{q,k}
    # (ops/pallas/autotune.py). block_q=0 requests the exact path.
    bq = attrs.get("block_q")
    bk = attrs.get("block_k")
    if bq == 0:  # explicit exact-path request
        out = reference_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                  dropout=dropout,
                                  rng=ctx.rng if dropout else None)
    elif dropout:
        # the tiled kernel has no dropout path; exact fallback keeps the
        # trained model identical (incl. the causal mask) across paths
        out = reference_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                  dropout=dropout, rng=ctx.rng)
    else:
        out = flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                              block_q=bq, block_k=bk)
    return {"Out": [out]}


@register_op("paged_attention", stateful=True,
             nondiff_inputs=("BlockTable", "StartPos", "NValid"))
def _paged_attention_op(ctx, ins, attrs):
    """Incremental attention over a block-table paged KV pool.

    One call both WRITES this step's new K/V into the physical pool and
    READS each row's own history back out of it:

      Q            [B, H, T, hd]   T new tokens per row (decode: T=1,
                                   chunked prefill: T=block_size,
                                   spec verify: T=k+1)
      K/V          [B, KV, T, hd]  KV == H, or grouped: KV divides H and
                                   KV head g serves query heads
                                   [g H/KV, (g+1) H/KV)
      CacheK/V     [nb, bs, lanes] the physical pool: a page is bs
                                   tokens, a token's KV*hd numbers side
                                   by side, heads in order, in
                                   pool_lanes(KV*hd) lanes (why the
                                   heads are no dimension of their own:
                                   models/gpt.build_paged_decode_step),
                                   float32 or bfloat16: new K/V are
                                   rounded to the pool's type as they
                                   are written
      BlockTable   [B, max_blocks] logical block j of row b lives in
                                   physical block BlockTable[b, j]
      StartPos     [B]             position of the row's first new token
      NValid       [B]             how many of the T tokens are real;
                                   0 mutes the row entirely

    Invalid (beyond-NValid) positions write to physical block 0 — the
    engine-reserved scratch block that no table ever maps — so the op
    is total over the fixed shape and the scheduler never needs a
    second executable for partial chunks. The read is a Pallas kernel
    (ops/pallas/paged_attention.py) that walks each row's table as far
    as the row's length StartPos + NValid and no further: key position
    j*bs+o is the row's j-th block at offset o, query t sits at
    StartPos + t and sees the keys at positions <= its own. Table
    entries past the row's pages are never read; a muted row reads
    nothing and returns zeros; rows t >= NValid are don't-care but
    finite.

    Rows are independent of their index, and rows of ONE call may be
    successive pages of one sequence (the same BlockTable row, StartPos
    a page apart: the engine's prefill tiles). That is right because
    every row's new K/V are written into the pool BEFORE any row reads:
    the later page reads from the pool what the earlier page wrote in
    this call. Keep the write ahead of the read.

    A LATENT pool (no V, no CacheV; attr `value_lanes`): a token holds
    ONE row for all H heads, K [B, T, w] written into CacheK
    [nb, bs, pool_lanes(w)]; Q is [B, H, T, w], a token's values are
    the first `value_lanes` numbers of its row, Out is
    [B, H, T, value_lanes], and the kernel copies each held page once
    for scores and values both.
    """
    q, k = ins["Q"][0], ins["K"][0]
    cache_k = ins["CacheK"][0]
    table = ins["BlockTable"][0].astype(jnp.int32)
    start = ins["StartPos"][0].astype(jnp.int32)
    nvalid = ins["NValid"][0].astype(jnp.int32)
    nb, bs, d = cache_k.shape
    B, H, T, hd = q.shape
    latent = not ins.get("V")
    sm_scale = attrs.get("sm_scale") or float(hd) ** -0.5

    steps = jnp.arange(T, dtype=jnp.int32)
    qpos = start[:, None] + steps[None, :]               # [B, T]
    valid = steps[None, :] < nvalid[:, None]             # [B, T]
    phys = jnp.take_along_axis(table, qpos // bs, axis=1)
    flat_idx = jnp.where(valid, phys * bs + qpos % bs, 0)

    def write(pool, new):                # new [B,KV,T,hd] or [B,T,w]
        flat = pool.reshape(nb * bs, d)
        if not latent:
            new = new.transpose(0, 2, 1, 3)
        rows = pad_lanes(new.reshape(B * T, -1), d)
        return flat.at[flat_idx.reshape(-1)].set(
            rows.astype(pool.dtype)).reshape(nb, bs, d)

    ck_new = write(cache_k, k)
    if latent:
        out = paged_attention_read(
            q, ck_new, None, table, start, nvalid,
            sm_scale=float(sm_scale), value_lanes=int(attrs["value_lanes"]))
        return {"Out": [out.astype(q.dtype)], "CacheKOut": [ck_new]}
    v, KV = ins["V"][0], k.shape[1]
    cv_new = write(ins["CacheV"][0], v)
    out = paged_attention_read(q, ck_new, cv_new, table, start, nvalid,
                               sm_scale=float(sm_scale),
                               kv_heads=None if KV == H else KV)
    return {"Out": [out.astype(q.dtype)], "CacheKOut": [ck_new],
            "CacheVOut": [cv_new]}
