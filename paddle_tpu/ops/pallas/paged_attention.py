"""The read half of `paged_attention` as one Pallas TPU kernel.

One program per row of the batch. The row's block table and its length
(`StartPos + NValid`) are scalar-prefetched; the program walks the
`ceil(len / block_size)` table entries the row holds, copies those
pages (and no other) from the pool in HBM into VMEM, `PAGES` at a time
and double-buffered, and folds each chunk of keys into a running
(online) softmax. A page past the row's length costs no DMA, and a row
of length 0 reads nothing, computes nothing and returns exact zeros.

A page of the pool `[nb, bs, H*hd]` is `[bs, H*hd]`, all heads side by
side on the lanes. The heads are kept apart by laying the
row's queries out block-diagonally: query (h, t) is a row of
`[H*T, H*hd]` that is zero outside head h's lanes, so ONE product with
a chunk of keys gives every head's scores, and the product of the
probabilities with the values carries head h's output in head h's
lanes of row (h, t). The products are float32 at full precision; max,
sum and accumulator are float32.

Runs interpreted on the CPU backend, as `flash_attention.py` does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _interpret

# pages copied and folded per turn of the walk: 8 pages of 16 tokens are
# 128 keys, one lane tile of scores
PAGES = 8
LANES = 128
# what the latent kernel may take of VMEM: at 64 heads x 16 tokens its
# scores, probabilities, accumulator and blocks pass the 16 MiB that a
# kernel gets unasked
LATENT_VMEM_BYTES = 64 * 2**20


def pool_lanes(d_model):
    """The lane width of a pool whose tokens are `d_model` wide: whole
    lane tiles, because a page is copied by DMA and Mosaic copies whole
    tiles (at a model's real width, a multiple of 128, nothing is
    added). The lanes past `d_model` stay zero and belong to no head."""
    return -(-int(d_model) // LANES) * LANES


def pad_lanes(x, lanes):
    """`x` [..., d] zero-padded on its last axis to `lanes`."""
    extra = lanes - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, extra)]) \
        if extra else x


def _page_walk(table_ref, b, n_pages, copies, sem, max_blocks):
    """(start_chunk, wait_chunk) over row `b`'s pages: chunk c is pages
    [c pages, (c + 1) pages) of the row's table, copied into buffer
    slot `slot`, once for each (pool, buffer) of `copies`: keys and
    values, or the one pool of a latent cache. A copy is started and
    waited for under the same guard, and a page past the row's length
    costs none."""
    pages = copies[0][1].shape[1]

    def for_held_pages(chunk, slot, act):
        for p in range(pages):
            page = chunk * pages + p

            @pl.when(page < n_pages)
            def _():
                block = table_ref[b * max_blocks + page]
                for which, (hbm, buf) in enumerate(copies):
                    act(pltpu.make_async_copy(
                        hbm.at[block], buf.at[slot, p],
                        sem.at[which, slot, p]))

    def start_chunk(chunk, slot):
        for_held_pages(chunk, slot, lambda dma: dma.start())

    def wait_chunk(chunk, slot):
        for_held_pages(chunk, slot, lambda dma: dma.wait())

    return start_chunk, wait_chunk


def _kernel(table_ref, start_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, qbd_s, qpos_s, m_s, l_s, acc_s, *,
            sm_scale, n_heads, head_dim, max_blocks):
    b = pl.program_id(0)
    _, pages, bs, d = kbuf.shape
    t = q_ref.shape[1]
    span = pages * bs
    length = len_ref[b]
    start = start_ref[b]
    n_pages = (length + bs - 1) // bs
    n_chunks = (n_pages + pages - 1) // pages

    start_chunk, wait_chunk = _page_walk(
        table_ref, b, n_pages, ((k_hbm, kbuf), (v_hbm, vbuf)), sem,
        max_blocks)

    @pl.when(length == 0)
    def _muted():
        o_ref[0] = jnp.zeros((t, d), jnp.float32)

    @pl.when(length > 0)
    def _attend():
        start_chunk(0, 0)
        # the row's queries, block-diagonal: row h*t + i is query i of
        # head h, zero outside that head's lanes; its position beside it
        lane_head = jax.lax.broadcasted_iota(
            jnp.int32, (t, d), 1) // head_dim
        steps = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
        q = q_ref[0]
        for h in range(n_heads):
            qbd_s[pl.ds(h * t, t), :] = jnp.where(lane_head == h, q, 0.0)
            qpos_s[pl.ds(h * t, t), :] = start + steps
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

        def fold(chunk, carry):
            slot = chunk % 2

            @pl.when(chunk + 1 < n_chunks)
            def _next():
                start_chunk(chunk + 1, 1 - slot)

            wait_chunk(chunk, slot)
            k = kbuf[slot].reshape(span, d)
            v = vbuf[slot].reshape(span, d)
            # a page that was not copied holds whatever the buffer held:
            # its scores go under the mask, its values must not reach
            # the product (0 * NaN)
            held = chunk * span + jax.lax.broadcasted_iota(
                jnp.int32, (span, 1), 0) < length
            v = jnp.where(held, v, 0.0)
            s = jax.lax.dot_general(
                qbd_s[...], k, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32) * sm_scale
            kpos = chunk * span + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            keep = jnp.logical_and(kpos <= qpos_s[...], kpos < length)
            s = jnp.where(keep, s, NEG_INF)
            m = m_s[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            m_s[...] = m_new
            return carry

        jax.lax.fori_loop(0, n_chunks, fold, 0)

        # head h's output is head h's lanes of its own rows; every query
        # sees key 0, so no sum is 0
        out = jnp.zeros((t, d), jnp.float32)
        for h in range(n_heads):
            rows = pl.ds(h * t, t)
            out = out + jnp.where(lane_head == h,
                                  acc_s[rows, :] / l_s[rows, :], 0.0)
        o_ref[0] = out


def _kernel_grouped(table_ref, start_ref, len_ref, q_ref, k_hbm, v_hbm,
                    o_ref, kbuf, vbuf, sem, m_s, l_s, acc_s, *, sm_scale,
                    kv_heads, head_dim, tokens, max_blocks):
    """Grouped KV heads, and a pool of any float type: KV head g's
    `head_dim` lanes of a page serve the H / KV query heads of group g,
    whose queries come as the rows (head in group, token) of
    `q_ref[0, g]`. One product a group and chunk; no block-diagonal
    zeros. The pool's numbers are widened to float32 as they are read."""
    b = pl.program_id(0)
    _, pages, bs, d = kbuf.shape
    rows = q_ref.shape[2]
    span = pages * bs
    length = len_ref[b]
    start = start_ref[b]
    n_pages = (length + bs - 1) // bs
    n_chunks = (n_pages + pages - 1) // pages
    start_chunk, wait_chunk = _page_walk(
        table_ref, b, n_pages, ((k_hbm, kbuf), (v_hbm, vbuf)), sem,
        max_blocks)

    @pl.when(length == 0)
    def _muted():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)

    @pl.when(length > 0)
    def _attend():
        start_chunk(0, 0)
        # row r of a group is token r % tokens of one of its heads
        qpos = start + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tokens)
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

        def fold(chunk, carry):
            slot = chunk % 2

            @pl.when(chunk + 1 < n_chunks)
            def _next():
                start_chunk(chunk + 1, 1 - slot)

            wait_chunk(chunk, slot)
            k = kbuf[slot].reshape(span, d).astype(jnp.float32)
            v = vbuf[slot].reshape(span, d).astype(jnp.float32)
            held = chunk * span + jax.lax.broadcasted_iota(
                jnp.int32, (span, 1), 0) < length
            v = jnp.where(held, v, 0.0)
            kpos = chunk * span + jax.lax.broadcasted_iota(
                jnp.int32, (rows, span), 1)
            keep = jnp.logical_and(kpos <= qpos, kpos < length)
            for g in range(kv_heads):
                lanes = slice(g * head_dim, (g + 1) * head_dim)
                s = jax.lax.dot_general(
                    q_ref[0, g], k[:, lanes], (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32) * sm_scale
                s = jnp.where(keep, s, NEG_INF)
                m = m_s[g]
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_s[g] = alpha * l_s[g] + jnp.sum(p, axis=1, keepdims=True)
                acc_s[g] = alpha * acc_s[g] + jax.lax.dot_general(
                    p, v[:, lanes], (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
                m_s[g] = m_new
            return carry

        jax.lax.fori_loop(0, n_chunks, fold, 0)
        for g in range(kv_heads):
            o_ref[0, g] = acc_s[g] / l_s[g]


def _read_grouped(q, pool_k, pool_v, table, start, length, sm_scale,
                  kv_heads):
    B, H, T, hd = q.shape
    nb, bs, d = pool_k.shape
    rep = H // kv_heads
    rows = rep * T
    q_rows = q.astype(jnp.float32).reshape(B, kv_heads, rows, hd)
    kernel = functools.partial(
        _kernel_grouped, sm_scale=float(sm_scale), kv_heads=kv_heads,
        head_dim=hd, tokens=T, max_blocks=table.shape[1])
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    block = vmem((1, kv_heads, rows, hd), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, PAGES, bs, d), pool_k.dtype),
                pltpu.VMEM((2, PAGES, bs, d), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2, PAGES)),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
                pltpu.VMEM((kv_heads, rows, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, kv_heads, rows, hd),
                                       jnp.float32),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.ARBITRARY,)),
        name="paged_attention_read_grouped",
    )(table.reshape(-1), start, length, q_rows, pool_k, pool_v)
    return out.reshape(B, H, T, hd)


# a latent page is one row kind of 640 lanes: 32 pages of 16 tokens are
# 512 keys a turn, so that the walk's own cost is a small share of a
# row of some thousand keys
PAGES_LATENT = 32


def _kernel_latent(table_ref, start_ref, len_ref, q_ref, k_hbm, o_ref,
                   kbuf, sem, m_s, l_s, acc_s, *, sm_scale, tokens,
                   value_lanes, max_blocks):
    """A latent cache: ONE pool, one row a token, shared by every query
    head; a token's values are the first `value_lanes` lanes of its
    key. Each held page is copied once and the copy serves both
    products. The queries come as the rows (head, token) of `q_ref[0]`,
    as wide as the pool. The products take the pool's type with float32
    accumulation (float32 pools: at full precision); max, sum and
    accumulator are float32."""
    b = pl.program_id(0)
    _, pages, bs, d = kbuf.shape
    rows = q_ref.shape[1]
    span = pages * bs
    length = len_ref[b]
    start = start_ref[b]
    n_pages = (length + bs - 1) // bs
    n_chunks = (n_pages + pages - 1) // pages
    start_chunk, wait_chunk = _page_walk(
        table_ref, b, n_pages, ((k_hbm, kbuf),), sem, max_blocks)
    precision = jax.lax.Precision.HIGHEST \
        if kbuf.dtype == jnp.float32 else None

    @pl.when(length == 0)
    def _muted():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(length > 0)
    def _attend():
        start_chunk(0, 0)
        qpos = start + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tokens)
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

        def fold(chunk, carry):
            slot = chunk % 2

            @pl.when(chunk + 1 < n_chunks)
            def _next():
                start_chunk(chunk + 1, 1 - slot)

            wait_chunk(chunk, slot)
            k = kbuf[slot].reshape(span, d)
            held = chunk * span + jax.lax.broadcasted_iota(
                jnp.int32, (span, 1), 0) < length
            v = jnp.where(held, k[:, :value_lanes], 0.0)
            s = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32) * sm_scale
            kpos = chunk * span + jax.lax.broadcasted_iota(
                jnp.int32, (rows, span), 1)
            s = jnp.where(jnp.logical_and(kpos <= qpos, kpos < length),
                          s, NEG_INF)
            m = m_s[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
                p.astype(k.dtype), v, (((1,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32)
            m_s[...] = m_new
            return carry

        jax.lax.fori_loop(0, n_chunks, fold, 0)
        o_ref[0] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


def _read_latent(q, pool, table, start, length, sm_scale, value_lanes):
    B, H, T, _ = q.shape
    nb, bs, d = pool.shape
    rows = H * T
    q_rows = pad_lanes(q.reshape(B, rows, q.shape[-1]), d).astype(pool.dtype)
    kernel = functools.partial(
        _kernel_latent, sm_scale=float(sm_scale), tokens=T,
        value_lanes=value_lanes, max_blocks=table.shape[1])
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[vmem((1, rows, d), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem((1, rows, value_lanes),
                           lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, PAGES_LATENT, bs, d), pool.dtype),
                pltpu.SemaphoreType.DMA((1, 2, PAGES_LATENT)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, value_lanes), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, rows, value_lanes), pool.dtype),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.ARBITRARY,),
            vmem_limit_bytes=LATENT_VMEM_BYTES),
        name="paged_attention_read_latent",
    )(table.reshape(-1), start, length, q_rows, pool)
    return out.reshape(B, H, T, value_lanes)


@functools.partial(jax.jit, static_argnames=("sm_scale", "kv_heads",
                                             "value_lanes"))
def paged_attention_read(q, pool_k, pool_v, table, start, nvalid, *,
                         sm_scale, kv_heads=None, value_lanes=None):
    """Attention of `q` [B, H, T, hd] over each row's own history in the
    paged pools [nb, bs, H*hd]: logical block j of row b is physical
    block `table[b, j]`, query t of row b sits at position
    `start[b] + t` and sees the keys at positions <= its own and below
    the row's length `start[b] + nvalid[b]` (`table`, `start`, `nvalid`
    int32). Rows with `nvalid == 0` return zeros. Returns [B, H, T, hd]
    in float32. With `kv_heads` < H the pools are [nb, bs, KV*hd] and
    KV head g serves query heads [g H/KV, (g+1) H/KV); that case, and a
    pool that is not float32, take the grouped kernel, chosen here from
    the static shapes and types: H == KV over float32 pools compiles to
    the one kernel it always did. With `pool_v` None the pool is a
    latent cache: one row a token for all H heads, `q` as wide as a row
    holds numbers, the values the row's first `value_lanes` lanes;
    returns [B, H, T, value_lanes] in the pool's type.

    Jitted, so that the layers of one program (same shapes) share one
    trace and one lowering of the kernel."""
    B, H, T, hd = q.shape
    nb, bs, d = pool_k.shape
    max_blocks = table.shape[1]
    length = jnp.where(nvalid > 0, start + nvalid, 0)
    if pool_v is None:
        return _read_latent(q, pool_k, table, start, length, sm_scale,
                            int(value_lanes))
    if (kv_heads or H) != H or pool_k.dtype != jnp.float32:
        return _read_grouped(q, pool_k, pool_v, table, start, length,
                             sm_scale, kv_heads or H)
    q_rows = pad_lanes(q.transpose(0, 2, 1, 3).reshape(B, T, H * hd), d)
    kernel = functools.partial(_kernel, sm_scale=float(sm_scale),
                               n_heads=H, head_dim=hd,
                               max_blocks=max_blocks)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                vmem((1, T, d), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=vmem((1, T, d), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, PAGES, bs, d), jnp.float32),
                pltpu.VMEM((2, PAGES, bs, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2, PAGES)),
                pltpu.VMEM((H * T, d), jnp.float32),
                pltpu.VMEM((H * T, 1), jnp.int32),
                pltpu.VMEM((H * T, 1), jnp.float32),
                pltpu.VMEM((H * T, 1), jnp.float32),
                pltpu.VMEM((H * T, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, T, d), jnp.float32),
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.ARBITRARY,)),
        name="paged_attention_read",
    )(table.reshape(-1), start, length, q_rows, pool_k, pool_v)
    return out[..., :H * hd].reshape(B, T, H, hd).transpose(0, 2, 1, 3)
