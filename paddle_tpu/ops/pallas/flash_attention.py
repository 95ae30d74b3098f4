"""Flash attention (forward + backward) as Pallas TPU kernels.

Replaces the composed matmul->softmax->matmul attention (reference
multihead path, operators/fused/multihead_matmul + the PaddleNLP attention
assembly) with an online-softmax tiled kernel: Q stays resident in VMEM per
block, K/V stream through in blocks, the softmax normaliser is carried as
running (max, sum) — O(T) memory instead of O(T^2), MXU-sized tiles.

Backward uses the FlashAttention-2 recomputation scheme: per (q-block,
k-block) tile recompute p = exp(qk - lse), accumulate dq, dk, dv. Wired to
jax.custom_vjp so both the IR-level generic grad (core/lowering.py) and
dygraph tape differentiate through it for free.

Runs in interpret mode on the CPU backend (tests) and under
FLAGS_pallas_interpret, same numerics; on any other backend that is not
a TPU it raises.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    """Interpret mode only where it is asked for (FLAGS_pallas_interpret)
    or where there is no device to run a kernel on (the CPU backend). A
    backend that is neither a TPU nor the CPU is an error: a Mosaic
    kernel cannot run there, and interpreting it silently would pass a
    run that never touched the kernel."""
    from ...core.flags import FLAGS
    backend = jax.default_backend()
    if FLAGS.pallas_interpret or backend == "cpu":
        return True
    if backend != "tpu":
        raise RuntimeError(
            f"flash_attention: backend {backend!r} is neither a TPU nor "
            f"the CPU; set FLAGS_pallas_interpret to interpret the "
            f"kernel there")
    return False


NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward kernel: grid = (batch*heads, num_q_blocks, num_k_blocks) — the
# k dimension is a GRID dimension (ARBITRARY semantics) rather than an
# in-kernel fori_loop, so Pallas streams k/v blocks through VMEM with
# automatic double buffering (DMA of block j+1 overlaps compute on j);
# the (m, l, acc) softmax state lives in VMEM scratch, which persists
# across the sequentially-executed innermost grid dimension.
# ---------------------------------------------------------------------------

def _mask_block(s, qi, kb, block_q, block_k, causal, kv_len, t):
    if causal or kv_len < t:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        keep = kpos < kv_len
        if causal:
            keep = jnp.logical_and(keep, qpos >= kpos)
        s = jnp.where(keep, s, NEG_INF)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                sm_scale, causal, kv_len, t):
    # block shapes carry a leading singleton (bh) dim: q_ref[0] = [bq, d],
    # k_ref[0]/v_ref[0] = [bk, d]. Operands stay in their input dtype
    # (bf16 under AMP) so the MXU runs its fast path; accumulation is f32.
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _mask_block(s, qi, kb, block_q, block_k, causal, kv_len, t)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    if causal:
        # blocks entirely above the diagonal contribute nothing
        pl.when(kb * block_k <= (qi + 1) * block_q - 1)(body)
    else:
        body()

    @pl.when(kb == nkb - 1)
    def _finish():
        l_safe = jnp.maximum(l_s[...], 1e-20)
        o_ref[0] = (acc_s[...] / l_safe).astype(o_ref.dtype)
        # lse is carried as [bh, 8, T] — replicated across an 8-sublane
        # dim so its blocks satisfy the TPU (8, 128) tile constraint.
        lse_ref[0] = jnp.broadcast_to(
            (m_s[...] + jnp.log(l_safe)).reshape(1, block_q),
            (8, block_q))


def _compiler_params():
    """bh/q dims parallel, the streamed dim arbitrary (sequential —
    scratch state persists across it)."""
    sem = pltpu.GridDimensionSemantics
    return pltpu.CompilerParams(dimension_semantics=(
        sem.PARALLEL, sem.PARALLEL, sem.ARBITRARY))


def _scratch(shape):
    return pltpu.VMEM(shape, jnp.float32)


def _kv_index(causal, block_q, block_k):
    """k/v BlockSpec index for the (bh, q, k) grids. Causal: clamp j to
    the diagonal block — consecutive skipped grid steps then map to the
    SAME block index, so Pallas performs no new DMA for them (the
    in-kernel pl.when already skips their compute)."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def index(b, i, j):
        jmax = ((i + 1) * block_q - 1) // block_k
        return (b, jnp.minimum(j, jmax), 0)
    return index


def _fwd(q, k, v, sm_scale, causal, block_q, block_k, kv_len):
    bh, t, d = q.shape
    grid = (bh, t // block_q, t // block_k)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale,
                               causal=causal, kv_len=kv_len, t=t)
    kw = {"memory_space": pltpu.VMEM}
    kv_idx = _kv_index(causal, block_q, block_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **kw),
            pl.BlockSpec((1, block_k, d), kv_idx, **kw),
            pl.BlockSpec((1, block_k, d), kv_idx, **kw),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0), **kw),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i), **kw),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, t), jnp.float32),
        ],
        scratch_shapes=[_scratch((block_q, 1)), _scratch((block_q, 1)),
                        _scratch((block_q, d))],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward: two tiled passes (FlashAttention-2 scheme), both O(T) memory:
#   dq pass:    grid (bh, q_blocks), stream k-blocks, accumulate dq
#   dk/dv pass: grid (bh, k_blocks), stream q-blocks, accumulate dk, dv
# Each tile recomputes p = exp(qk - lse); delta = rowsum(do*o) is computed
# once per row up front (FlashAttention-2) and streamed into both kernels.
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, delta_ref, lse_ref, do_ref, dq_ref,
                   dq_s, *, sm_scale, causal, kv_len, t):
    # grid (bh, q_blocks, k_blocks): k/v stream through the innermost
    # dim; dq accumulates in VMEM scratch and is flushed once.
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _init():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)

    def body():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, :].astype(jnp.float32)
        delta = delta_ref[0, 0, :].astype(jnp.float32)[:, None]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _mask_block(s, qi, kb, block_q, block_k, causal, kv_len, t)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_s[...] = dq_s[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kb * block_k <= (qi + 1) * block_q - 1)(body)
    else:
        body()

    @pl.when(kb == nkb - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, delta_ref, lse_ref, do_ref,
                    dk_ref, dv_ref, dk_s, dv_s, *, sm_scale, causal,
                    kv_len, t):
    # grid (bh, k_blocks, q_blocks): q/do stream through the innermost
    # dim; dk/dv accumulate in VMEM scratch.
    ki = pl.program_id(1)
    qb = pl.program_id(2)
    nqb = pl.num_programs(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(qb == 0)
    def _init():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)
        dv_s[...] = jnp.zeros(dv_s.shape, jnp.float32)

    def body():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, :].astype(jnp.float32)
        delta = delta_ref[0, 0, :].astype(jnp.float32)[:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        s = _mask_block(s, qb, ki, block_q, block_k, causal, kv_len, t)
        p = jnp.exp(s - lse[:, None])
        dv_s[...] = dv_s[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_s[...] = dk_s[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # q blocks strictly before the diagonal see no keys of this
        # k block
        pl.when((qb + 1) * block_q - 1 >= ki * block_k)(body)
    else:
        body()

    @pl.when(qb == nqb - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, kv_len, res, do):
    q, k, v, o, lse = res
    bh, t, d = q.shape
    # delta = rowsum(do * o), once per row; XLA fuses this elementwise
    # reduction, the kernels just stream the [bh, t] result.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # replicate across the 8-sublane dim to match the lse carry layout
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, t))
    kw = {"memory_space": pltpu.VMEM}

    # dq pass: (bh, q, k) — fix q block on the middle dim
    spec_q_qk = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                             **kw)
    spec_k_qk = pl.BlockSpec((1, block_k, d),
                             _kv_index(causal, block_q, block_k), **kw)
    spec_lse_qk = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i),
                               **kw)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          kv_len=kv_len, t=t),
        grid=(bh, t // block_q, t // block_k),
        in_specs=[spec_q_qk, spec_k_qk, spec_k_qk, spec_lse_qk,
                  spec_lse_qk, spec_q_qk],
        out_specs=spec_q_qk,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name="flash_attention_bwd_dq",
    )(q, k, v, delta, lse, do)

    # dk/dv pass: (bh, k, q) — fix k block on the middle dim. Causal:
    # q blocks strictly before this k block contribute nothing; clamp
    # their index up to the diagonal so skipped steps re-map to an
    # already-fetched block (no DMA), mirroring _kv_index.
    if causal:
        def q_idx(b, i, j):
            jmin = (i * block_k) // block_q
            return (b, jnp.maximum(j, jmin), 0)

        def lse_idx(b, i, j):
            jmin = (i * block_k) // block_q
            return (b, 0, jnp.maximum(j, jmin))
    else:
        def q_idx(b, i, j):
            return (b, j, 0)

        def lse_idx(b, i, j):
            return (b, 0, j)
    spec_q_kq = pl.BlockSpec((1, block_q, d), q_idx, **kw)
    spec_k_kq = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0),
                             **kw)
    spec_lse_kq = pl.BlockSpec((1, 8, block_q), lse_idx, **kw)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, kv_len=kv_len, t=t),
        grid=(bh, t // block_k, t // block_q),
        in_specs=[spec_q_kq, spec_k_kq, spec_k_kq, spec_lse_kq,
                  spec_lse_kq, spec_q_kq],
        out_specs=[spec_k_kq, spec_k_kq],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype)] * 2,
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name="flash_attention_bwd_dkv",
    )(q, k, v, delta, lse, do)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, kv_len):
    o, _ = _fwd(q, k, v, sm_scale, causal, block_q, block_k, kv_len)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, kv_len):
    o, lse = _fwd(q, k, v, sm_scale, causal, block_q, block_k, kv_len)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def reference_attention(q, k, v, causal=False, sm_scale=None, dropout=0.0,
                        rng=None):
    """Naive exact attention over [..., T, d]; same numerics as the Pallas
    kernel. Used when block divisibility fails or attention dropout is on
    (the tiled kernel has no dropout path)."""
    d = q.shape[-1]
    t = q.shape[-2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qpos = jnp.arange(t)[:, None]
        kpos = jnp.arange(t)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    if dropout and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout), 0.0)
    return jnp.einsum("...qk,...kd->...qd", w.astype(q.dtype), v)


def _pick_block(t, want):
    """Largest TPU-legal block size for a 128-aligned t: divides t AND is
    a multiple of 128 (lane-dim tiling of the lse carry). Requests below
    128 are clamped up — sub-128 tiles cannot satisfy the lse lane
    constraint. t is always a 128-multiple here, so b=128 is the floor."""
    want = min(max(want, 128), t)
    for b in range(want - want % 128, 0, -128):
        if t % b == 0:
            return b
    return t


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=None,
                    block_k=None):
    """q, k, v: [batch, heads, T, head_dim] (or [bh, T, d]).
    Returns attention output, same shape/dtype as q. Falls back to the
    exact naive path when T has no usable tile divisor.

    block_q/block_k=None (the default) delegates tile choice to the
    autotuner (ops/pallas/autotune.py: memo -> persistent cache ->
    timed sweep under FLAGS_flash_autotune=full) and, on a miss, to
    FLAGS_flash_attention_block_{q,k} — no call path pins a tile."""
    orig_shape = q.shape
    if q.ndim == 4:
        b, h, t, d = q.shape
        q = q.reshape(b * h, t, d)
        k = k.reshape(b * h, t, d)
        v = v.reshape(b * h, t, d)
    t, d = q.shape[1], q.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if t < 128:
        # short sequences: exact path is cheaper than kernel padding
        out = reference_attention(q, k, v, causal=causal, sm_scale=sm_scale)
        return out.reshape(orig_shape)
    # Pad T to a 128-multiple so every length stays on the flash path; the
    # kernels mask padded key columns (kv_len), padded query rows are
    # sliced off below. Zero-padding is grad-safe: masked columns get p=0
    # and padded rows get zero cotangents.
    t_pad = (t + 127) & ~127
    if t_pad != t:
        pad = [(0, 0), (0, t_pad - t), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    if block_q is None or block_k is None:
        from ...core.flags import FLAGS
        from . import autotune
        tuned = autotune.resolve(t_pad, d, q.dtype, causal)
        dq, dk = tuned if tuned is not None else (
            FLAGS.flash_attention_block_q, FLAGS.flash_attention_block_k)
        if block_q is None:
            block_q = dq
        if block_k is None:
            block_k = dk
    block_q = _pick_block(t_pad, block_q)
    block_k = _pick_block(t_pad, block_k)
    # trace-time gauges: the tile the compiled program actually runs
    # (the sweep ledger's "blk512 really means 512" evidence)
    from ...monitor import STAT_SET
    STAT_SET("flash.block_q", block_q)
    STAT_SET("flash.block_k", block_k)
    out = _flash(q, k, v, float(sm_scale), bool(causal), block_q, block_k,
                 t)
    if t_pad != t:
        out = out[:, :t, :]
    return out.reshape(orig_shape)
