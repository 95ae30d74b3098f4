"""The paged_attention op: its Pallas read (ops/pallas/paged_attention.py,
interpreted here as on every CPU run) against a plain gather-and-softmax
over the whole table, and its write against a plain scatter.

The reference is the formulation the op itself used until PR 26: gather
every table entry, mask by position, one softmax over max_seq. The
kernel must agree with it wherever the reference is defined, and must
not touch what a row does not hold: table entries past a row's pages
name pool blocks poisoned with NaN here.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import attention
from paddle_tpu.ops.pallas import paged_attention as kernel

BS, H, HD, MB = 4, 4, 8, 6          # the tests' toy page; max_seq 24
MAX_SEQ = BS * MB
SPEC_K = 2
B = 4


def reference_read(q, pool_k, pool_v, table, start, sm_scale):
    """jnp.take of every table entry, the [B, T, max_t] mask, one
    softmax: what the op did before the kernel."""
    nb, bs, lanes = pool_k.shape
    b, h, t, hd = q.shape
    max_t = table.shape[1] * bs

    def history(pool):
        g = jnp.take(pool[..., :h * hd], table, axis=0)
        return g.reshape(b, max_t, h, hd).transpose(0, 2, 1, 3)

    keys, vals = history(pool_k), history(pool_v)
    qpos = start[:, None] + jnp.arange(t)[None, :]
    scores = jnp.einsum("bhtd,bhsd->bhts", q, keys,
                        precision="highest") * sm_scale
    keep = jnp.arange(max_t)[None, None, :] <= qpos[:, :, None]
    scores = jnp.where(keep[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", probs, vals, precision="highest")


def reference_write(pool, new, table, start, nvalid):
    """Token t of row b goes to block table[b, pos // bs], offset
    pos % bs; nothing else changes (block 0 takes the invalid ones)."""
    pool = np.array(pool)
    b, h, t, hd = new.shape
    bs = pool.shape[1]
    for i in range(b):
        for j in range(int(nvalid[i])):
            pos = int(start[i]) + j
            pool[table[i, pos // bs], pos % bs, :h * hd] = \
                np.asarray(new[i, :, j, :]).reshape(-1)
    return pool


def run_op(q, k, v, pool_k, pool_v, table, start, nvalid):
    out = attention._paged_attention_op(
        None,
        {"Q": [q], "K": [k], "V": [v], "CacheK": [pool_k],
         "CacheV": [pool_v], "BlockTable": [jnp.asarray(table)],
         "StartPos": [jnp.asarray(start)], "NValid": [jnp.asarray(nvalid)]},
        {"sm_scale": float(q.shape[-1]) ** -0.5})
    return out["Out"][0], out["CacheKOut"][0], out["CacheVOut"][0]


class Batch:
    """Pools whose blocks 1..nb-2 hold finite numbers and whose last
    block is NaN; every table entry past a row's pages names the NaN
    block. `rows` is [(start, nvalid)]; `share` lets two rows name one
    physical block as their first (a prefix both hold)."""

    def __init__(self, t, rows, bs=BS, h=H, hd=HD, mb=MB, share=None,
                 seed=0):
        rng = np.random.default_rng(seed)
        b = len(rows)
        lanes = kernel.pool_lanes(h * hd)
        self.nb = nb = b * mb + 2
        self.poison = nb - 1
        pools = rng.normal(size=(2, nb, bs, lanes)).astype(np.float32)
        pools[..., h * hd:] = 0.0
        pools[:, self.poison] = np.nan
        self.pool_k, self.pool_v = jnp.asarray(pools[0]), jnp.asarray(pools[1])
        self.q, self.k, self.v = (
            jnp.asarray(rng.normal(size=(b, h, t, hd)).astype(np.float32))
            for _ in range(3))
        self.start = np.array([s for s, _ in rows], np.int32)
        self.nvalid = np.array([n for _, n in rows], np.int32)
        self.table = np.full((b, mb), self.poison, np.int32)
        free = list(rng.permutation(np.arange(1, nb - 1)))
        for i, (s, n) in enumerate(rows):
            pages = -(-(s + n) // bs) if n else 0
            self.table[i, :pages] = [free.pop() for _ in range(pages)]
        if share is not None:
            i, j = share
            self.table[j, 0] = self.table[i, 0]

    def check(self):
        out, ck, cv = run_op(self.q, self.k, self.v, self.pool_k,
                             self.pool_v, self.table, self.start,
                             self.nvalid)
        out = np.asarray(out)
        assert np.isfinite(out).all()     # don't-care rows included
        want_k = reference_write(self.pool_k, self.k, self.table,
                                 self.start, self.nvalid)
        want_v = reference_write(self.pool_v, self.v, self.table,
                                 self.start, self.nvalid)
        # bit for bit (NaN == NaN here); block 0 is the scratch block
        np.testing.assert_array_equal(np.asarray(ck)[1:], want_k[1:])
        np.testing.assert_array_equal(np.asarray(cv)[1:], want_v[1:])
        # the reference gathers every entry: give it a table that names
        # the scratch block where the kernel must not look, and pools
        # without the poison
        clean = np.where(self.table == self.poison, 0, self.table)
        ref = np.asarray(reference_read(
            self.q, jnp.nan_to_num(jnp.asarray(want_k)),
            jnp.nan_to_num(jnp.asarray(want_v)), jnp.asarray(clean),
            jnp.asarray(self.start), float(self.q.shape[-1]) ** -0.5))
        for i, n in enumerate(self.nvalid):
            if n == 0:
                assert (out[i] == 0.0).all()   # exact zeros, never NaN
            else:
                np.testing.assert_allclose(out[i, :, :n], ref[i, :, :n],
                                           rtol=2e-5, atol=2e-6)
        return out


def rows_for(kind, t):
    """Four rows; row 0 is the one the case is named for."""
    other = [(0, 0), (5, min(t, 2)), (BS, t)]
    return {
        "muted": [(7, 0)] + other,
        "ends_mid_page": [(BS + 1, t)] + other,       # length bs + 1 + t
        "ends_on_page_boundary": [(2 * BS - t, t)] + other,
        "ends_at_max_seq": [(MAX_SEQ - t, t)] + other,
        "start_pos_0": [(0, t)] + other,
        "one_valid_token": [(9, 1)] + other,
        "shares_a_block": [(BS, t), (0, 0), (5, min(t, 2)), (2 * BS, t)],
    }[kind]


KINDS = ["muted", "ends_mid_page", "ends_on_page_boundary",
         "ends_at_max_seq", "start_pos_0", "one_valid_token",
         "shares_a_block"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("t", [1, BS, SPEC_K + 1],
                         ids=["decode", "chunk_prefill", "spec_verify"])
def test_kernel_matches_gather_reference(t, kind):
    share = (0, 3) if kind == "shares_a_block" else None
    Batch(t, rows_for(kind, t), share=share,
          seed=KINDS.index(kind)).check()


def test_all_rows_muted_return_zeros():
    out = Batch(BS, [(0, 0)] * B).check()
    assert (out == 0.0).all()


def test_long_row_walks_several_chunks():
    """More pages than the kernel folds at a time, and a last chunk it
    holds only partly."""
    mb = 2 * kernel.PAGES + 3
    length = BS * (2 * kernel.PAGES + 1) + 2
    Batch(1, [(length - 1, 1), (0, 0), (3, 1), (BS * kernel.PAGES - 1, 1)],
          mb=mb).check()


@pytest.mark.parametrize("pool", ["dense", "latent"])
def test_pages_of_one_sequence_in_one_call_match_a_page_a_call(pool):
    """Rows of one call may be successive pages of ONE sequence (the
    engine's prefill tiles): rows 0..2 share a block-table row at page
    starts 0, BS, 2 BS, the last partial, row 3 is a page of another
    sequence. The op writes every row's keys before any row reads, so
    row 1 reads from the pool what row 0 wrote in that call: outputs
    and pools equal those of the same pages fed one a call, each
    reading what the calls before it wrote."""
    rng = np.random.default_rng(7)
    t, width = BS, (3 * HD if pool == "latent" else H * HD)
    lanes = kernel.pool_lanes(width)
    nb = 2 * MB + 1
    start = np.array([0, BS, 2 * BS, BS], np.int32)
    nvalid = np.array([BS, BS, BS - 1, BS], np.int32)
    table = np.zeros((B, MB), np.int32)
    table[:3] = 1 + np.arange(MB)                # one sequence, thrice
    table[3] = 1 + MB + np.arange(MB)            # another

    def draw(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32))

    def padded():
        a = np.zeros((nb, BS, lanes), np.float32)
        a[..., :width] = rng.normal(size=(nb, BS, width))
        return jnp.asarray(a)

    if pool == "latent":
        q, k, v = draw(B, H, t, width), draw(B, t, width), None
        pools = [padded()]
        attrs = {"sm_scale": 0.2, "value_lanes": 2 * HD}
    else:
        q, k, v = draw(B, H, t, HD), draw(B, H, t, HD), draw(B, H, t, HD)
        pools = [padded(), padded()]
        attrs = {"sm_scale": HD ** -0.5}

    def call(pools, nvalid):
        ins = {"Q": [q], "K": [k], "CacheK": [pools[0]],
               "BlockTable": [jnp.asarray(table)],
               "StartPos": [jnp.asarray(start)],
               "NValid": [jnp.asarray(nvalid)]}
        if v is not None:
            ins.update(V=[v], CacheV=[pools[1]])
        out = attention._paged_attention_op(None, ins, attrs)
        new = [out["CacheKOut"][0]] + \
            ([out["CacheVOut"][0]] if v is not None else [])
        return np.asarray(out["Out"][0]), new

    packed, packed_pools = call(pools, nvalid)
    serial_pools = pools
    for r in range(B):                           # a page a call, in order
        alone = np.where(np.arange(B) == r, nvalid, 0).astype(np.int32)
        out, serial_pools = call(serial_pools, alone)
        n = int(nvalid[r])
        np.testing.assert_array_equal(packed[r, :, :n], out[r, :, :n])
    for a, b in zip(packed_pools, serial_pools):
        # block 0 is the scratch block: it takes the muted positions
        np.testing.assert_array_equal(np.asarray(a)[1:], np.asarray(b)[1:])
    # and the later tile did read the earlier one's keys: with the
    # sequence's first page muted, row 1 answers otherwise
    other, _ = call(pools, np.array([0, BS, BS - 1, BS], np.int32))
    assert np.abs(other[1] - packed[1]).max() > 1e-3


def test_cell_page_shape():
    """The serving cells' page: 16 tokens of 16 heads x 64."""
    Batch(1, [(36, 1), (0, 0)], bs=16, h=16, hd=64, mb=4).check()
    Batch(16, [(32, 16), (16, 5)], bs=16, h=16, hd=64, mb=4).check()


def test_pool_lanes_are_whole_tiles():
    assert kernel.pool_lanes(32) == 128
    assert kernel.pool_lanes(1024) == 1024
    assert kernel.pool_lanes(768) == 768
    x = jnp.ones((2, 3, 5))
    assert kernel.pad_lanes(x, 5) is x
    padded = kernel.pad_lanes(x, 8)
    assert padded.shape == (2, 3, 8) and float(padded[..., 5:].sum()) == 0.0


# -- the kernel, compiled for the chip (no chip needed) ---------------------

@pytest.fixture(scope="module")
def one_chip():
    """A described v5e, as the on-chip-measurement guide sets out: only
    inside a fixture, so that every worker collects the same tests."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("t,h,hd,slots,mb", [
    (1, 16, 64, 32, 64), (16, 16, 64, 32, 64), (9, 16, 64, 32, 64),
    (1, 4, 8, 8, 8), (16, 4, 8, 8, 8)],
    ids=["cell_decode", "cell_chunk_prefill", "cell_spec_verify_k8",
         "chip_smoke_decode", "chip_smoke_chunk_prefill"])
def test_kernel_compiles_for_v5e(one_chip, monkeypatch, t, h, hd, slots, mb):
    """Mosaic takes the kernel at the sizes the repo runs on the chip,
    and the pools reach it with no copy (what a [.., H, hd] pool cost:
    PERF.md section 6, PR 26)."""
    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    bs, lanes = 16, kernel.pool_lanes(h * hd)
    nb = slots * mb + 1

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    read = jax.jit(lambda *a: kernel.paged_attention_read.__wrapped__(
        *a, sm_scale=hd ** -0.5))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = read.lower(
            arg((slots, h, t, hd), jnp.float32),
            arg((nb, bs, lanes), jnp.float32),
            arg((nb, bs, lanes), jnp.float32),
            arg((slots, mb), jnp.int32), arg((slots,), jnp.int32),
            arg((slots,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in text
    pool = f"f32[{nb},{bs},{lanes}]"
    copies = [ln for ln in text.splitlines()
              if f"= {pool}" in ln and " copy(" in ln]
    assert not copies, copies


@pytest.mark.parametrize("t", [1, 16], ids=["decode", "chunk_prefill"])
def test_grouped_kernel_compiles_for_v5e(one_chip, monkeypatch, t):
    """The grouped kernel at `nemotron3_super_ep4_l11`'s sizes: 32
    query heads over 2 KV heads of 128, 64 slots of 2,048 positions, a
    bfloat16 pool of 256 lanes that reaches the kernel with no copy."""
    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    h, kv, hd, slots, mb, bs = 32, 2, 128, 64, 128, 16
    lanes, nb = kernel.pool_lanes(kv * hd), slots * mb + 1

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    read = jax.jit(lambda *a: kernel.paged_attention_read.__wrapped__(
        *a, sm_scale=hd ** -0.5, kv_heads=kv))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = read.lower(
            arg((slots, h, t, hd), jnp.float32),
            arg((nb, bs, lanes), jnp.bfloat16),
            arg((nb, bs, lanes), jnp.bfloat16),
            arg((slots, mb), jnp.int32), arg((slots,), jnp.int32),
            arg((slots,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in text
    copies = [ln for ln in text.splitlines()
              if f"= bf16[{nb},{bs},{lanes}]" in ln and " copy(" in ln]
    assert not copies, copies


@pytest.mark.parametrize("t", [1, 16, 5],
                         ids=["decode", "chunk_prefill", "spec_verify_k4"])
def test_latent_kernel_compiles_for_v5e(one_chip, monkeypatch, t):
    """The latent kernel at `kimi_k2_5_ep32_l5`'s sizes: 64 query heads
    over ONE pool of 576-number rows in 640 lanes, 64 slots of 9,216
    positions, bfloat16; the pool reaches the kernel with no copy and
    is its only pool operand."""
    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    h, row, rank, slots, mb, bs = 64, 576, 512, 64, 576, 16
    lanes, nb = kernel.pool_lanes(row), slots * mb + 1
    assert lanes == 640

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    read = jax.jit(lambda q, pool, *a: kernel.paged_attention_read.__wrapped__(
        q, pool, None, *a, sm_scale=0.1447, value_lanes=rank))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = read.lower(
            arg((slots, h, t, row), jnp.bfloat16),
            arg((nb, bs, lanes), jnp.bfloat16),
            arg((slots, mb), jnp.int32), arg((slots,), jnp.int32),
            arg((slots,), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert "tpu_custom_call" in text
    copies = [ln for ln in text.splitlines()
              if f"= bf16[{nb},{bs},{lanes}]" in ln and " copy(" in ln]
    assert not copies, copies


# -- the expert layers' grouped product, compiled for the chip: in this
# file because it is the one that loads the TPU's compiler --------------------

@pytest.mark.parametrize("m", ["decode", "prefill"])
@pytest.mark.parametrize("held,d_in,f_in,f_out,d_out,act,rows", [
    (128, 1024, 2688, 2688, 1024, "_relu2", (1408, 22528)),
    (12, 7168, 4096, 2048, 7168, "_swiglu", (512, 8192)),
    (32, 2304, 2048, 1024, 2304, "_swiglu", (1024, 16384))],
    ids=["nemotron3_super_ep4_l11", "kimi_k2_5_ep32_l5",
         "kimi_linear_ep8_l8"])
def test_grouped_matmul_compiles_for_v5e(one_chip, monkeypatch, held, d_in,
                                         f_in, f_out, d_out, act, rows, m):
    """`experts_apply` at the sizes of the three expert cells' decode
    and prefill steps (slots x tokens x top_k rows, bfloat16): Mosaic
    takes both products' kernels, and the held experts' weights reach
    them as they lie in HBM, with no copy and no transpose."""
    from paddle_tpu.ops.pallas import grouped_matmul
    from paddle_tpu.parallel import moe
    monkeypatch.setattr(grouped_matmul, "_interpret", lambda: False)
    grouped_matmul._product.clear_cache()   # no interpreted trace is reused
    m = rows[m == "prefill"]

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layer = jax.jit(lambda x, sizes, w1, w2: moe.experts_apply(
        x, sizes, w1, w2, getattr(moe, act)))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = layer.lower(
            arg((m, d_in)), arg((held,), jnp.int32),
            arg((held, d_in, f_in)), arg((held, f_out, d_out))
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    weights = (f"= bf16[{held},{d_in},{f_in}]",
               f"= bf16[{held},{f_out},{d_out}]")
    moved = [ln for ln in text.splitlines()
             if any(w in ln for w in weights)
             and (" copy(" in ln or " transpose(" in ln)]
    assert not moved, moved
