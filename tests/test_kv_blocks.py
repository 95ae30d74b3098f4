"""Paged KV-cache bookkeeping tests: BlockPool free-list/refcount
semantics, PrefixCache chain hashing + LRU eviction, and the
block-aware admission errors of GenerationEngine.submit.

Pure host-side unit tests — no programs are built or compiled here
(the paged decode executables are covered end-to-end by
tests/test_generation.py); the engine admission test constructs the
engine without start(), so no warmup runs either.
"""
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt
from paddle_tpu.serving import GenerationEngine, GenerationRequest
from paddle_tpu.serving.kv_blocks import (SCRATCH_BLOCK, BlockPool,
                                          PrefixCache, blocks_for_tokens)


# ---------------------------------------------------------------------------
# blocks_for_tokens
# ---------------------------------------------------------------------------

def test_blocks_for_tokens_ceil():
    assert blocks_for_tokens(0, 16) == 0
    assert blocks_for_tokens(-3, 16) == 0
    assert blocks_for_tokens(1, 16) == 1
    assert blocks_for_tokens(16, 16) == 1
    assert blocks_for_tokens(17, 16) == 2
    assert blocks_for_tokens(32, 16) == 2


# ---------------------------------------------------------------------------
# BlockPool
# ---------------------------------------------------------------------------

def test_block_pool_alloc_order_and_scratch():
    pool = BlockPool(num_blocks=4, block_size=8)
    assert pool.capacity() == 3 and pool.free_count() == 3
    # lowest id first, and the scratch block is never handed out
    assert [pool.alloc() for _ in range(3)] == [1, 2, 3]
    assert SCRATCH_BLOCK not in (1, 2, 3)
    assert pool.alloc() is None          # exhausted, not an exception
    assert pool.used_count() == 3


def test_block_pool_refcount_release():
    pool = BlockPool(num_blocks=4, block_size=8)
    a = pool.alloc()
    assert pool.refcount(a) == 1
    pool.incref(a)                        # shared: two holders
    pool.decref(a)
    assert pool.refcount(a) == 1 and pool.free_count() == 2
    pool.decref(a)                        # last holder gone -> freed
    assert pool.refcount(a) == 0 and pool.free_count() == 3
    assert pool.alloc() == a              # lowest free id again


def test_block_pool_validation():
    with pytest.raises(ValueError):
        BlockPool(num_blocks=1, block_size=8)     # no usable block
    with pytest.raises(ValueError):
        BlockPool(num_blocks=4, block_size=0)
    pool = BlockPool(num_blocks=4, block_size=8)
    with pytest.raises(ValueError):
        pool.incref(SCRATCH_BLOCK)
    with pytest.raises(ValueError):
        pool.decref(2)                            # never allocated


# ---------------------------------------------------------------------------
# PrefixCache
# ---------------------------------------------------------------------------

def test_chunk_hashes_chain_semantics():
    bs = 4
    h_ab = PrefixCache.chunk_hashes([1, 2, 3, 4, 5, 6, 7, 8], bs)
    assert len(h_ab) == 2
    # same first block -> same first hash; the chain makes the second
    # hash cover the whole prefix, not just its own tokens
    h_ac = PrefixCache.chunk_hashes([1, 2, 3, 4, 9, 9, 9, 9], bs)
    assert h_ac[0] == h_ab[0] and h_ac[1] != h_ab[1]
    # same second block under a DIFFERENT first block must not collide
    h_db = PrefixCache.chunk_hashes([0, 0, 0, 0, 5, 6, 7, 8], bs)
    assert h_db[1] != h_ab[1]
    # partial tail blocks are not hashable
    assert len(PrefixCache.chunk_hashes([1, 2, 3, 4, 5], bs)) == 1
    assert PrefixCache.chunk_hashes([1, 2], bs) == []


def test_prefix_cache_lookup_insert_and_cap():
    bs = 4
    pool = BlockPool(num_blocks=8, block_size=bs)
    cache = PrefixCache(pool)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    hashes = PrefixCache.chunk_hashes(prompt, bs)
    b1, b2 = pool.alloc(), pool.alloc()
    assert cache.insert(hashes[0], b1)
    assert cache.insert(hashes[1], b2)
    assert not cache.insert(hashes[0], b2)   # first writer wins
    assert pool.refcount(b1) == 2            # slot ref + cache ref

    n, ids = cache.lookup(prompt, max_tokens=len(prompt) - 1)
    assert n == 8 and ids == [b1, b2]
    assert pool.refcount(b1) == 3            # lookup increfs for caller
    # max_tokens caps the match at full blocks below the limit: a
    # 5-token prompt may only reuse tokens 0..3 (position 4 must stay
    # writable for the adopting slot's first decode step)
    n, ids = cache.lookup([1, 2, 3, 4, 5], max_tokens=4)
    assert n == 4 and ids == [b1]
    # a diverging prompt matches only up to the divergence
    n, ids = cache.lookup([1, 2, 3, 4, 9, 9, 9, 9, 0], max_tokens=8)
    assert n == 4 and ids == [b1]


def test_prefix_cache_evict_lru_skips_live_blocks():
    bs = 2
    pool = BlockPool(num_blocks=6, block_size=bs)
    cache = PrefixCache(pool)
    h = PrefixCache.chunk_hashes([1, 2, 3, 4, 5, 6], bs)
    blocks = [pool.alloc() for _ in range(3)]
    for hj, bj in zip(h, blocks):
        cache.insert(hj, bj)
    # slots drop their refs on blocks 0 and 2; block 1 stays live
    pool.decref(blocks[0])
    pool.decref(blocks[2])
    assert cache.evictable_count() == 2
    assert cache.evict_lru() == blocks[0]    # oldest evictable first
    assert cache.evict_lru() == blocks[2]    # blocks[1] is protected
    assert cache.evict_lru() is None
    assert len(cache) == 1 and pool.free_count() == 4


# ---------------------------------------------------------------------------
# block-aware admission errors (satellite: GenerationEngine.submit)
# ---------------------------------------------------------------------------

def test_submit_error_names_blocks_needed_vs_available():
    cfg = gpt.gpt_small(vocab_size=16, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=16,
                        dropout=0.0, use_flash=False)
    eng = GenerationEngine(cfg, fluid.Scope(), exe=fluid.Executor(),
                           max_slots=2, max_seq=16, block_size=4)
    # prompt + max_new - 1 = 20 tokens -> 5 blocks > the 4-block table
    with pytest.raises(ValueError) as ei:
        eng.submit(GenerationRequest(list(range(10)), 11))
    msg = str(ei.value)
    assert "5 KV blocks" in msg and "block table holds at most 4" in msg

    # a pool smaller than a request's worst case: the error must name
    # the pool's allocatable capacity, not the table bound
    small = GenerationEngine(cfg, fluid.Scope(), exe=fluid.Executor(),
                             max_slots=2, max_seq=16, block_size=4,
                             kv_pool_blocks=4)   # 3 allocatable
    with pytest.raises(ValueError) as ei:
        small.submit(GenerationRequest(list(range(10)), 7))  # 4 blocks
    msg = str(ei.value)
    assert "4 KV blocks" in msg and "only 3 allocatable blocks" in msg
    assert "free now" in msg
