"""Disaggregated prefill/decode serving tests: the KV wire format
(byte-exact fp32/bf16 round-trips), cross-engine export -> adopt with
refcount/parity checks, graph-opt-level invariance of a decode worker
continuing on adopted blocks under eviction pressure, the fleet-level
content-addressed prefix store, router role restriction, and the
in-process two-phase prefill->decode dispatch end to end.

Same exactness discipline as tests/test_generation.py: the model is
trained on the cyclic-successor task, so any divergence between a
decode worker running on shipped KV and the unified engine shows up as
a wrong token, never a tolerance failure.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt
from paddle_tpu.serving import (FleetPrefixStore, GenerationEngine,
                                PrefixCache, Replica, Router,
                                adopt_prefix, export_prefix)
from paddle_tpu.serving.kv_wire import (pack_blocks, payload_bytes,
                                        unpack_blocks)

VOCAB, SEQ, BLOCK = 16, 12, 4


@pytest.fixture(scope="module")
def trained():
    """Tiny GPT trained on the cyclic-successor task; returns
    (cfg, scope).  Greedy continuation of [a, b, c] is
    [(c+1) % VOCAB, (c+2) % VOCAB, ...]."""
    cfg = gpt.gpt_small(vocab_size=VOCAB, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=SEQ,
                        dropout=0.0, use_flash=False)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        loss, _, _ = gpt.build_train(cfg, batch=8, seq_len=SEQ,
                                     lr=5e-3)
        exe = fluid.Executor()
        exe.run(startup)
        base = np.arange(SEQ) % VOCAB
        toks = np.stack([(base + i) % VOCAB for i in range(8)]) \
            .astype(np.int64)
        for _ in range(40):
            exe.run(main, feed={"tokens": toks}, fetch_list=[loss])
    return cfg, scope


def _clone_scope(scope):
    """Fresh scope holding only the parameter tensors (no gen.* decode
    state), so two engines can coexist without name collisions — the
    in-test stand-in for two replica processes loading one npz."""
    dst = fluid.Scope()
    for name in scope.names():
        if name.startswith("gen."):
            continue
        v = scope.get(name)
        if v is not None:
            dst.var(name)
            dst.set(name, np.array(np.asarray(v)))
    return dst


def _serial_tokens(cfg, scope, prompt, max_new):
    dec_main, dec_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_start):
        step = gpt.build_decode_step(cfg, batch=1, max_seq=SEQ)
    return gpt.kv_generate(fluid.Executor(), scope, dec_main,
                           step.token_var, step.logits_var,
                           step.cache_names, prompt=prompt,
                           max_new_tokens=max_new)


# ---------------------------------------------------------------------------
# kv_wire: serialize -> deserialize parity
# ---------------------------------------------------------------------------

class _FakeScope:
    def __init__(self, pools):
        self._pools = pools

    def get(self, name):
        return self._pools[name]


def _fake_pools(dtype, n_blocks=6, lanes=6):
    rng = np.random.RandomState(0)
    names = ["k0", "v0", "k1", "v1"]
    pools = {n: rng.randn(n_blocks, BLOCK, lanes).astype(dtype)
             for n in names}
    return _FakeScope(pools), names, pools


def test_kv_wire_roundtrip_fp32_byte_exact():
    scope, names, pools = _fake_pools(np.float32)
    ids, hashes = [2, 4], ["aa", "bb"]
    payload = pack_blocks(scope, names, ids, hashes, BLOCK)
    assert payload["kind"] == "kv_shipment"
    assert payload["n_blocks"] == 2 and payload["n_tokens"] == 2 * BLOCK
    assert payload["shape"] == [2, BLOCK, 6]
    # raw-bytes accounting: 4 pools x rows x fp32
    assert payload_bytes(payload) == 4 * (2 * BLOCK * 6) * 4

    ship = unpack_blocks(payload)
    assert ship.chain_hashes == hashes
    assert ship.dtype == np.float32 and len(ship.pools) == 4
    for rows, name in zip(ship.pools, names):
        assert rows.tobytes() == pools[name][ids].tobytes()
    # the model's pool list as it is: an odd count (one latent pool a
    # layer) ships as any other
    odd = unpack_blocks(pack_blocks(scope, names[:3], ids, hashes, BLOCK))
    assert [r.tobytes() for r in odd.pools] == \
        [pools[n][ids].tobytes() for n in names[:3]]


def test_kv_wire_roundtrip_bf16_byte_exact():
    ml_dtypes = pytest.importorskip("ml_dtypes")
    scope, names, pools = _fake_pools(ml_dtypes.bfloat16)
    payload = pack_blocks(scope, names, [1, 3, 5], ["a", "b", "c"],
                          BLOCK)
    assert payload["dtype"] == "bfloat16"
    ship = unpack_blocks(payload)
    assert ship.dtype == np.dtype(ml_dtypes.bfloat16)
    assert ship.pools[0].tobytes() == pools["k0"][[1, 3, 5]].tobytes()


def test_kv_wire_rejects_malformed():
    scope, names, _ = _fake_pools(np.float32)
    with pytest.raises(ValueError):   # pools of two row shapes
        pack_blocks(_FakeScope({"a": np.zeros((4, BLOCK, 6), np.float32),
                                "b": np.zeros((4, BLOCK, 8), np.float32)}),
                    ["a", "b"], [1], ["a"], BLOCK)
    with pytest.raises(ValueError):
        pack_blocks(scope, names, [1, 2], ["a"], BLOCK)  # id/hash skew
    good = pack_blocks(scope, names, [1], ["a"], BLOCK)
    with pytest.raises(ValueError):
        unpack_blocks({**good, "kind": "nope"})
    with pytest.raises(ValueError):
        unpack_blocks({**good, "version": 99})
    with pytest.raises(ValueError):
        unpack_blocks({**good, "chain_hashes": ["a", "b"]})
    bad = {**good, "pools": [good["pools"][0][:8]] + good["pools"][1:]}
    with pytest.raises(ValueError):
        unpack_blocks(bad)


def test_kv_wire_empty_shipment():
    scope, names, _ = _fake_pools(np.float32)
    payload = pack_blocks(scope, names, [], [], BLOCK)
    ship = unpack_blocks(payload)
    assert ship.n_blocks == 0 and ship.n_tokens == 0
    assert payload_bytes(payload) == 0


# ---------------------------------------------------------------------------
# export_prefix -> adopt_prefix across two engines (the tentpole)
# ---------------------------------------------------------------------------

def test_export_adopt_cross_engine_parity(trained):
    """A prefill engine exports a prompt's full-block KV; a separate
    decode engine (own scope = own process stand-in) adopts it, ends up
    with cache-held refcounts and byte-identical pool rows, and then
    decodes EXACTLY the serial-reference tokens with the prefix counted
    as cached and zero post-warmup compiles."""
    cfg, scope = trained
    prompt = [i % VOCAB for i in range(2 * BLOCK + 1)]  # 2 full blocks
    want = _serial_tokens(cfg, _clone_scope(scope), prompt, 3)

    eng_a = GenerationEngine(cfg, _clone_scope(scope),
                             exe=fluid.Executor(), max_slots=2,
                             max_seq=SEQ, block_size=BLOCK)
    eng_b = GenerationEngine(cfg, _clone_scope(scope),
                             exe=fluid.Executor(), max_slots=2,
                             max_seq=SEQ, block_size=BLOCK)
    eng_a.start()
    eng_b.start()
    try:
        payload = export_prefix(eng_a, prompt)
        assert payload["n_blocks"] == 2
        res = adopt_prefix(eng_b, payload)
        assert res["adopted"] == 2 and res["duplicate"] == 0
        assert res["resident"] == 2

        # adopted blocks are cache-held (refcount 1 -> evictable) and
        # byte-identical to the exporting engine's rows
        ship = unpack_blocks(payload)
        names = eng_b.step.cache_names
        for j, h in enumerate(ship.chain_hashes):
            bid = eng_b._prefix._entries[h]
            assert eng_b._pool.refcount(bid) == 1
            for name, rows in zip(names, ship.pools):
                pool = np.asarray(eng_b.scope.get(name))
                assert pool[bid].tobytes() == rows[j].tobytes()

        # re-adoption is a pure dup (move-to-end, no new blocks)
        res2 = adopt_prefix(eng_b, payload)
        assert res2["adopted"] == 0 and res2["duplicate"] == 2

        out = eng_b.generate(prompt, 3)
        assert out["tokens"] == want
        assert out["cached_tokens"] == 2 * BLOCK
        assert eng_b.post_warmup_compiles() == 0

        # shipment validation against a live engine (shares eng_b
        # rather than paying another warmup ladder)
        scope_f, names = _fake_pools(np.float32)[:2]
        with pytest.raises(ValueError):
            adopt_prefix(eng_b, pack_blocks(scope_f, names, [1], ["a"],
                                            BLOCK + 1))  # block size
        with pytest.raises(ValueError):
            adopt_prefix(eng_b, pack_blocks(scope_f, names[:2], [1],
                                            ["a"], BLOCK))  # layers
        with pytest.raises(ValueError):
            # 2x3 heads != engine pools
            adopt_prefix(eng_b, pack_blocks(scope_f, names, [1], ["a"],
                                            BLOCK))
    finally:
        eng_a.stop()
        eng_b.stop()


@pytest.fixture(scope="module")
def shipped(trained):
    """The eviction tests' shared-prefix KV payload, exported ONCE from
    a short-lived prefill engine — a shipment is plain data, so one
    export serves every graph-opt-level variant."""
    cfg, scope = trained
    prefix = [i % VOCAB for i in range(2 * BLOCK)]
    eng_p = GenerationEngine(cfg, _clone_scope(scope),
                             exe=fluid.Executor(), max_slots=2,
                             max_seq=SEQ, block_size=BLOCK)
    eng_p.start()
    try:
        payload = export_prefix(eng_p, prefix + [8])
        assert eng_p.post_warmup_compiles() == 0
    finally:
        eng_p.stop()
    return prefix, payload


@pytest.mark.parametrize("opt_level", [0, 2])
def test_adopted_decode_parity_under_eviction(trained, shipped,
                                              opt_level):
    """Decode-worker-on-adopted-KV vs unified engine, token for token,
    at graph opt levels 0 and 2, with a pool tight enough that finished
    requests' blocks (and eventually the adopted prefix itself) face
    eviction pressure."""
    cfg, scope = trained
    prefix, payload = shipped
    prev = fluid.FLAGS.graph_opt_level
    fluid.set_flags({"FLAGS_graph_opt_level": opt_level})
    try:
        prompts = [prefix + [8], prefix + [9], [5, 6, 7]]
        ref_scope = _clone_scope(scope)
        want = [_serial_tokens(cfg, ref_scope, p, 3) for p in prompts]

        # 8 blocks total, block 0 reserved: 2 slots x 3 blocks of live
        # decode state + the 2 adopted blocks only fit via eviction
        eng_d = GenerationEngine(cfg, _clone_scope(scope),
                                 exe=fluid.Executor(), max_slots=2,
                                 max_seq=SEQ, block_size=BLOCK,
                                 kv_pool_blocks=8)
        eng_d.start()
        try:
            adopt_prefix(eng_d, payload)
            outs = [eng_d.generate(p, 3) for p in prompts]
            assert [o["tokens"] for o in outs] == want
            assert outs[0]["cached_tokens"] == 2 * BLOCK
            assert eng_d.post_warmup_compiles() == 0
        finally:
            eng_d.stop()
    finally:
        fluid.set_flags({"FLAGS_graph_opt_level": prev})


# ---------------------------------------------------------------------------
# FleetPrefixStore
# ---------------------------------------------------------------------------

def test_fleet_prefix_store_depth_owner_lru():
    store = FleetPrefixStore(max_entries=3)
    assert store.block_size is None and len(store) == 0
    store.learn_block_size(8)
    assert store.block_size == 8

    store.register(["h1", "h2"], "d0")
    assert store.owned_depth(["h1", "h2"], "d0") == 2
    assert store.owned_depth(["h1", "h2", "h3"], "d0") == 2
    assert store.owned_depth(["h1", "h2"], "d1") == 0
    # chain_owner needs the WHOLE chain; exclusion respected
    assert store.chain_owner(["h1", "h2"]) == "d0"
    assert store.chain_owner(["h1", "h2", "h3"]) is None
    assert store.chain_owner(["h1"], exclude=("d0",)) is None
    store.register(["h1"], "d1")
    assert store.chain_owner(["h1"], exclude=("d0",)) == "d1"

    store.drop_owner("d0")
    assert store.owned_depth(["h1"], "d1") == 1  # d1's claim survives
    assert store.owned_depth(["h2"], "d0") == 0
    assert len(store) == 1

    # LRU bound: oldest untouched hash falls off
    store.register(["a", "b", "c"], "d0")  # h1 evicted (4 > max 3)
    assert store.owned_depth(["h1"], "d1") == 0
    assert len(store) == 3
    st = store.stats()
    assert st["entries"] == 3 and st["block_size"] == 8


# ---------------------------------------------------------------------------
# Router role restriction + in-process two-phase dispatch
# ---------------------------------------------------------------------------

class _FakeGen:
    """Minimal gen-engine stand-in for routing tests: health + queue
    gauges only."""

    def health(self):
        return {"state": "ok", "retry_after_s": 0.0}

    def load(self):
        return 0.0


def test_router_role_restriction():
    with pytest.raises(ValueError):
        Replica("x", gen_engine=_FakeGen(), role="wat")
    rp = Replica("p0", gen_engine=_FakeGen(), role="prefill")
    rd = Replica("d0", gen_engine=_FakeGen(), role="decode")
    router = Router([rp, rd], start_probe=False)
    try:
        # decode traffic never routes to a prefill-only replica…
        for _ in range(8):
            assert router._pick("generate", set(), None).name == "d0"
        # …prefill traffic never to a decode-only one…
        for _ in range(8):
            assert router._pick("prefill", set(), None).name == "p0"
        # …and predict needs a unified replica: none here
        assert router._pick("predict", set(), None) is None
        status, body, _ = router.healthz()
        assert status == 200
        roles = {n: d["role"] for n, d in body["replicas"].items()}
        assert roles == {"p0": "prefill", "d0": "decode"}
    finally:
        router.close()


def test_router_disagg_end_to_end_in_process(trained):
    """Two-phase dispatch against real engines in one process: a
    prefill-role engine and a decode-role engine behind
    Router(disagg=True). Outputs match the serial reference exactly,
    the fleet store learns the prefix, and the second request with the
    same prefix skips the transfer (prefix reuse)."""
    from paddle_tpu import monitor
    cfg, scope = trained
    prefix = [i % VOCAB for i in range(2 * BLOCK)]
    p_a, p_b = prefix + [8], prefix + [9]
    ref_scope = _clone_scope(scope)
    want_a = _serial_tokens(cfg, ref_scope, p_a, 3)
    want_b = _serial_tokens(cfg, ref_scope, p_b, 3)

    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    eng_p = GenerationEngine(cfg, _clone_scope(scope),
                             exe=fluid.Executor(), max_slots=2,
                             max_seq=SEQ, block_size=BLOCK)
    eng_d = GenerationEngine(cfg, _clone_scope(scope),
                             exe=fluid.Executor(), max_slots=2,
                             max_seq=SEQ, block_size=BLOCK)
    eng_p.start()
    eng_d.start()
    router = Router([Replica("p0", gen_engine=eng_p, role="prefill"),
                     Replica("d0", gen_engine=eng_d, role="decode")],
                    start_probe=False, disagg=True)
    try:
        out_a = router.generate({"prompt": p_a, "max_new_tokens": 3})
        out_b = router.generate({"prompt": p_b, "max_new_tokens": 3})
        assert out_a["tokens"] == want_a
        assert out_b["tokens"] == want_b
        # decode worker served both from the adopted prefix
        assert out_b["cached_tokens"] == 2 * BLOCK
        assert eng_d.post_warmup_compiles() == 0
        assert eng_p.post_warmup_compiles() == 0
        assert router.prefix_store.owned_depth(
            PrefixCache.chunk_hashes(prefix, BLOCK), "d0") == 2
        c = monitor.get_stats_snapshot()["counters"]
        assert c.get("serving.disagg_requests") == 2
        assert c.get("serving.kv_xfer_blocks", 0) >= 2
        # request B found the chain already owned by d0: no 2nd hop
        assert c.get("serving.disagg_prefix_reuse") == 1
        assert not c.get("serving.disagg_fallbacks")
    finally:
        router.close()
        eng_p.stop()
        eng_d.stop()
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})


def test_router_disagg_falls_back_without_prefill_replica(trained):
    """Prefill worker dead mid-fleet: dispatch must fall back to plain
    decode (local re-prefill) with the SAME answer, counting a
    fallback."""
    from paddle_tpu import monitor
    cfg, scope = trained
    prompt = [i % VOCAB for i in range(2 * BLOCK + 1)]
    want = _serial_tokens(cfg, _clone_scope(scope), prompt, 3)

    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    eng_d = GenerationEngine(cfg, _clone_scope(scope),
                             exe=fluid.Executor(), max_slots=2,
                             max_seq=SEQ, block_size=BLOCK)
    eng_d.start()
    router = Router([Replica("d0", gen_engine=eng_d, role="decode")],
                    start_probe=False, disagg=True)
    try:
        out = router.generate({"prompt": prompt, "max_new_tokens": 3})
        assert out["tokens"] == want
        c = monitor.get_stats_snapshot()["counters"]
        assert c.get("serving.disagg_fallbacks") == 1
    finally:
        router.close()
        eng_d.stop()
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})
