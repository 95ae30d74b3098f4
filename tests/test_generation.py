"""Continuous-batching generation tests: sampling helper, SlotManager,
multi-slot decode parity against serial kv_generate (including
join-mid-flight admission), graph-opt-level invariance, the /v1/generate
HTTP route, and the generation loadgen JSONL schema + report rendering.

The trained model is the tests/test_models.py cyclic-successor task
(token t is followed by (t + 1) % vocab), so greedy continuations are
known exactly and any numerical or scheduling divergence between the
serial and continuous-batching decode paths shows up as a wrong token,
not a tolerance failure.
"""
import io
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models import gpt, sampling
from paddle_tpu.serving import (DeadlineExceededError, GenerationEngine,
                                GenerationRequest, QueueFullError,
                                SlotManager, serve)

VOCAB, SEQ = 16, 12


@pytest.fixture(scope="module")
def trained():
    """Tiny GPT trained on the cyclic-successor task; returns
    (cfg, scope, exe).  Greedy continuation of [a, b, c] is
    [(c+1) % VOCAB, (c+2) % VOCAB, ...]."""
    cfg = gpt.gpt_small(vocab_size=VOCAB, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=SEQ,
                        dropout=0.0, use_flash=False)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        loss, logits, tokens = gpt.build_train(cfg, batch=8, seq_len=SEQ,
                                               lr=5e-3)
        exe = fluid.Executor()
        exe.run(startup)
        base = np.arange(SEQ) % VOCAB
        toks = np.stack([(base + i) % VOCAB for i in range(8)]) \
            .astype(np.int64)
        for _ in range(40):
            exe.run(main, feed={"tokens": toks}, fetch_list=[loss])
    return cfg, scope, exe


def _serial_decode(cfg):
    """Fresh batch=1 decode program with UNPREFIXED state names (no
    collision with a gen.-prefixed engine sharing the scope)."""
    dec_main, dec_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_start):
        step = gpt.build_decode_step(cfg, batch=1, max_seq=SEQ)
    return dec_main, step


def _kv(exe, scope, dec_main, step, prompt, max_new, **kw):
    return gpt.kv_generate(exe, scope, dec_main, step.token_var,
                           step.logits_var, step.cache_names,
                           prompt=prompt, max_new_tokens=max_new, **kw)


# ---------------------------------------------------------------------------
# sampling helper (models/sampling.py)
# ---------------------------------------------------------------------------

def test_sample_token_greedy_is_argmax():
    logits = np.array([0.1, 2.0, -1.0, 1.9], np.float32)
    assert sampling.sample_token(logits) == 1
    assert sampling.sample_token(logits, temperature=0.0, top_k=2) == 1


def test_sample_token_top_k_masks_tail():
    # with top_k=2 only ids {1, 3} are eligible; at any temperature the
    # sampled id must come from that set
    logits = np.array([0.0, 5.0, 1.0, 4.0], np.float32)
    rng = np.random.RandomState(0)
    got = {sampling.sample_token(logits, temperature=1.0, top_k=2,
                                 rng=rng) for _ in range(64)}
    assert got <= {1, 3} and 1 in got


def test_sample_token_temperature_deterministic_per_seed():
    logits = np.random.RandomState(3).randn(VOCAB).astype(np.float32)
    a = [sampling.sample_token(logits, temperature=0.8,
                               rng=np.random.RandomState(7))
         for _ in range(5)]
    b = [sampling.sample_token(logits, temperature=0.8,
                               rng=np.random.RandomState(7))
         for _ in range(5)]
    assert a == b
    # temperature -> 0 concentrates on the argmax
    assert sampling.sample_token(logits, temperature=1e-4,
                                 rng=np.random.RandomState(0)) == \
        int(np.argmax(logits))


def test_sample_token_validation():
    with pytest.raises(ValueError):
        sampling.sample_token(np.zeros((2, 3), np.float32))
    with pytest.raises(ValueError):
        sampling.sample_token(np.zeros(4, np.float32), temperature=1.0)


# ---------------------------------------------------------------------------
# SlotManager / GenerationRequest
# ---------------------------------------------------------------------------

def test_slot_manager_lowest_first_and_release():
    m = SlotManager(3)
    assert [m.acquire() for _ in range(3)] == [0, 1, 2]
    assert m.acquire() is None and m.free_count() == 0
    m.release(1)
    assert m.active_count() == 2 and m.acquire() == 1
    m.release(2)
    m.release(0)
    assert m.acquire() == 0    # lowest free slot wins again
    with pytest.raises(ValueError):
        m.release(2)           # double release
    with pytest.raises(ValueError):
        m.release(99)
    with pytest.raises(ValueError):
        SlotManager(0)


def test_generation_request_validation():
    with pytest.raises(ValueError):
        GenerationRequest([], 4)
    with pytest.raises(ValueError):
        GenerationRequest([1], 0)
    r = GenerationRequest(np.array([1, 2], np.int64), 3, eos_id=7)
    assert r.prompt == [1, 2] and r.eos_id == 7


# ---------------------------------------------------------------------------
# kv_generate: graph-opt-level invariance (satellite 3)
# ---------------------------------------------------------------------------

def test_kv_generate_bit_exact_across_graph_opt_levels(trained):
    """The optimization pipeline (DCE/fold/CSE/fusion) must not change
    a single sampled token: decode at FLAGS_graph_opt_level 0 and 2
    from identical state must agree bit-exactly."""
    cfg, scope, _ = trained
    dec_main, step = _serial_decode(cfg)
    prev = fluid.FLAGS.graph_opt_level
    outs = {}
    try:
        for lvl in (0, 2):
            fluid.set_flags({"FLAGS_graph_opt_level": lvl})
            exe = fluid.Executor()   # fresh executable cache per level
            outs[lvl] = _kv(exe, scope, dec_main, step,
                            prompt=[0, 1, 2], max_new=7)
    finally:
        fluid.set_flags({"FLAGS_graph_opt_level": prev})
    assert outs[0] == outs[2], outs
    assert outs[0] == [(3 + i) % VOCAB for i in range(7)]


# ---------------------------------------------------------------------------
# GenerationEngine vs serial kv_generate (tentpole parity)
# ---------------------------------------------------------------------------

def test_engine_matches_serial_kv_generate(trained):
    """3 mixed-length requests over 2 slots (forces eviction + re-
    admission) must produce EXACTLY the serial kv_generate tokens, with
    zero post-warmup compiles."""
    cfg, scope, exe = trained
    prompts = [([0, 1, 2], 5), ([5, 6], 5), ([1, 2, 3, 4], 4)]
    dec_main, step = _serial_decode(cfg)
    want = [_kv(exe, scope, dec_main, step, p, n) for p, n in prompts]

    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=2, max_seq=SEQ)
    eng.start()
    try:
        resps = [eng.submit(GenerationRequest(p, n)) for p, n in prompts]
        got = [r.result(timeout=30.0)["tokens"] for r in resps]
        assert got == want, (got, want)
        assert eng.post_warmup_compiles() == 0, eng.cache_stats()
    finally:
        eng.stop()
    assert not eng.ready


def test_engine_join_mid_flight_matches_serial(trained):
    """A request admitted from another request's stream callback (i.e.
    joining the batch while decode is mid-flight) must neither perturb
    the running slot nor be perturbed by it."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    want_a = _kv(exe, scope, dec_main, step, [0, 1, 2], 6)
    want_b = _kv(exe, scope, dec_main, step, [7, 8], 4)

    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=2, max_seq=SEQ)
    eng.start()
    try:
        later = []

        def cb(tok):
            if not later:   # first generated token of A -> admit B
                later.append(eng.submit(GenerationRequest([7, 8], 4)))

        resp_a = eng.submit(GenerationRequest([0, 1, 2], 6,
                                              stream_cb=cb))
        got_a = resp_a.result(timeout=30.0)["tokens"]
        got_b = later[0].result(timeout=30.0)["tokens"]
        assert got_a == want_a and got_b == want_b
        assert eng.post_warmup_compiles() == 0, eng.cache_stats()
    finally:
        eng.stop()


def test_engine_eos_and_result_metadata(trained):
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    full = _kv(exe, scope, dec_main, step, [0, 1], 6)
    eos = full[2]   # stop after the 3rd generated token
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=2, max_seq=SEQ)
    eng.start()
    try:
        out = eng.generate([0, 1], 6, eos_id=eos)
        assert out["tokens"] == full[:3]
        assert out["finish_reason"] == "eos"
        assert out["ttft_ms"] > 0 and out["e2e_ms"] >= out["ttft_ms"]
        out2 = eng.generate([0, 1], 4)
        assert out2["finish_reason"] == "length"
        assert len(out2["tokens"]) == 4
    finally:
        eng.stop()


def test_engine_backpressure_and_capacity_validation(trained):
    cfg, scope, _ = trained
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=1, max_seq=SEQ, queue_capacity=1)
    # not started: submissions queue up, nothing drains
    eng.submit(GenerationRequest([1], 2))
    with pytest.raises(QueueFullError):
        eng.submit(GenerationRequest([2], 2))
    # prompt + max_new - 1 must fit in the KV cache
    with pytest.raises(ValueError):
        eng.submit(GenerationRequest(list(range(8)), SEQ))


def test_engine_deadline_fails_queued_request(trained):
    cfg, scope, _ = trained
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=1, max_seq=SEQ)
    eng.start()
    try:
        # saturate the single slot with a long request, then queue one
        # with a deadline far shorter than the occupant's runtime
        slow = eng.submit(GenerationRequest([0, 1], 8))
        fast = eng.submit(GenerationRequest([3], 2, timeout_ms=0.01))
        with pytest.raises(DeadlineExceededError):
            fast.result(timeout=30.0)
        assert len(slow.result(timeout=30.0)["tokens"]) == 8
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# Paged KV cache: parity, prefix hits, planner visibility (tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("opt_level", [0, 2])
def test_paged_engine_matches_serial(trained, opt_level):
    """Paged engine (tight pool -> eviction + re-admission pressure)
    over mixed-length prompts must produce EXACTLY the serial slab
    kv_generate tokens at graph-opt level 0 and 2, with both of its
    executables compiled in warmup and none after."""
    cfg, scope, exe = trained
    prompts = [([0, 1, 2], 5), ([5, 6], 5), ([1, 2, 3, 4], 4),
               ([7], 6), ([3, 4, 5, 6, 7], 3)]
    dec_main, step = _serial_decode(cfg)
    want = [_kv(exe, scope, dec_main, step, p, n) for p, n in prompts]

    prev = fluid.FLAGS.graph_opt_level
    fluid.set_flags({"FLAGS_graph_opt_level": opt_level})
    try:
        # 2 slots x 3 blocks/slot (block_size=4, SEQ=12) but only 7
        # allocatable blocks shared with the prefix cache: finished
        # requests' blocks must be evicted and reused for admission
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ,
                               block_size=4, kv_pool_blocks=8)
        assert eng.block_size == 4
        eng.start()
        try:
            resps = [eng.submit(GenerationRequest(p, n))
                     for p, n in prompts]
            got = [r.result(timeout=60.0)["tokens"] for r in resps]
            assert got == want, (got, want)
            assert eng.post_warmup_compiles() == 0, eng.cache_stats()
        finally:
            eng.stop()
    finally:
        fluid.set_flags({"FLAGS_graph_opt_level": prev})


def test_paged_prefix_cache_hit_reuses_blocks(trained):
    """Two requests sharing a whole-block prefix: the second must
    report cached_tokens == the shared full blocks, still match the
    serial reference exactly, and TTFT bookkeeping must count one hit
    and one miss."""
    from paddle_tpu import monitor
    cfg, scope, exe = trained
    prefix = [0, 1, 2, 3, 4, 5, 6, 7]      # two full 4-token blocks
    p_a, p_b = prefix + [8], prefix + [9]
    dec_main, step = _serial_decode(cfg)
    want_a = _kv(exe, scope, dec_main, step, p_a, 3)
    want_b = _kv(exe, scope, dec_main, step, p_b, 3)

    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4)
        eng.start()
        try:
            out_a = eng.generate(p_a, 3)
            out_b = eng.generate(p_b, 3)
            assert out_a["tokens"] == want_a
            assert out_b["tokens"] == want_b
            assert out_a["cached_tokens"] == 0
            assert out_b["cached_tokens"] == len(prefix)
            assert eng.post_warmup_compiles() == 0
            stats = eng.kv_block_stats()
            assert stats["prefix_entries"] >= 2
            c = monitor.get_stats_snapshot()["counters"]
            assert c["serving.gen_prefix_hits"] == 1
            assert c["serving.gen_prefix_misses"] == 1
        finally:
            eng.stop()
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})


def test_paged_pool_decouples_planner_kv_from_slots(trained):
    """The static memory planner must price the paged program's KV at
    num_blocks x block_bytes (pool persistables, pinned) while the slab
    program pins max_slots x max_seq — the planner-visibility
    acceptance of the paged subsystem."""
    from paddle_tpu.analysis import analyze_program_memory
    from paddle_tpu.ops.pallas.paged_attention import pool_lanes
    cfg, _, _ = trained
    block_size, num_blocks, slots = 4, 5, 4

    paged_main, paged_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(paged_main, paged_start):
        gpt.build_paged_decode_step(cfg, batch=slots, max_seq=SEQ,
                                    block_size=block_size,
                                    num_blocks=num_blocks)
    slab_main, slab_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(slab_main, slab_start):
        gpt.build_decode_step(cfg, batch=slots, max_seq=SEQ)

    kv_paged = analyze_program_memory(paged_main).kv_summary()
    kv_slab = analyze_program_memory(slab_main).kv_summary()
    assert kv_paged["layout"] == "paged"
    assert kv_slab["layout"] == "slab"
    elem = 2 * cfg.n_layers * cfg.d_model * 4        # K+V, fp32
    # a pool's token takes whole lane tiles (ops/pallas/paged_attention)
    lanes = pool_lanes(cfg.d_model) // cfg.d_model
    assert kv_paged["kv_bytes"] == num_blocks * block_size * elem * lanes
    assert kv_slab["kv_bytes"] == slots * SEQ * elem
    # the tight pool above holds fewer tokens than the slab bound — the
    # whole point: pool size is budget-derived, not slots x max_seq
    # (token for token: this toy width is a quarter of a lane tile)
    assert kv_paged["kv_bytes"] // lanes < kv_slab["kv_bytes"]


# ---------------------------------------------------------------------------
# Prefill tiles: many pages of ONE request in one step (PR 34)
# ---------------------------------------------------------------------------

LONG_SEQ, LONG_BS, LONG_VOCAB = 48, 4, 32


@pytest.fixture(scope="module")
def long_model():
    """A GPT of random weights with room for prompts of several pages;
    (cfg, scope, serial decoder). The token embedding is N(0, 1), so
    that a row's logits follow its tokens (at the start-up program's
    0.02 under positions of amplitude 1 they barely do, and a pool
    holding the wrong keys would pass)."""
    cfg = gpt.gpt_small(vocab_size=LONG_VOCAB, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=LONG_SEQ,
                        dropout=0.0, use_flash=False)
    scope = fluid.Scope()
    GenerationEngine(cfg, scope, exe=fluid.Executor(), max_slots=1,
                     max_seq=LONG_SEQ, block_size=LONG_BS,
                     state_prefix="init.").init_scope()
    emb = np.random.RandomState(0).normal(
        size=scope.find_var("word_emb").shape).astype(np.float32)
    scope.set("word_emb", emb)
    dec_main, dec_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_start):
        step = gpt.build_decode_step(cfg, batch=1, max_seq=LONG_SEQ)

    def serial(prompt, n):
        return gpt.kv_generate(fluid.Executor(), scope, dec_main,
                               step.token_var, step.logits_var,
                               step.cache_names, prompt=prompt,
                               max_new_tokens=n)
    return cfg, scope, serial


def _long_engine(model, slots, prefix, **kw):
    cfg, scope, _ = model
    return GenerationEngine(cfg, scope, exe=fluid.Executor(),
                            max_slots=slots, max_seq=LONG_SEQ,
                            block_size=LONG_BS, state_prefix=prefix, **kw)


def _tap_prefill(eng):
    """Every prefill call's live rows as (first block of the row's
    table, start, n_valid), a list a call."""
    calls, inner = [], eng._run_paged

    def tap(prog, step, tokens, table, start, nvalid):
        if prog is eng._prefill_prog:
            calls.append([(int(table[r, 0]), int(start[r]), int(nvalid[r]))
                          for r in np.flatnonzero(nvalid)])
        return inner(prog, step, tokens, table, start, nvalid)

    eng._run_paged = tap
    return calls


# five pages of prompt to prefill, the last partial: 19 = 4 x 4 + 3
FIVE_PAGES = [(7 * i + 3) % LONG_VOCAB for i in range(20)]


@pytest.mark.parametrize("slots,steps", [(8, 1), (4, 2), (1, 5)])
def test_a_long_prompt_prefills_in_the_steps_its_tiles_need(long_model,
                                                            slots, steps):
    """A prompt of five pages is ONE prefill step of an engine with
    eight rows, two of one with four, and five of one with a single
    row: the last is the schedule of before PR 34, a page a step. All
    three serve the serial decoder's tokens, and the tiles of a step
    are the request's successive pages, the last one partial."""
    _, _, serial = long_model
    want = serial(FIVE_PAGES, 6)
    eng = _long_engine(long_model, slots, f"tiles{slots}.")
    eng.start()
    calls = _tap_prefill(eng)
    try:
        resp = eng.submit(GenerationRequest(FIVE_PAGES, 6))
        assert resp.result(timeout=60.0)["tokens"] == want
        assert resp.timings["prefill_steps"] == steps == len(calls)
        assert eng.post_warmup_compiles() == 0, eng.cache_stats()
    finally:
        eng.stop()
    fed = [(s, n) for call in calls for _, s, n in call]
    assert fed == [(0, 4), (4, 4), (8, 4), (12, 4), (16, 3)]
    assert all(len(call) <= slots for call in calls)
    assert len({b for call in calls for b, _, _ in call}) == 1


def test_prefill_rows_go_to_the_oldest_request_first(long_model):
    """Two long prompts and a short one, admitted together: in every
    prefill step a request gets rows only when each older one that is
    still in prefill has all the rows it can use, and all three come
    out as the serial decoder's."""
    _, _, serial = long_model
    older = FIVE_PAGES
    younger = [(5 * i + 1) % LONG_VOCAB for i in range(18)]
    short = [9, 8, 7]
    jobs = [(older, 4), (younger, 4), (short, 5)]
    want = [serial(p, n) for p, n in jobs]
    eng = _long_engine(long_model, 4, "fcfs.")
    calls = _tap_prefill(eng)
    # queued before the worker starts: one admission, in this order
    resps = [eng.submit(GenerationRequest(p, n, timeout_ms=6e5))
             for p, n in jobs]
    eng.start()
    try:
        got = [r.result(timeout=60.0)["tokens"] for r in resps]
    finally:
        eng.stop()
    assert got == want
    # who a row belongs to: the first block of its table, in the order
    # the requests first appear (the oldest is fed first)
    order = []
    for call in calls:
        for b, _, _ in call:
            if b not in order:
                order.append(b)
    assert len(order) == 3
    left = dict(zip(order, (-(-(len(p) - 1) // LONG_BS) for p, _ in jobs)))
    for call in calls:
        rows = [order.index(b) for b, _, _ in call]
        assert rows == sorted(rows)              # oldest first in a step
        took = {b: sum(1 for x, _, _ in call if x == b) for b in order}
        for i, b in enumerate(order):
            if took[b] < left[b]:                # b is not done: nobody
                assert all(took[y] == 0          # younger got a row
                           for y in order[i + 1:])
            left[b] -= took[b]
    assert not any(left.values())
    assert len(calls[0]) == 4 and {b for b, _, _ in calls[0]} == {order[0]}
    assert [r.timings["prefill_steps"] for r in resps][0] == 2


def test_tiles_start_where_a_prefix_cache_hit_ends(long_model):
    """A hit in the prefix cache sets `fed` to a page boundary in mid
    prompt: the one prefill step feeds the pages after it."""
    _, _, serial = long_model
    first = FIVE_PAGES[:9]                       # two full pages cached
    second = FIVE_PAGES[:8] + [(3 * i + 2) % LONG_VOCAB for i in range(11)]
    want = serial(second, 5)
    eng = _long_engine(long_model, 8, "hit.")
    eng.start()
    calls = _tap_prefill(eng)
    try:
        eng.generate(first, 2)
        del calls[:]
        out = eng.submit(GenerationRequest(second, 5))
        assert out.result(timeout=60.0)["tokens"] == want
        assert out.timings["cached_tokens"] == 8
        assert out.timings["prefill_steps"] == 1
    finally:
        eng.stop()
    (call,) = calls
    assert [(s, n) for _, s, n in call] == [(8, 4), (12, 4), (16, 2)]


def test_a_non_finite_tile_fails_its_request_and_no_other(long_model):
    """The prefill step's probe is a number a row: a request whose
    THIRD tile reads non-finite fails, and the request whose tiles ride
    in the same step is served as the serial decoder serves it."""
    _, scope, serial = long_model
    good = [(5 * i + 1) % 31 for i in range(11)]         # never token 31
    bad = good[:9] + [31, 2]                             # 31 on page three
    want = serial(good, 4)
    emb = np.array(scope.find_var("word_emb"))
    eng = _long_engine(long_model, 8, "nan_tile.")
    calls = _tap_prefill(eng)
    try:
        poked = emb.copy()
        poked[31] = np.nan
        scope.set("word_emb", poked)
        r_bad = eng.submit(GenerationRequest(bad, 3, timeout_ms=6e5))
        r_good = eng.submit(GenerationRequest(good, 4, timeout_ms=6e5))
        eng.start()
        try:
            with pytest.raises(RuntimeError,
                               match="non-finite activations in chunked"):
                r_bad.result(timeout=60.0)
            assert r_good.result(timeout=60.0)["tokens"] == want
        finally:
            eng.stop()
    finally:
        scope.set("word_emb", emb)
    assert len(calls) == 1 and len(calls[0]) == 6        # one step, both


# ---------------------------------------------------------------------------
# HTTP front end: /v1/generate
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# The iteration record, the request's own timings, the logits hook
# ---------------------------------------------------------------------------

GEN_REGIONS = {"gen.iteration", "gen.admit", "gen.idle_wait",
               "gen.prefill.stage", "gen.prefill.step",
               "gen.decode.draft", "gen.decode.stage", "gen.decode.step",
               "gen.sample", "executor.resolve", "executor.feed",
               "executor.compile", "executor.dispatch", "executor.fetch"}


def _records_since(t0):
    from paddle_tpu import trace
    return [r for r in trace.iteration_records() if r["t_start"] >= t0]


def test_paged_engine_leaves_one_record_an_iteration(trained):
    """With no flag set and no profiler attached: one record for every
    turn of the loop that ran a step, whose fields add up and whose
    gauges are the ones the monitor publishes."""
    import time

    from paddle_tpu import monitor
    cfg, scope, exe = trained
    prompts = [([0, 1, 2, 3, 4, 5], 4), ([5, 6], 5), ([1, 2, 3, 4], 4),
               ([7], 6), ([3, 4, 5, 6, 7], 3)]
    streamed = []
    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4)
        eng.start()
        t0 = time.perf_counter()
        try:
            resps = [eng.submit(GenerationRequest(
                p, n, stream_cb=streamed.append)) for p, n in prompts]
            outs = [r.result(timeout=60.0) for r in resps]
        finally:
            eng.stop()
        snap = monitor.get_stats_snapshot()
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})
    recs = _records_since(t0)
    assert recs and all(r["t_end"] > r["t_start"] for r in recs)
    # turns do not overlap, and each ran a step
    for a, b in zip(recs, recs[1:]):
        assert a["t_end"] <= b["t_start"]
    total = eng.kv_block_stats()["blocks_total"]
    for r in recs:
        assert r["prefill_rows"] + r["decode_rows"] > 0
        assert r["decode_rows"] <= r["slots"] == 2
        assert r["prefill_rows"] <= r["active_slots"] <= r["slots"]
        assert r["block_size"] == 4 and r["kv_blocks_total"] == total
        assert r["prefill_rows"] <= r["prefill_tiles"] <= r["slots"]
        assert r["prefill_tokens"] <= r["prefill_tiles"] * r["block_size"]
        assert (r["prefill_tokens"] > 0) == (r["prefill_rows"] > 0)
        assert r["kv_tokens_resident"] <= \
            r["kv_blocks_held"] * r["block_size"]
        assert r["tokens_emitted"] <= r["decode_rows"]
        assert set(r["host_s"]) <= GEN_REGIONS, sorted(r["host_s"])
        assert {"gen.iteration", "gen.admit"} <= set(r["host_s"])
        assert "gen.idle_wait" not in r["host_s"]
        assert sum(r["host_s"].values()) <= r["t_end"] - r["t_start"]
        if r["decode_rows"]:
            assert {"gen.decode.stage", "gen.decode.step", "gen.sample",
                    "executor.dispatch", "executor.fetch"} \
                <= set(r["host_s"])
    n_out = sum(len(o["tokens"]) for o in outs)
    assert sum(r["tokens_emitted"] for r in recs) == len(streamed) \
        == n_out == sum(n for _, n in prompts)
    # every prompt token but a prompt's last goes through a chunk
    assert sum(r["prefill_tokens"] for r in recs) == \
        sum(len(p) - 1 for p, _ in prompts)
    assert max(r["queue_depth"] for r in recs) >= 1   # 5 requests, 2 slots
    assert "executor.compile" not in {k for r in recs for k in r["host_s"]}
    # the monitor's gauges and goodput seconds are the records' own
    occ = snap["histograms"]["serving.gen_slot_occupancy"]
    assert occ["count"] == sum(1 for r in recs if r["decode_rows"])
    assert snap["gauges"]["serving.gen_active_slots"] == 0
    assert snap["gauges"]["serving.gen_kv_blocks_free"] <= total


def test_iteration_record_counts_the_pages_the_fed_rows_hold(trained):
    """`kv_pages_read` / `kv_pages_table` of a turn are what the
    `start` / `n_valid` arrays of its step calls imply: a row reads
    the pages its length covers, a muted row none; the table has
    slots x max_blocks entries a step."""
    import time

    from paddle_tpu.serving.kv_blocks import blocks_for_tokens
    cfg, scope, exe = trained
    prompts = [([0, 1, 2, 3, 4, 5, 6, 7, 0], 3), ([5, 6], 5), ([7], 6)]
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=2, max_seq=SEQ, block_size=4)
    eng.start()
    calls = []
    inner = eng._run_paged

    def tap(prog, step, tokens, table, start, nvalid):
        calls.append((time.perf_counter(), table.shape, start.copy(),
                      nvalid.copy()))
        return inner(prog, step, tokens, table, start, nvalid)

    eng._run_paged = tap
    t0 = time.perf_counter()
    try:
        for r in [eng.submit(GenerationRequest(p, n)) for p, n in prompts]:
            r.result(timeout=60.0)
    finally:
        eng.stop()
    recs = _records_since(t0)
    assert recs and calls
    max_blocks = eng.step.max_blocks_per_slot
    seen = 0
    for r in recs:
        mine = [c for c in calls if r["t_start"] <= c[0] <= r["t_end"]]
        seen += len(mine)
        assert len(mine) == (r["prefill_rows"] > 0) + (r["decode_rows"] > 0)
        assert r["kv_pages_table"] == len(mine) * 2 * max_blocks
        assert all(shape == (2, max_blocks) for _, shape, _, _ in mine)
        assert r["kv_pages_read"] == sum(
            blocks_for_tokens(int(s) + int(n), 4)
            for _, _, start, nvalid in mine
            for s, n in zip(start, nvalid) if n)
        assert 0 < r["kv_pages_read"] <= r["kv_pages_table"]
    assert seen == len(calls)
    # the rows of this run hold far less than their tables name
    assert sum(r["kv_pages_read"] for r in recs) < \
        sum(r["kv_pages_table"] for r in recs) // 2


def test_timings_are_readable_before_the_request_finishes(trained):
    """`timings` fills as the request passes each boundary; a request
    submitted to a full engine waits in the queue for a slot, and its
    `queue_ms` says so."""
    import threading
    cfg, scope, _ = trained
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(), max_slots=1,
                           max_seq=SEQ, block_size=4)
    eng.start()
    seen, sent, gate = {}, threading.Event(), threading.Event()

    def first_token(tok):
        # on the engine's thread, mid-request: the response is not done
        if not seen:
            sent.wait(10.0)
            seen.update(first.timings, done=first.done())
            gate.wait(10.0)

    try:
        first = eng.submit(GenerationRequest([0, 1, 2, 3, 4, 5], 4,
                                             stream_cb=first_token))
        sent.set()
        second = eng.submit(GenerationRequest([0, 1, 2, 3, 4, 6], 2))
        assert second.timings == {}      # still queued: no slot is free
        gate.set()
        out1 = first.result(timeout=60.0)
        out2 = second.result(timeout=60.0)
    finally:
        gate.set()
        eng.stop()
    assert seen["done"] is False
    assert seen["queue_ms"] >= 0.0 and seen["ttft_ms"] >= seen["queue_ms"]
    assert seen["cached_tokens"] == 0
    # 5 prompt tokens but the last go through chunks of 4
    assert seen["prefill_steps"] == 2
    assert first.timings["ttft_ms"] == out1["ttft_ms"]
    assert out1["queue_ms"] == first.timings["queue_ms"]
    # the second waited for the first to finish, and found its first
    # block in the prefix cache
    assert second.timings["queue_ms"] > first.timings["queue_ms"]
    assert second.timings["queue_ms"] >= \
        out1["e2e_ms"] - first.timings["queue_ms"] - 50.0
    assert second.timings["cached_tokens"] == out2["cached_tokens"] == 4
    assert second.timings["prefill_steps"] == 1
    assert out2["queue_ms"] == second.timings["queue_ms"]


@pytest.mark.parametrize("spec", [False, True])
def test_logits_cb_gets_the_rows_the_step_call_returned(trained, spec):
    """`logits_cb` hands over, token by token, the logits row each of
    the request's tokens was sampled from: the very rows of
    `_run_paged`'s return (the call the benchmark taps today), under
    plain decode and under speculative verify."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    want = _kv(exe, scope, dec_main, step, [3, 4, 5, 3], 6)
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(), max_slots=2,
                           max_seq=SEQ, block_size=4, spec_decode=spec,
                           spec_k=2)
    fetched = []
    inner = eng._run_paged

    def tap(prog, step, tokens, table, start, nvalid):
        out = inner(prog, step, tokens, table, start, nvalid)
        if prog is not eng._prefill_prog:
            fetched.append(out)
        return out

    eng._run_paged = tap
    rows, toks, other = [], [], []
    eng.start()
    try:
        # the repeated 3 lets the n-gram drafter propose [4, 5]
        a = eng.submit(GenerationRequest([3, 4, 5, 3], 6,
                                         logits_cb=rows.append,
                                         stream_cb=toks.append))
        b = eng.submit(GenerationRequest([5, 6, 7, 8, 9], 3,
                                         logits_cb=other.append))
        out = a.result(timeout=60.0)
        b.result(timeout=60.0)
    finally:
        eng.stop()
    assert out["tokens"] == toks == want
    assert len(rows) == 6 and len(other) == 3
    assert all(r.shape == (VOCAB,) for r in rows)
    # greedy: each token is the argmax of the row it came with
    assert [int(r.argmax()) for r in rows] == toks
    # and each row is one of slot 0's rows of a step's own return,
    # in the order the steps ran
    flat = [f[0, j] for f in fetched for j in range(f.shape[1])]
    at = 0
    for r in rows:
        while not np.array_equal(flat[at], r):
            at += 1
            assert at < len(flat), "a row that no step returned"
        at += 1
    if spec:
        assert any(f.shape[1] == 3 for f in fetched)


# ---------------------------------------------------------------------------
# What a decode step brings back (PR 32): the picks, and the rows that
# were asked for
# ---------------------------------------------------------------------------

def _cb_keeps(rows):
    return lambda row: rows.append(np.array(row))


@pytest.mark.parametrize("mix", ["greedy", "mixed"])
def test_rows_cross_to_the_host_only_for_what_asks(trained, mix):
    """Every request decodes to the serial decoder's tokens. A greedy
    one is answered from the device's pick and none of its logits is
    fetched; a temperature (here with top_k) reads the row it draws
    from, a `logits_cb` the row it is handed: `logit_rows_fetched`
    counts exactly those, a token each."""
    import time

    from paddle_tpu import monitor
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    rows, want_rows = [], []
    reqs = [dict(prompt=[0, 1, 2], max_new=5),
            dict(prompt=[1, 2, 3, 4], max_new=4)]
    asked = 0
    if mix == "mixed":
        reqs += [dict(prompt=[5, 6], max_new=5, temperature=0.9,
                      top_k=3, seed=11),
                 dict(prompt=[7, 8, 9], max_new=4)]
        asked = 5 + 4
    want = []
    for k, r in enumerate(reqs):
        kw = {a: r[a] for a in ("temperature", "top_k", "seed") if a in r}
        if mix == "mixed" and k == 3:
            kw["logits_cb"] = _cb_keeps(want_rows)
        want.append(_kv(exe, scope, dec_main, step, r["prompt"],
                        r["max_new"], **kw))
    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4)
        eng.start()
        t0 = time.perf_counter()
        try:
            resps = []
            for k, r in enumerate(reqs):
                kw = {a: r[a] for a in ("temperature", "top_k", "seed")
                      if a in r}
                if mix == "mixed" and k == 3:
                    kw["logits_cb"] = _cb_keeps(rows)
                resps.append(eng.submit(GenerationRequest(
                    r["prompt"], r["max_new"], **kw)))
            got = [r.result(timeout=60.0)["tokens"] for r in resps]
            assert eng.post_warmup_compiles() == 0, eng.cache_stats()
        finally:
            eng.stop()
        snap = monitor.get_stats_snapshot()
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})
    assert got == want, (got, want)
    recs = _records_since(t0)
    assert all("logit_rows_fetched" in r for r in recs)
    assert all(r["logit_rows_fetched"] <= r["decode_rows"] for r in recs)
    assert sum(r["logit_rows_fetched"] for r in recs) == asked
    assert snap["counters"].get("serving.gen_logit_rows_fetched", 0) \
        == asked
    if mix == "mixed":
        # the rows handed over are float32 NumPy rows, the serial
        # decoder's to rounding, and each token is its row's arg-max
        assert len(rows) == len(want_rows) == 4
        assert all(type(r) is np.ndarray and r.dtype == np.float32
                   and r.shape == (VOCAB,) for r in rows)
        np.testing.assert_allclose(rows, want_rows, rtol=1e-4, atol=1e-4)
        assert [int(r.argmax()) for r in rows] == got[3]


def test_step_return_stays_readable_and_a_numpy_one_is_served_from(trained):
    """`_run_paged` returns the step's logits as they lie on the
    device: `[i, 0]` reads to the row whose arg-max is the token the
    slot emitted, at once and after later steps have run (nothing is
    donated away under a caller who kept the return). A NumPy array
    returned in its place is what the engine then samples from."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    want = _kv(exe, scope, dec_main, step, [3, 4, 5], 6)

    def run(alter):
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4)
        kept, at_once = [], []
        inner = eng._run_paged

        def tap(prog, step, tokens, table, start, nvalid):
            out = inner(prog, step, tokens, table, start, nvalid)
            if prog is eng._prefill_prog:
                return out
            assert out.shape == (2, 1, VOCAB)
            out = alter(out)
            kept.append(out)
            at_once.append(np.array(out[0, 0], np.float32))
            return out

        eng._run_paged = tap
        eng.start()
        try:
            toks = eng.generate([3, 4, 5], 6)["tokens"]
        finally:
            eng.stop()
        return toks, kept, at_once

    toks, kept, at_once = run(lambda out: out)
    assert toks == want and len(kept) == 6
    later = [np.array(f[0, 0], np.float32) for f in kept]
    assert all(r.shape == (VOCAB,) for r in later)
    assert [int(r.argmax()) for r in later] == toks
    assert all(np.array_equal(a, b) for a, b in zip(at_once, later))
    # the whole of a return reads as the array it is
    assert np.array_equal(np.asarray(kept[2])[0, 0], later[2])

    # the vocabulary turned by one: every token is one past the model's
    toks, kept, _ = run(lambda out: np.roll(np.asarray(out), 1, axis=-1))
    assert all(type(f) is np.ndarray for f in kept)
    assert toks == [int(f[0, 0].argmax()) for f in kept]
    assert toks[0] == (want[0] + 1) % VOCAB and toks != want


@pytest.mark.parametrize("fault", ["weights", "step_nan"])
def test_a_non_finite_row_fails_its_own_request_and_no_other(trained,
                                                             fault):
    """The guard reads the step's fetched health numbers, a row each:
    a row whose logits are not finite (the embedding of its token
    poked; the injector's `step_nan` at site `generation`, which pokes
    slot 0's) fails its request, and the row beside it in the same
    step decodes on to the serial decoder's tokens."""
    from paddle_tpu.resilience.faults import reset_injector
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    want = _kv(exe, scope, dec_main, step, [7], 5)
    emb = np.array(scope.find_var("word_emb"))
    # pools of its own: a block that held NaN is not left to the
    # module's other engines
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(), max_slots=2,
                           max_seq=SEQ, block_size=4,
                           state_prefix=f"nan_{fault}.")
    try:
        if fault == "weights":
            poked = emb.copy()
            poked[12] = np.nan     # a token the other request never sees
            scope.set("word_emb", poked)
        else:
            fluid.set_flags({"FLAGS_fault_spec":
                             "step_nan:at=1:site=generation"})
            reset_injector()
        # both queued before the worker starts (the deadline has to
        # outlast the warm-up): the first turn admits them to slots 0
        # and 1, and their first decode step is one
        bad = eng.submit(GenerationRequest([12], 4, timeout_ms=6e5))
        good = eng.submit(GenerationRequest([7], 5, timeout_ms=6e5))
        eng.start()
        try:
            with pytest.raises(RuntimeError, match="non-finite logits"):
                bad.result(timeout=60.0)
            assert good.result(timeout=60.0)["tokens"] == want
        finally:
            eng.stop()
    finally:
        scope.set("word_emb", emb)
        fluid.set_flags({"FLAGS_fault_spec": ""})
        reset_injector()


def test_compiling_an_executable_either_way_finds_the_warmed_one(trained):
    """`executables()` names the first variable a run of each program
    fetches: compiled as `[fetch]` (benchmark/families/gpt_serve.py)
    or as `fetch_list(prog)` (hybrid_serve.py), each of the three is
    the executable `start()` warmed, and a step's fetch is the small
    `[2, slots, tokens]` picks, not its logits."""
    cfg, scope, _ = trained
    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(), max_slots=2,
                           max_seq=SEQ, block_size=4, spec_decode=True,
                           spec_k=2)
    eng.start()
    try:
        cells = eng.executables()
        assert [c[0] for c in cells] == ["decode", "prefill", "spec_verify"]
        with fluid.scope_guard(scope):
            for name, prog, feed, fetch in cells:
                assert eng.fetch_list(prog) == [fetch]
                for fetch_list in ([fetch], eng.fetch_list(prog)):
                    compiled = eng.exe.compiled(prog, feed=feed,
                                                fetch_list=fetch_list)
                    assert compiled.memory_analysis() is not None
        assert eng.post_warmup_compiles() == 0, eng.cache_stats()
        assert tuple(eng.step.picks_var.shape) == (2, 2, 1)
        assert tuple(eng.spec_step.picks_var.shape) == (2, 2, 3)
        assert [tuple(c[3].shape) for c in cells] == \
            [(2, 2, 1), (2,), (2, 2, 3)]
        assert eng.generate([0, 1, 2], 3)["tokens"] == [3, 4, 5]
        assert eng.post_warmup_compiles() == 0, eng.cache_stats()
    finally:
        eng.stop()


def _post(url, obj):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        body = e.read().decode()
        try:
            return e.code, json.loads(body)
        except ValueError:
            return e.code, body


def test_http_generate_route(trained):
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    want = _kv(exe, scope, dec_main, step, [0, 1, 2], 5)

    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=2, max_seq=SEQ)
    srv = serve(gen_engine=eng, port=0)   # starts the engine too
    try:
        url = srv.url
        with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
            assert r.status == 200
        code, body = _post(url + "/v1/generate",
                           {"prompt": [0, 1, 2], "max_new_tokens": 5})
        assert code == 200, body
        assert body["tokens"] == want
        assert body["finish_reason"] == "length"
        code, _ = _post(url + "/v1/generate", {"prompt": []})
        assert code == 400
        # no encoder engine behind this server
        code, _ = _post(url + "/v1/predict", {"inputs": {}})
        assert code == 404
    finally:
        srv.close()
        eng.stop()


# ---------------------------------------------------------------------------
# Loadgen schema + metrics report (satellite 6)
# ---------------------------------------------------------------------------

def _load_tool(name):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_generation_loadgen_schema_and_speedup(tmp_path, capsys):
    loadgen = _load_tool("serving_loadgen")
    v = _load_tool("validate_bench_json")
    out = str(tmp_path / "gen.jsonl")
    rc = loadgen.main(["--generate", "--slots", "4", "--requests", "12",
                       "--max-new-tokens", "6", "--compare-serial",
                       "--check-compiles", "--out", out])
    capsys.readouterr()
    assert rc == 0, "--check-compiles saw a post-warmup compile"
    assert v.validate_file(out) == []
    recs = [json.loads(ln) for ln in open(out) if ln.strip()]
    assert [r["mode"] for r in recs] == ["closed", "serial_baseline"]
    cont, ser = recs
    assert cont["requests"] == 12 and cont["errors"] == 0
    assert cont["tokens"] == 12 * 6
    assert cont["cache"]["post_warmup_compiles"] == 0
    for q in ("p50", "p95", "p99"):
        assert isinstance(cont["ttft_ms"][q], float)
        assert isinstance(cont["latency_ms"][q], float)
    # the acceptance headline: continuous batching beats serial decode
    assert cont["tokens_per_s"] > ser["tokens_per_s"], (cont, ser)

    bad = dict(cont)
    bad["ttft_ms"] = {"p50": 1.0}
    assert any("ttft_ms.p95" in e
               for e in v.validate_generation_loadgen(bad))
    bad2 = dict(cont, tokens_per_s="fast")
    assert any("tokens_per_s" in e
               for e in v.validate_generation_loadgen(bad2))


def test_metrics_report_renders_generation_section(trained, tmp_path):
    metrics_report = _load_tool("metrics_report")
    from paddle_tpu import monitor
    cfg, scope, _ = trained
    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    log = str(tmp_path / "gen_stats.jsonl")
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ)
        eng.start()
        try:
            eng.generate([0, 1, 2], 4)
            eng.generate([5, 6], 3)
        finally:
            eng.stop()
        snap = monitor.get_stats_snapshot()
        c = snap["counters"]
        assert c["serving.gen_requests"] == 2
        assert c["serving.gen_tokens"] == 7
        assert c["serving.gen_steps"] >= 1
        assert "serving.gen_ttft_ms" in snap["histograms"]
        assert "serving.gen_e2e_ms" in snap["histograms"]
        monitor.snapshot_to_jsonl(log)
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})
    with open(log, "a") as f:
        f.write(json.dumps({
            "kind": "generation_loadgen", "mode": "closed",
            "requests": 2, "errors": 0, "duration_s": 0.1,
            "throughput_rps": 20.0, "tokens": 7, "tokens_per_s": 70.0,
            "latency_ms": {"p50": 2.0, "p95": 3.0, "p99": 3.0},
            "ttft_ms": {"p50": 1.0, "p95": 1.5, "p99": 1.5},
            "inter_token_ms": {"p50": 0.5, "p95": 0.7, "p99": 0.7},
            "config": {}, "cache": {"post_warmup_compiles": 0}}) + "\n")
    buf = io.StringIO()
    rc = metrics_report.report(log, out=buf)
    out = buf.getvalue()
    assert rc == 0
    assert "-- generation (continuous batching)" in out
    assert "genload[closed]" in out
    assert "post-warmup compiles 0" in out
    assert "ttft" in out and "inter-token" in out
