"""Speculative decoding tests: NgramDrafter suffix matching,
accept_draft accept/reject boundaries, and end-to-end engine parity —
the spec-decode engine must produce EXACTLY the serial kv_generate
tokens (greedy and sampled) at graph-opt level 0 and 2 with zero
post-warmup compiles.

The trained model is the usual cyclic-successor task (token t is
followed by (t + 1) % VOCAB) at max_seq_len 32, long enough for
generations to wrap the vocab-16 cycle: once the context repeats, the
n-gram drafter locks on and the verify path actually runs, so parity
here exercises real accepted drafts, not just the n_valid=1 fallback.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.models import gpt, sampling
from paddle_tpu.serving import (GenerationEngine, GenerationRequest,
                                NgramDrafter)

VOCAB, SEQ = 16, 32


@pytest.fixture(scope="module")
def trained():
    """Tiny GPT trained on the cyclic-successor task; returns
    (cfg, scope, exe). max_seq_len is 32 so generations can run past
    one full cycle of the vocab and give the drafter repeats to find."""
    cfg = gpt.gpt_small(vocab_size=VOCAB, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=SEQ,
                        dropout=0.0, use_flash=False)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        loss, logits, tokens = gpt.build_train(cfg, batch=8, seq_len=12,
                                               lr=5e-3)
        exe = fluid.Executor()
        exe.run(startup)
        base = np.arange(12) % VOCAB
        toks = np.stack([(base + i) % VOCAB for i in range(8)]) \
            .astype(np.int64)
        for _ in range(40):
            exe.run(main, feed={"tokens": toks}, fetch_list=[loss])
    return cfg, scope, exe


def _serial_decode(cfg):
    dec_main, dec_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_start):
        step = gpt.build_decode_step(cfg, batch=1, max_seq=SEQ)
    return dec_main, step


def _kv(exe, scope, dec_main, step, prompt, max_new, **kw):
    return gpt.kv_generate(exe, scope, dec_main, step.token_var,
                           step.logits_var, step.cache_names,
                           prompt=prompt, max_new_tokens=max_new, **kw)


# ---------------------------------------------------------------------------
# NgramDrafter (serving/spec_decode.py)
# ---------------------------------------------------------------------------

def test_drafter_proposes_what_followed_the_suffix():
    d = NgramDrafter(max_ngram=3, k=4)
    # suffix [7, 8] occurred earlier, followed by 9, 1, 2, 7
    assert d.draft([7, 8, 9, 1, 2, 7, 8]) == [9, 1, 2, 7]
    assert d.draft([7, 8, 9, 1, 2, 7, 8], k=2) == [9, 1]


def test_drafter_caps_at_k():
    d = NgramDrafter(max_ngram=2, k=2)
    assert d.draft([5, 6, 1, 2, 3, 4, 5, 6]) == [1, 2]
    # per-call k overrides the constructor cap
    assert d.draft([5, 6, 1, 2, 3, 4, 5, 6], k=3) == [1, 2, 3]


def test_drafter_most_recent_occurrence_wins():
    # suffix [1, 2] appears twice; the later occurrence (followed by 9)
    # must win over the earlier one (followed by 5)
    d = NgramDrafter(max_ngram=2, k=1)
    assert d.draft([1, 2, 5, 0, 1, 2, 9, 0, 1, 2]) == [9]


def test_drafter_prefers_longer_ngram():
    # the 1-gram suffix [2] occurs at index 0 (followed by 7) but the
    # 2-gram suffix [3, 2] also matches (followed by 8): longer wins
    d = NgramDrafter(max_ngram=3, k=1)
    assert d.draft([2, 7, 3, 2, 8, 0, 3, 2]) == [8]


def test_drafter_no_match_returns_empty():
    d = NgramDrafter(max_ngram=3, k=4)
    assert d.draft([1, 2, 3, 4, 5]) == []       # unique suffix
    assert d.draft([]) == []
    assert d.draft([1]) == []                   # too short
    assert NgramDrafter(max_ngram=0).draft([1, 2, 1, 2]) == []


def test_drafter_period_one_repeat():
    # an immediately-repeated token is itself an n-gram hit: the match
    # at index 0 is followed by the second 9
    d = NgramDrafter(max_ngram=1, k=2)
    assert d.draft([9, 9]) == [9]


# ---------------------------------------------------------------------------
# accept_draft (models/sampling.py)
# ---------------------------------------------------------------------------

def _rows(*argmaxes, vocab=8):
    """Logit rows whose greedy token is the given id per row."""
    out = np.zeros((len(argmaxes), vocab), np.float32)
    for j, t in enumerate(argmaxes):
        out[j, t] = 5.0
    return out


def test_accept_draft_full_accept_emits_bonus():
    emitted, n_acc = sampling.accept_draft(_rows(1, 2, 3, 4), [1, 2, 3])
    assert emitted == [1, 2, 3, 4] and n_acc == 3   # k accepted + bonus


def test_accept_draft_full_reject_is_single_step():
    emitted, n_acc = sampling.accept_draft(_rows(7, 2, 3), [1, 2])
    assert emitted == [7] and n_acc == 0  # the draw IS the correction


def test_accept_draft_stops_at_first_mismatch():
    emitted, n_acc = sampling.accept_draft(_rows(1, 6, 3), [1, 2])
    assert emitted == [1, 6] and n_acc == 1


def test_accept_draft_empty_draft_degenerates_to_sample():
    emitted, n_acc = sampling.accept_draft(_rows(5), [])
    assert emitted == [sampling.sample_token(_rows(5)[0])] == [5]
    assert n_acc == 0


def test_accept_draft_shape_validation():
    with pytest.raises(ValueError):
        sampling.accept_draft(_rows(1, 2), [1, 2])   # needs k+1 rows
    with pytest.raises(ValueError):
        sampling.accept_draft(_rows(1)[0], [])       # 1-D logits


def test_accept_draft_sampled_path_matches_serial_rng_order():
    """One rng draw per EMITTED token in serial order: replaying the
    same rows through sample_token with an identically-seeded rng must
    reproduce accept_draft's emissions exactly."""
    rows = np.random.RandomState(11).randn(4, VOCAB).astype(np.float32)
    draft = [3, 1, 4]
    emitted, n_acc = sampling.accept_draft(
        rows, draft, temperature=0.9, top_k=5,
        rng=np.random.RandomState(42))
    ref_rng = np.random.RandomState(42)
    want = []
    for j in range(len(emitted)):
        want.append(sampling.sample_token(rows[j], temperature=0.9,
                                          top_k=5, rng=ref_rng))
    assert emitted == want
    # n_accepted is the length of the agreeing prefix
    agree = 0
    while agree < min(len(emitted), len(draft)) \
            and emitted[agree] == draft[agree]:
        agree += 1
    assert n_acc == agree


# ---------------------------------------------------------------------------
# engine parity: spec decode vs serial kv_generate
# ---------------------------------------------------------------------------

# mixed lengths; max_new large enough that contexts wrap the vocab-16
# cycle and the drafter actually fires
PROMPTS = [([0, 1, 2], 24), ([5, 6], 20), ([1, 2, 3, 4], 22),
           ([7], 18), ([3, 4, 5], 16)]


@pytest.mark.parametrize("opt_level", [0, 2])
def test_spec_engine_matches_serial_greedy(trained, opt_level):
    """Greedy spec-decode engine under eviction pressure (tight pool)
    must be token-for-token identical to serial kv_generate, with all
    three executables compiled in warmup and none after, and with the
    spec counters showing real drafting happened."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    want = [_kv(exe, scope, dec_main, step, p, n) for p, n in PROMPTS]

    prev_opt = fluid.FLAGS.graph_opt_level
    prev_mon = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_graph_opt_level": opt_level,
                     "FLAGS_enable_monitor": True})
    monitor.reset_stats()
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4,
                               spec_decode=True, spec_k=4)
        assert eng.spec_decode and eng.spec_k == 4
        eng.start()
        try:
            resps = [eng.submit(GenerationRequest(p, n))
                     for p, n in PROMPTS]
            got = [r.result(timeout=120.0)["tokens"] for r in resps]
            assert got == want, (got, want)
            assert eng.post_warmup_compiles() == 0, eng.cache_stats()
        finally:
            eng.stop()
        c = monitor.get_stats_snapshot()["counters"]
        assert c.get("serving.gen_spec_steps", 0) > 0
        proposed = c.get("serving.gen_spec_draft_proposed", 0)
        accepted = c.get("serving.gen_spec_draft_accepted", 0)
        assert proposed > 0 and 0 < accepted <= proposed
    finally:
        fluid.set_flags({"FLAGS_graph_opt_level": prev_opt,
                         "FLAGS_enable_monitor": prev_mon})


def test_spec_engine_matches_serial_sampled(trained):
    """temperature > 0: accept_draft's one-draw-per-emitted-token rng
    discipline keeps sampled outputs bit-exact against serial decode
    with the same seed."""
    cfg, scope, exe = trained
    cases = [([0, 1, 2], 24, 0.9, 7), ([5, 6], 20, 1.3, 11),
             ([1, 2, 3, 4], 22, 0.7, 3)]
    dec_main, step = _serial_decode(cfg)
    want = [_kv(exe, scope, dec_main, step, p, n,
                temperature=t, seed=s) for p, n, t, s in cases]

    eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                           max_slots=2, max_seq=SEQ, block_size=4,
                           spec_decode=True, spec_k=4)
    eng.start()
    try:
        resps = [eng.submit(GenerationRequest(p, n, temperature=t,
                                              seed=s))
                 for p, n, t, s in cases]
        got = [r.result(timeout=120.0)["tokens"] for r in resps]
        assert got == want, (got, want)
        assert eng.post_warmup_compiles() == 0, eng.cache_stats()
    finally:
        eng.stop()


def test_spec_per_request_opt_out_and_flag_default(trained):
    """GenerationRequest.spec_decode=False forces plain decode on a
    spec engine (still correct); FLAGS_gen_spec_decode drives the
    engine default when the ctor arg is omitted."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    prompt, n = [0, 1, 2], 20
    want = _kv(exe, scope, dec_main, step, prompt, n)

    prev = fluid.FLAGS.gen_spec_decode
    fluid.set_flags({"FLAGS_gen_spec_decode": True})
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4)
        assert eng.spec_decode  # picked up the flag default
        eng.start()
        try:
            opted_out = eng.submit(
                GenerationRequest(prompt, n, spec_decode=False))
            opted_in = eng.submit(GenerationRequest(prompt, n))
            assert opted_out.result(timeout=120.0)["tokens"] == want
            assert opted_in.result(timeout=120.0)["tokens"] == want
        finally:
            eng.stop()
    finally:
        fluid.set_flags({"FLAGS_gen_spec_decode": prev})


# ---------------------------------------------------------------------------
# acceptance-aware adaptive spec_k (serving/spec_decode.update_spec_k)
# ---------------------------------------------------------------------------

def test_update_spec_k_pure_function():
    from paddle_tpu.serving import update_spec_k
    # first sample seeds the EWMA directly; low acceptance shrinks
    k, ewma, moved = update_spec_k(4, None, 0.0, k_max=4)
    assert (k, moved) == (3, -1) and ewma == 0.0
    # floor at 1 draft — never moves below
    k2, _, moved2 = update_spec_k(1, 0.0, 0.0, k_max=4)
    assert (k2, moved2) == (1, 0)
    # high acceptance grows back, capped at k_max
    k3, ewma3, moved3 = update_spec_k(3, 0.9, 1.0, k_max=4)
    assert (k3, moved3) == (4, 1) and ewma3 > 0.8
    k4, _, moved4 = update_spec_k(4, 0.95, 1.0, k_max=4)
    assert (k4, moved4) == (4, 0)
    # mid-band holds steady; EWMA blends alpha*rate + (1-alpha)*prev
    k5, ewma5, moved5 = update_spec_k(3, 0.5, 0.6, k_max=4, alpha=0.5)
    assert (k5, moved5) == (3, 0) and abs(ewma5 - 0.55) < 1e-9
    # out-of-range rates are clamped, not propagated
    _, ewma6, _ = update_spec_k(2, None, 7.5, k_max=4)
    assert ewma6 == 1.0


class _BadDrafter:
    """Adversarial drafter: always proposes the wrong successor, so
    every draft is rejected and the adaptive budget must collapse."""

    def draft(self, ctx, k=None):
        k = int(k or 1)
        return [(int(ctx[-1]) + 3) % VOCAB] * k


def test_adaptive_spec_k_shrinks_under_bad_drafter(trained):
    """With a drafter that is always wrong, the per-slot budget must
    walk down to 1 (gen_spec_k_shrinks counter moves, effective-k gauge
    ends at 1) while verify keeps the output EXACTLY serial."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    prompt, n = [0, 1, 2], 24
    want = _kv(exe, scope, dec_main, step, prompt, n)

    prev_mon = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4,
                               spec_decode=True, spec_k=4,
                               spec_adaptive=True)
        assert eng.spec_adaptive
        eng._drafter = _BadDrafter()
        eng.start()
        try:
            got = eng.generate(prompt, n)["tokens"]
            assert got == want, (got, want)
            assert eng.post_warmup_compiles() == 0
        finally:
            eng.stop()
        snap = monitor.get_stats_snapshot()
        c = snap["counters"]
        assert c.get("serving.gen_spec_k_shrinks", 0) >= 3  # 4 -> 1
        assert not c.get("serving.gen_spec_k_grows")
        assert snap["gauges"].get("serving.gen_spec_k_effective") == 1
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev_mon})


def test_adaptive_spec_k_off_keeps_static_budget(trained):
    """spec_adaptive=False: the same bad drafter never moves the
    budget (no shrink counters), and parity still holds."""
    cfg, scope, exe = trained
    dec_main, step = _serial_decode(cfg)
    prompt, n = [5, 6], 20
    want = _kv(exe, scope, dec_main, step, prompt, n)

    prev_mon = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    try:
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=SEQ, block_size=4,
                               spec_decode=True, spec_k=4,
                               spec_adaptive=False)
        assert not eng.spec_adaptive
        eng._drafter = _BadDrafter()
        eng.start()
        try:
            assert eng.generate(prompt, n)["tokens"] == want
        finally:
            eng.stop()
        c = monitor.get_stats_snapshot()["counters"]
        assert not c.get("serving.gen_spec_k_shrinks")
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev_mon})
