"""The latent-attention decoder (models/hybrid.py, letters L, D, G)
against its plain reference
(benchmark/configs/kimi_k2_5_ep32_l5_reference.py) at a small size,
seeded: YaRN against the closed form, each op against the reference's
layer (absorbed against per-head attention), the whole model through
GenerationEngine over reused slots, a prefix-cache hit and a shipped
prefix over latent blocks, the expert shares adding up, no token
dropped."""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark import manifest
from benchmark.configs import kimi_k2_5_ep32_l5_reference as ref
from benchmark.families import mla_serve
from paddle_tpu.analysis.memory import analyze_program_memory
from paddle_tpu.core.registry import REGISTRY
from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops.pallas.paged_attention import pool_lanes
from paddle_tpu.parallel import moe
from paddle_tpu.serving import GenerationEngine, GenerationRequest, disagg

CELL = "kimi_k2_5_ep32_l5.batch_long_ctx"
SEED = 2**31 + 33


def small(**over):
    """The rehearsal's sizes: hidden 64, 4 heads of 16 | 8 | 16 over
    latents of 48 and 32, 8 experts top-2 (2 held), three layers
    L D, L G, L G, vocabulary 512."""
    _, cfg, _, _ = manifest.cell(CELL, rehearsal=True)
    cfg = {**cfg, **over}
    return cfg, ref.sizes(cfg)


def leaves(sz, i):
    return {k: v.astype(jnp.float32)
            for k, v in ref.layer_leaves(sz, SEED, i).items()}


# -- (a) YaRN ----------------------------------------------------------------

def test_yarn_frequencies_and_scale_against_the_closed_form():
    """At the published settings: theta 50000 over 64 lanes, factor 64
    from 4096 positions, beta 32 / 1."""
    cfg = manifest.config("kimi_k2_5_ep32_l5")
    sz = ref.sizes(cfg)
    got = la.yarn_inv_freq(64, 50000.0, 64.0, 4096, 32.0, 1.0)
    i = np.arange(32)
    f = 50000.0 ** (-2.0 * i / 64)

    def pair(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(50000))
    low, high = math.floor(pair(32)), math.ceil(pair(1))
    assert (low, high) == (8, 20)
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, f / 64 * (1 - m) + f * m, rtol=1e-12)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(sz), rtol=1e-12)
    # the fast pairs turn as published, the slow ones 64 times slower
    assert got[0] == 1.0 and got[8] == f[8]
    np.testing.assert_allclose(got[20:], f[20:] / 64, rtol=1e-12)
    scale = la.yarn_sm_scale(192, 64.0, 1.0)
    assert abs(scale - 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2) < 1e-15
    assert abs(scale - 0.1447) < 5e-5 and scale == ref.sm_scale(sz)
    # mscale / mscale_all_dim is 1: the rotation keeps lengths
    assert la._yarn(ref.rope_attrs(sz), 64)[1] == 1.0


def test_yarn_rotary_op_turns_pairs_from_start_pos():
    _, sz = small()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 5, 4, 8)), jnp.float32)
    start = jnp.asarray([3, 40], jnp.int32)
    got = REGISTRY.get("yarn_rotary").lower(
        None, {"X": [x], "StartPos": [start]}, ref.rope_attrs(sz))["Out"][0]
    for b in range(2):
        want = ref.rope(x[b], int(start[b]) + jnp.arange(5), sz)
        np.testing.assert_allclose(got[b], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert float(jnp.abs(got[0, 0] - x[0, 0]).max()) > 1e-3


# -- (b) each op against the reference's layer ---------------------------------

@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
def test_latent_attention_absorbed_against_the_per_head_reference(pool_dtype):
    """`mla_project` -> `paged_attention` over ONE pool -> `mla_output`:
    a chunk of 16 (one row partly valid, one muted), then single steps,
    against the reference's per-head keys and values. The pool holds a
    token's 40 numbers (32 | 8) in one row of 128 lanes."""
    _, sz = small()
    p = leaves(sz, 0)
    d, bs, nb = sz["hidden_size"], 16, 9
    h, rank = sz["num_attention_heads"], sz["kv_lora_rank"]
    nope, rope = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"]
    rng = np.random.default_rng(2)
    lengths = [19, 7, 0]
    u = [jnp.asarray(rng.normal(size=(m, d)), jnp.float32) for m in lengths]
    plain = jax.jit(lambda x: ref.attention(x, p, sz))
    want = [np.asarray(plain(x)) if len(x) else None for x in u]
    pool = jnp.zeros((nb, bs, pool_lanes(rank + rope)), pool_dtype)
    table = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0], [0, 0, 0, 0]],
                        jnp.int32)
    rattrs = ref.rope_attrs(sz)

    @jax.jit
    def layer(x, pool, start, nv):
        proj = REGISTRY.get("mla_project").lower(None, {
            "X": [x], "QA": [p["att.q_a.w"]], "QNorm": [p["att.q_norm.w"]],
            "QB": [p["att.q_b.w"]], "KVA": [p["att.kv_a.w"]],
            "KVNorm": [p["att.kv_norm.w"]], "KVB": [p["att.kv_b.w"]],
            "StartPos": [start]},
            {"heads": h, "nope_dim": nope, "rope_dim": rope,
             "epsilon": sz["norm_eps"], **rattrs})
        att = REGISTRY.get("paged_attention").lower(None, {
            "Q": proj["Q"], "K": proj["Row"], "CacheK": [pool],
            "BlockTable": [table], "StartPos": [start], "NValid": [nv]},
            {"sm_scale": ref.sm_scale(sz), "value_lanes": rank})
        assert set(att) == {"Out", "CacheKOut"}
        y = REGISTRY.get("mla_output").lower(None, {
            "X": att["Out"], "KVB": [p["att.kv_b.w"]],
            "WO": [p["att.o.w"]]}, {"nope_dim": nope})["Out"][0]
        return y, att["CacheKOut"][0]

    fed, got = [0, 0, 0], [[], [], []]
    for t, nv in [(16, [16, 5, 0]), (1, [1, 1, 0]), (1, [1, 1, 0]),
                  (1, [1, 0, 0])]:
        x = np.zeros((3, t, d), np.float32)
        for b, m in enumerate(nv):
            x[b, :m] = u[b][fed[b]:fed[b] + m]
        y, pool = layer(jnp.asarray(x), pool, jnp.asarray(fed, jnp.int32),
                        jnp.asarray(nv, jnp.int32))
        assert pool.dtype == pool_dtype and y.shape == (3, t, d)
        for b, m in enumerate(nv):
            if m:
                got[b].append(np.asarray(y[b, :m]))
                fed[b] += m
    tol = 2e-4 if pool_dtype == jnp.float32 else 3e-2
    for b, m in enumerate(lengths):
        if m:
            np.testing.assert_allclose(np.concatenate(got[b]), want[b],
                                       rtol=tol, atol=tol)
    # the lanes past a row's 40 numbers stay zero, and the muted row
    # wrote to the scratch block alone
    assert float(jnp.abs(pool[:, :, rank + rope:].astype(jnp.float32)
                         ).max()) == 0.0
    assert float(jnp.abs(pool[5:].astype(jnp.float32)).max()) == 0.0


def moe_params(p):
    return {"router_w": p["moe.router.w"], "router_bias": p["moe.router.bias"],
            "w1": p["moe.w1"], "w2": p["moe.w2"],
            "shared_w1": p["moe.shared.w1"], "shared_w2": p["moe.shared.w2"]}


def jit_moe(sz, share=0):
    return jax.jit(lambda x, p, nv: moe.gated_moe(
        x, moe_params(p), sz["num_experts_per_tok"],
        sz["routed_scaling_factor"], share=share, n_valid=nv))


@pytest.mark.parametrize("tokens", [1, 16])
def test_gated_ffn_and_gated_moe_against_the_reference_layer(tokens):
    """Share 0 of 4 (2 of 8 experts held), rows muted and partly valid:
    the valid tokens equal the reference's layer, and the probe counts
    them and no other; the dense FFN beside it."""
    _, sz = small()
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, tokens, sz["hidden_size"])),
                    jnp.float32)
    flat = x.reshape(4 * tokens, -1)
    dense = leaves(sz, 1)
    got = REGISTRY.get("gated_ffn").lower(
        None, {"X": [x], "W1": [dense["ffn.w1"]], "W2": [dense["ffn.w2"]]},
        {})["Out"][0]
    np.testing.assert_allclose(
        got.reshape(flat.shape),
        ref.gated(flat, dense["ffn.w1"], dense["ffn.w2"]),
        rtol=2e-4, atol=2e-5)
    p = leaves(sz, 3)
    nv = [tokens, max(1, tokens // 3), 0, tokens]
    out, probe = jit_moe(sz)(x, p, jnp.asarray(nv, jnp.int32))
    want = jax.jit(lambda u: ref.gated_moe(u, p, sz))(flat).reshape(x.shape)
    sel, _ = jax.jit(lambda u: ref.route(u, p, sz))(flat)
    sel = np.asarray(sel).reshape(4, tokens, -1)
    made = held = 0
    for b, m in enumerate(nv):
        np.testing.assert_allclose(out[b, :m], want[b, :m], rtol=2e-4,
                                   atol=2e-5)
        made += sel[b, :m].size
        held += int((sel[b, :m] < sz["experts_held"]).sum())
    assert probe.dtype == jnp.int32
    assert int(probe[0]) == made and int(probe[1]) == held
    assert int(probe[2]) <= sz["experts_held"]
    assert int(probe[3]) * max(int(probe[2]), 1) >= held >= int(probe[3])


# -- (c) the shares add up; (d) nothing is dropped ----------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Shares k = 0..3, each with its own two experts of eight (the
    cell's 32 shares of 12 of 384, at the test's size): their routed
    parts, with the shared expert counted once, are the uncut layer: in
    the reference, in the program's op, and over an `ep` mesh axis."""
    cfg, _ = small()
    sz = ref.sizes(dict(cfg, n_routed_experts=8))
    p = leaves(sz, 3)
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(24, sz["hidden_size"])), jnp.float32)
    whole = np.asarray(jax.jit(
        lambda u: ref.gated_moe(u, p, sz, share=0))(u))
    held = 2
    cut = dict(sz, experts_held=held)
    shared = jax.jit(lambda u: ref.shared_part(u, p))(u)
    part = jax.jit(lambda u, mine, k: ref.routed_part(u, mine, cut, k))
    routed_ref, routed_op = 0.0, 0.0
    for k in range(4):
        mine = dict(p, **{"moe.w1": p["moe.w1"][k * held:(k + 1) * held],
                          "moe.w2": p["moe.w2"][k * held:(k + 1) * held]})
        routed_ref = routed_ref + part(u, mine, k)
        out, probe = jax.jit(lambda x, mine, k: moe.gated_moe(
            x, moe_params(mine), sz["num_experts_per_tok"],
            sz["routed_scaling_factor"], share=k))(u[None], mine, k)
        routed_op = routed_op + out[0] - shared
    np.testing.assert_allclose(routed_ref + shared, whole, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(routed_op + shared, whole, rtol=2e-4,
                               atol=2e-5)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("ep",))
    out, probe = moe.gated_moe_sharded(
        u[None], moe_params(p), mesh, sz["num_experts_per_tok"],
        sz["routed_scaling_factor"])
    np.testing.assert_allclose(out[0], whole, rtol=2e-4, atol=2e-5)
    assert int(probe[0]) == int(probe[1]) == 24 * sz["num_experts_per_tok"]


def test_no_token_is_dropped_when_every_token_takes_one_expert():
    """A selection bias that sends all 64 tokens to expert 1 (and to an
    expert of another share): the held expert sees 64 tokens, 16 times
    the even load of top-2 over 8, and every one gets its output."""
    _, sz = small()
    p = leaves(sz, 3)
    bias = np.full(sz["router_width"], -10.0, np.float32)
    bias[[1, 5]] = 10.0
    p["moe.router.bias"] = jnp.asarray(bias)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(4, 16, sz["hidden_size"])), jnp.float32)
    out, probe = jit_moe(sz)(u, p, jnp.full((4,), 16, jnp.int32))
    assert [int(v) for v in probe] == [128, 64, 1, 64]
    flat = u.reshape(64, -1)
    want = jax.jit(lambda u: ref.gated_moe(u, p, sz))(flat)
    np.testing.assert_allclose(out.reshape(64, -1), want, rtol=2e-4,
                               atol=2e-5)
    routed = out.reshape(64, -1) - ref.shared_part(flat, p)
    assert float(jnp.abs(routed).max(axis=1).min()) > 1e-7


# -- (e) through GenerationEngine ---------------------------------------------

JOBS = [(1, 5), (17, 6), (40, 4), (16, 5), (33, 7), (5, 3)]


def engine_logits(dtype, max_slots=3, jobs=None):
    """Six requests of uneven prompts over three slots (so slots are
    reused), greedy: for each the logits rows it was sampled from (and
    in its result the prefill steps it rode)."""
    cfg, sz = small()
    cfg = dict(cfg, engine=dict(cfg["engine"], dtype=dtype,
                                max_slots=max_slots))
    cell = mla_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED)
    cell.warm()
    t0 = time.perf_counter()
    rng = np.random.default_rng(6)
    sent = []
    for n_prompt, n_out in jobs or JOBS:
        prompt = rng.integers(0, sz["vocab_size"], n_prompt).tolist()
        rows = []
        resp = cell.engine.submit(GenerationRequest(
            prompt, n_out, timeout_ms=600000,
            logits_cb=lambda r, rows=rows: rows.append(np.array(r))))
        sent.append((prompt, rows, resp))
    done = [(prompt, rows, dict(resp.result(timeout=300),
                                prefill_steps=resp.timings["prefill_steps"]))
            for prompt, rows, resp in sent]
    cell.stop()
    records = [r for r in fluid.trace.iteration_records()
               if r["t_start"] >= t0]
    return cfg, cell, done, records


def gaps(cfg, done, greedy=True):
    """(widest gap, mean squared gap) between the rows the engine
    handed over and the reference's, in units of a row's spread."""
    p = ref.params(cfg, SEED)
    worst, squares = 0.0, []
    for prompt, rows, result in done:
        tokens = result["tokens"]
        assert len(rows) == len(tokens)
        seq = prompt + tokens
        want = ref.logits(cfg, p, seq)[len(prompt) - 1:len(seq) - 1]
        gap = (np.stack(rows) - want) / want.std(axis=-1, keepdims=True)
        worst = max(worst, float(np.abs(gap).max()))
        squares.append(float((gap * gap).mean()))
        if greedy:      # the token is the arg-max of the row handed over
            assert [int(r.argmax()) for r in rows] == tokens
    return worst, float(np.mean(squares))


def test_engine_float32_prefill_and_decode_match_the_full_forward():
    """Chunk-prefilled then decoded through the latent cache against
    the reference's full forward (per-head attention, no cache), logits
    not tokens, to 1e-4 of a row's spread. One pool a latent layer, a
    block priced at 128 lanes a token and layer."""
    cfg, cell, done, records = engine_logits("float32")
    assert gaps(cfg, done)[0] < 1e-4
    eng = cell.engine
    assert eng.step.cache_names == [f"gen.layer_{i}.kv_pool"
                                    for i in (0, 2, 4)]
    assert not eng.recurrent and eng.step.state_names == []
    assert eng.kv_block_bytes() == eng.block_size * 3 * 128 * 4
    assert sum(r["moe_selected"] for r in records) > 0
    # the bytes counted are the pages counted, at a page's bytes
    assert all(r["kv_bytes_read"] == r["kv_pages_read"] * eng.kv_block_bytes()
               for r in records) and records[-1]["kv_bytes_read"] > 0
    kv = analyze_program_memory(eng._prog).kv_summary()
    assert kv["layout"] == "paged" and kv["kv_vars"] == 3
    assert kv["kv_bytes"] == eng.num_blocks * eng.kv_block_bytes()


@pytest.mark.parametrize("slots,steps", [(8, 1), (4, 2)])
def test_a_prompt_of_five_pages_prefills_in_one_step_of_eight_rows(slots,
                                                                   steps):
    """Five pages of prompt (75 tokens to prefill: four pages and
    eleven tokens) are ONE prefill step of an engine with eight rows
    and two of one with four: the later tiles read, from the one latent
    pool a layer, the rows the earlier tiles wrote in that step, and
    rotate by their own start. Logits against the reference's full
    forward, as for a page a step."""
    cfg, cell, done, records = engine_logits("float32", max_slots=slots,
                                             jobs=[(76, 5)])
    assert gaps(cfg, done)[0] < 1e-4
    assert done[0][2]["prefill_steps"] == steps
    fed = [(r["prefill_rows"], r["prefill_tiles"], r["prefill_tokens"])
           for r in records if r["prefill_rows"]]
    assert fed == ([(1, 5, 75)] if slots == 8
                   else [(1, 4, 64), (1, 1, 11)])
    assert not cell.engine.recurrent


def bf16_accumulating(x, w):
    """A product that keeps its running sum in bfloat16: every term
    rounded, and added to the sum in bfloat16."""
    terms = (x[..., None].astype(jnp.float32)
             * w.astype(jnp.float32)).astype(jnp.bfloat16)
    acc = terms[..., 0, :]
    for k in range(1, w.shape[0]):
        acc = acc + terms[..., k, :]
    return acc


def test_engine_bfloat16_is_inside_what_bfloat16_accumulation_fails(
        monkeypatch):
    """The bfloat16 build (weights, activations and latent rows of 8
    bits of mantissa through six sub-layers, sums in float32): the mean
    squared gap to the reference is 2.3e-5 of a row's variance, the
    widest gap 0.021 of its spread. The same build with the products of
    the attention's projections and the dense FFN summed in bfloat16
    reads 7.4e-5 and 0.049. The limit, 4e-5, lies between (1.7 times
    over the one, 1.85 times under the other)."""
    limit = 4e-5
    cfg, _, done, _ = engine_logits("bfloat16")
    worst, var = gaps(cfg, done)
    assert var < limit and worst < 0.032, (worst, var)
    monkeypatch.setattr(la, "_mm", bf16_accumulating)
    cfg, _, done, _ = engine_logits("bfloat16")
    worst, var = gaps(cfg, done, greedy=False)
    assert var > limit, (worst, var)


def test_a_cached_and_a_shipped_latent_prefix_give_the_same_logits():
    """40 tokens are two full latent blocks and a tail: asked again the
    engine takes the blocks from its prefix cache; exported over
    `kv_wire` (three pools, one a latent layer) and adopted by a second
    engine they serve there. Each time the logits are those of the
    first, uncached pass."""
    cfg, sz = small()
    prompt = np.random.default_rng(7).integers(
        0, sz["vocab_size"], 40).tolist()

    def ask(eng):
        rows = []
        res = eng.submit(GenerationRequest(
            prompt, 4, timeout_ms=600000,
            logits_cb=lambda r: rows.append(np.array(r)))).result(timeout=300)
        return res, np.stack(rows)

    a = mla_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED)
    a.warm()
    first, rows1 = ask(a.engine)
    again, rows2 = ask(a.engine)
    assert first["cached_tokens"] == 0 and again["cached_tokens"] == 32
    assert again["tokens"] == first["tokens"]
    np.testing.assert_allclose(rows2, rows1, rtol=1e-5, atol=1e-6)
    payload = disagg.export_prefix(a.engine, prompt, run_prefill=False)
    assert payload["n_blocks"] == 2 and len(payload["pools"]) == 3
    assert payload["shape"] == [2, a.engine.block_size, 128]
    a.stop()
    b = mla_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED)
    b.warm()
    got = disagg.adopt_prefix(b.engine, payload)
    assert got["adopted"] == 2 and got["resident"] == 2
    shipped, rows3 = ask(b.engine)
    assert shipped["cached_tokens"] == 32
    assert shipped["tokens"] == first["tokens"]
    np.testing.assert_allclose(rows3, rows1, rtol=1e-5, atol=1e-6)
    assert b.engine.post_warmup_compiles() == 0
    b.stop()


def test_speculative_verify_program_builds_over_the_latent_pool():
    """No recurrent state, so `spec_k` stays usable: the third
    executable has k + 1 tokens a row over the same pools."""
    cfg, sz = small()
    tcfg = mla_serve.model_config(cfg, sz, "float32")
    assert tcfg.state_slot_bytes() == 0
    assert tcfg.kv_token_bytes() == 3 * 128 * 4
    eng = GenerationEngine(tcfg, fluid.Scope(), max_slots=2, max_seq=64,
                           spec_decode=True, spec_k=2)
    assert [name for name, *_ in eng.executables()] == \
        ["decode", "prefill", "spec_verify"]
    assert eng.spec_step.seq_tokens == 3
    assert eng.spec_step.cache_names == eng.step.cache_names
