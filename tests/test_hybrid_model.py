"""The pattern-string decoder (models/hybrid.py) against its plain
reference (benchmark/configs/nemotron3_super_ep4_l11_reference.py) at a
small size, seeded: each op against the reference's layer, the whole
model through GenerationEngine, the expert shares adding up, no token
dropped, what the engine refuses for a recurrent model, and the
engine's programs, GPT's three and this model's two, as the parent
commit built them."""
import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark import manifest
from benchmark.configs import nemotron3_super_ep4_l11_reference as ref
from benchmark.families import hybrid_serve
from paddle_tpu.core.registry import REGISTRY
from paddle_tpu.models import gpt
from paddle_tpu.ops import state_space
from paddle_tpu.parallel import moe
from paddle_tpu.serving import GenerationEngine, GenerationRequest, disagg
from paddle_tpu.serving import kv_wire

CELL = "nemotron3_super_ep4_l11.batch_reason"
SEED = 2**31 + 29


def small(**over):
    """The rehearsal's sizes: hidden 64, 8 query heads over 2 KV heads
    of 16, 16 experts top-4 in a latent of 32 (4 held), 8 Mamba heads of
    8, state 16, pattern EM*EM, vocabulary 512."""
    _, cfg, _, _ = manifest.cell(CELL, rehearsal=True)
    cfg = {**cfg, **over}
    return cfg, ref.sizes(cfg)


def leaves(sz, i, dtype=jnp.float32):
    return {k: v.astype(dtype) if v.dtype == jnp.bfloat16 else v
            for k, v in ref.layer_leaves(sz, SEED, i).items()}


def f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


# -- (a) each op against the reference's layer --------------------------------

def test_rms_norm_plain_grouped_and_gated():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 32)), jnp.float32)
    w = jnp.asarray(1 + 0.1 * rng.normal(size=32), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(3, 5, 32)), jnp.float32)
    np.testing.assert_allclose(state_space.rms_norm(x, w, 1e-5),
                               ref.rms_norm(x, w, 1e-5), rtol=1e-6)
    want = ref.rms_norm((x * jax.nn.silu(gate)).reshape(3, 5, 4, 8),
                        w.reshape(4, 8), 1e-5).reshape(3, 5, 32)
    got = REGISTRY.get("rms_norm").lower(
        None, {"X": [x], "Scale": [w], "Gate": [gate]},
        {"epsilon": 1e-5, "groups": 4})["Out"][0]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert state_space.rms_norm(x.astype(jnp.bfloat16), w, 1e-5).dtype \
        == jnp.bfloat16


def mixer_weights(p):
    return {"in_proj": p["mixer.in_proj.w"], "conv_w": p["mixer.conv.w"],
            "conv_b": p["mixer.conv.b"], "dt_bias": p["mixer.dt_bias"],
            "a_log": p["mixer.A_log"], "d": p["mixer.D"],
            "norm_w": p["mixer.norm.w"], "out_proj": p["mixer.out_proj.w"]}


def test_mamba2_mixer_chunks_and_steps_match_the_full_scan():
    """Four rows, each its own sequence: a full chunk then steps; a
    partly valid chunk then steps; a row muted throughout, whose state
    (garbage) must come back untouched; a row that starts late over a
    stale state, which start == 0 must wipe."""
    _, sz = small()
    p = leaves(sz, 1)
    w = mixer_weights(p)
    d, k = sz["hidden_size"], sz["conv_kernel"]
    h, hp, n = sz["mamba_num_heads"], sz["mamba_head_dim"], \
        sz["ssm_state_size"]
    _, conv_c, _ = ref.mamba_dims(sz)
    rng = np.random.default_rng(1)
    lengths = [20, 12, 0, 6]
    u = [jnp.asarray(rng.normal(size=(m, d)), jnp.float32) for m in lengths]
    full = jax.jit(lambda x: ref.mamba_mixer(x, f32(p), sz))
    want = [np.asarray(full(x)) if len(x) else None for x in u]
    mixer = jax.jit(lambda *a: state_space.mamba2_mixer(
        *a, groups=sz["n_groups"], eps=sz["norm_eps"]))
    conv = jnp.asarray(rng.normal(size=(4, k - 1, conv_c)), jnp.float32)
    ssm = jnp.asarray(rng.normal(size=(4, h, hp, n)), jnp.float32)
    conv0, ssm0 = np.asarray(conv), np.asarray(ssm)
    got = [[] for _ in lengths]
    fed = [0, 0, 0, None]       # row 3 joins after the first chunk
    # (tokens a step, n_valid by row); row 1's chunk holds 5 of 16
    plan = [(16, [16, 5, 0, 0])] + [(1, [1, 1, 0, 1])] * 4 \
        + [(16, [0, 3, 0, 2])]
    for t, nv in plan:
        x = np.zeros((4, t, d), np.float32)
        start = np.zeros(4, np.int32)
        for b, m in enumerate(nv):
            if m:
                fed[b] = fed[b] or 0
                x[b, :m] = u[b][fed[b]:fed[b] + m]
                start[b] = fed[b]
        out, conv, ssm = mixer(jnp.asarray(x), w, conv, ssm,
                               jnp.asarray(start),
                               jnp.asarray(nv, jnp.int32))
        for b, m in enumerate(nv):
            if m:
                got[b].append(np.asarray(out[b, :m]))
                fed[b] += m
    for b, m in enumerate(lengths):
        if m:
            np.testing.assert_allclose(np.concatenate(got[b]), want[b],
                                       rtol=2e-4, atol=2e-5)
            assert fed[b] == m
    np.testing.assert_array_equal(np.asarray(conv[2]), conv0[2])
    np.testing.assert_array_equal(np.asarray(ssm[2]), ssm0[2])


@pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_grouped_heads_against_the_reference(pool_dtype):
    """8 query heads over 2 KV heads, a narrow pool of kv_heads x
    head_dim lanes: a chunk of 16 (one row partly valid, one muted),
    then single steps, against plain causal attention."""
    _, sz = small()
    p = f32(leaves(sz, 2))
    h, kv, hd = sz["num_attention_heads"], sz["num_key_value_heads"], \
        sz["head_dim"]
    d, bs, nb = sz["hidden_size"], 16, 9
    rng = np.random.default_rng(2)
    lengths = [19, 7, 0]
    u = [jnp.asarray(rng.normal(size=(m, d)), jnp.float32) for m in lengths]
    plain = jax.jit(lambda x: ref.attention(x, p, sz))
    want = [np.asarray(plain(x)) if len(x) else None for x in u]
    from paddle_tpu.ops.pallas.paged_attention import pool_lanes
    lanes = pool_lanes(kv * hd)
    pools = [jnp.zeros((nb, bs, lanes), pool_dtype)] * 2
    table = jnp.asarray([[1, 2, 0, 0], [3, 4, 0, 0], [0, 0, 0, 0]],
                        jnp.int32)
    lower = jax.jit(lambda ins: REGISTRY.get("paged_attention").lower(
        None, ins, {"sm_scale": hd ** -0.5}))
    fed, got = [0, 0, 0], [[], [], []]
    for t, nv in [(16, [16, 5, 0]), (1, [1, 1, 0]), (1, [1, 1, 0]),
                  (1, [1, 0, 0])]:
        x = np.zeros((3, t, d), np.float32)
        for b, m in enumerate(nv):
            x[b, :m] = u[b][fed[b]:fed[b] + m]
        x = jnp.asarray(x)

        def heads(z, n):
            return z.reshape(3, t, n, hd).transpose(0, 2, 1, 3)
        outs = lower({
            "Q": [heads(x @ p["att.q.w"], h)],
            "K": [heads(x @ p["att.k.w"], kv)],
            "V": [heads(x @ p["att.v.w"], kv)],
            "CacheK": [pools[0]], "CacheV": [pools[1]],
            "BlockTable": [table], "StartPos": [jnp.asarray(fed)],
            "NValid": [jnp.asarray(nv)]})
        pools = [outs["CacheKOut"][0], outs["CacheVOut"][0]]
        assert pools[0].dtype == pool_dtype
        ctx = outs["Out"][0].transpose(0, 2, 1, 3).reshape(3, t, h * hd)
        y = ctx @ p["att.o.w"]
        for b, m in enumerate(nv):
            if m:
                got[b].append(np.asarray(y[b, :m]))
                fed[b] += m
    tol = 2e-4 if pool_dtype == jnp.float32 else 3e-2
    for b, m in enumerate(lengths):
        if m:
            np.testing.assert_allclose(np.concatenate(got[b]), want[b],
                                       rtol=tol, atol=tol)


def jit_moe(sz, share=0):
    """The program's layer, jitted: (x [B, T, d], leaves, n_valid)."""
    return jax.jit(lambda x, p, nv: moe.latent_moe(
        x, moe_params(p), sz["num_experts_per_tok"],
        sz["routed_scaling_factor"], share=share, n_valid=nv))


def moe_params(p):
    return {"router_w": p["moe.router.w"], "router_bias": p["moe.router.bias"],
            "down": p["moe.down.w"], "w1": p["moe.w1"], "w2": p["moe.w2"],
            "up": p["moe.up.w"], "shared_w1": p["moe.shared.w1"],
            "shared_w2": p["moe.shared.w2"]}


@pytest.mark.parametrize("tokens", [1, 16])
def test_latent_moe_against_the_reference_layer(tokens):
    """Share 0 of 4 (4 of 16 experts held), rows muted and partly valid:
    the valid tokens equal the reference's layer, and the probe counts
    them and no other."""
    _, sz = small()
    p = leaves(sz, 0)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, tokens, sz["hidden_size"])),
                    jnp.float32)
    nv = [tokens, max(1, tokens // 3), 0, tokens]
    out, probe = jit_moe(sz)(x, p, jnp.asarray(nv, jnp.int32))
    # the reference over every token; the valid ones are compared
    want = jax.jit(lambda u: ref.latent_moe(u, f32(p), sz))(
        x.reshape(4 * tokens, -1)).reshape(x.shape)
    sel, _ = jax.jit(lambda u: ref.route(u, f32(p), sz))(
        x.reshape(4 * tokens, -1))
    sel = np.asarray(sel).reshape(4, tokens, -1)
    made = held = 0
    for b, m in enumerate(nv):
        np.testing.assert_allclose(out[b, :m], want[b, :m], rtol=2e-4,
                                   atol=2e-5)
        made += sel[b, :m].size
        held += int((sel[b, :m] < sz["experts_held"]).sum())
    assert probe.dtype == jnp.int32
    assert int(probe[0]) == made and int(probe[1]) == held
    assert 0 < int(probe[2]) <= sz["experts_held"]
    assert int(probe[3]) * int(probe[2]) >= held >= int(probe[3])


# -- (c) the shares add up; (d) nothing is dropped ----------------------------

def uncut():
    """The same layer with all 16 experts held, and its leaves."""
    cfg, _ = small()
    whole = dict(cfg, n_routed_experts=16)
    sz = ref.sizes(whole)
    return sz, leaves(sz, 0)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Shares k = 0..3, each with its own four experts: their routed
    parts, with the latent projections and the shared expert counted
    once, are the uncut 16-expert layer: in the reference, in the
    program's op, and over an `ep` mesh axis of four devices."""
    sz, p = uncut()
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(24, sz["hidden_size"])), jnp.float32)
    whole = np.asarray(jax.jit(
        lambda u: ref.latent_moe(u, f32(p), sz, share=0))(u))
    held = 4
    cut = dict(sz, experts_held=held)
    shared = jax.jit(lambda u: ref.shared_part(u, f32(p)))(u)
    part = jax.jit(lambda u, mine, k: ref.routed_part(u, f32(mine), cut, k))
    routed_ref, routed_op = 0.0, 0.0
    for k in range(4):
        mine = dict(p, **{"moe.w1": p["moe.w1"][k * held:(k + 1) * held],
                          "moe.w2": p["moe.w2"][k * held:(k + 1) * held]})
        routed_ref = routed_ref + part(u, mine, k)
        out, _ = jax.jit(lambda x, mine, k: moe.latent_moe(
            x, moe_params(mine), sz["num_experts_per_tok"],
            sz["routed_scaling_factor"], share=k))(u[None], mine, k)
        # a share's output is its routed part through W_up, plus the
        # shared expert that every share computes alike
        routed_op = routed_op + out[0] - shared
    summed = ref._mm(routed_ref, p["moe.up.w"]) + shared
    np.testing.assert_allclose(summed, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(routed_op + shared, whole, rtol=2e-4,
                               atol=2e-5)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("ep",))
    out, probe = moe.latent_moe_sharded(
        u[None], moe_params(p), mesh, sz["num_experts_per_tok"],
        sz["routed_scaling_factor"])
    np.testing.assert_allclose(out[0], whole, rtol=2e-4, atol=2e-5)
    assert int(probe[0]) == int(probe[1]) == 24 * sz["num_experts_per_tok"]


def test_no_token_is_dropped_when_every_token_takes_one_expert():
    """A selection bias that sends all 64 tokens to expert 2 (and to
    three experts of other shares): the held expert sees 64 tokens, 16
    times the even load, and every one gets its output."""
    _, sz = small()
    p = leaves(sz, 0)
    bias = np.full(sz["router_width"], -10.0, np.float32)
    bias[[2, 5, 9, 13]] = 10.0
    p["moe.router.bias"] = jnp.asarray(bias)
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.normal(size=(4, 16, sz["hidden_size"])), jnp.float32)
    out, probe = jit_moe(sz)(u, p, jnp.full((4,), 16, jnp.int32))
    assert [int(v) for v in probe] == [256, 64, 1, 64]
    want = jax.jit(lambda u: ref.latent_moe(u, f32(p), sz))(
        u.reshape(64, -1))
    np.testing.assert_allclose(out.reshape(64, -1), want, rtol=2e-4,
                               atol=2e-5)
    routed = out.reshape(64, -1) - ref.shared_part(u.reshape(64, -1), f32(p))
    assert float(jnp.abs(routed).max(axis=1).min()) > 1e-7


# -- (b) through GenerationEngine ---------------------------------------------

def engine_logits(dtype, max_slots=3):
    """Six requests of uneven prompts over three slots (so slots are
    reused), greedy: for each the logits rows it was sampled from."""
    cfg, sz = small()
    cfg = dict(cfg, engine=dict(cfg["engine"], dtype=dtype,
                                max_slots=max_slots))
    cell = hybrid_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED)
    cell.warm()
    t0 = time.perf_counter()
    rng = np.random.default_rng(6)
    jobs = []
    for n_prompt, n_out in [(1, 5), (17, 6), (40, 4), (16, 5), (33, 7),
                            (5, 3)]:
        prompt = rng.integers(0, sz["vocab_size"], n_prompt).tolist()
        rows = []
        resp = cell.engine.submit(GenerationRequest(
            prompt, n_out, timeout_ms=600000,
            logits_cb=lambda r, rows=rows: rows.append(np.array(r))))
        jobs.append((prompt, rows, resp))
    done = [(prompt, rows, dict(resp.result(timeout=300),
                                prefill_steps=resp.timings["prefill_steps"]))
            for prompt, rows, resp in jobs]
    assert cell.engine.recurrent
    cell.stop()     # the last turn's record is there once it has ended
    records = [r for r in fluid.trace.iteration_records()
               if r["t_start"] >= t0]
    return cfg, done, records


@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4),
                                         ("bfloat16", 0.15)])
def test_engine_prefill_and_decode_match_the_full_forward(dtype, limit):
    """Chunk-prefilled then decoded through the engine against the
    reference's full forward, logits not tokens: the float32 build to
    1e-4 of a row's spread; the bfloat16 build (weights, activations and
    pool rounded to 8 bits of mantissa through five blocks) to 0.15 of
    it, which a wrong state or a wrong page (gaps of order 1) fails."""
    cfg, done, records = engine_logits(dtype)
    p = ref.params(cfg, SEED)
    for prompt, rows, result in done:
        tokens = result["tokens"]
        assert len(rows) == len(tokens) and result["cached_tokens"] == 0
        seq = prompt + tokens
        want = ref.logits(cfg, p, seq)[len(prompt) - 1:len(seq) - 1]
        gap = np.abs(np.stack(rows) - want) / want.std(axis=-1,
                                                       keepdims=True)
        assert gap.max() < limit, (len(prompt), gap.max())
        # greedy: the token is the arg-max of the row handed over
        assert [int(r.argmax()) for r in rows] == tokens
    assert any(r["state_slots_live"] == 3 and r["state_bytes"] > 0
               for r in records)
    assert sum(r["moe_selected"] for r in records) > 0
    # every request asked for its rows (`logits_cb`): one crossed to
    # the host for each token, and no other
    assert sum(r["logit_rows_fetched"] for r in records) == \
        sum(len(result["tokens"]) for _, _, result in done)


def test_a_recurrent_model_keeps_a_page_a_request_a_step():
    """The mixers carry row b's state in row b, so the engine packs no
    second tile of a request into a prefill step: a prompt rides one
    step a page, every step feeds each request one row, and the logits
    stay the full forward's (the prompts of 40 and 33 tokens are three
    pages each on an engine with three rows, which a model without
    such state prefills in one step)."""
    cfg, done, records = engine_logits("float32")
    p = ref.params(cfg, SEED)
    for prompt, rows, result in done:
        assert result["prefill_steps"] == -(-(len(prompt) - 1) // 16)
        seq = prompt + result["tokens"]
        want = ref.logits(cfg, p, seq)[len(prompt) - 1:len(seq) - 1]
        gap = np.abs(np.stack(rows) - want) / want.std(axis=-1,
                                                       keepdims=True)
        assert gap.max() < 1e-4, (len(prompt), gap.max())
    fed = [r for r in records if r["prefill_rows"]]
    assert fed and all(r["prefill_tiles"] == r["prefill_rows"] <= 3
                       and r["block_size"] == 16 for r in fed)


# -- (e) what the engine does not do for a recurrent model --------------------

def test_prefix_cache_adopts_nothing_and_spec_disagg_wire_refuse():
    cfg, sz = small()
    cell = hybrid_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED)
    eng = cell.engine
    assert eng.recurrent and eng.state_bytes() == \
        cfg["engine"]["max_slots"] * eng.cfg.state_slot_bytes()
    cell.warm()
    t0 = time.perf_counter()
    prompt = list(range(40))
    first = eng.generate(prompt, 2, timeout_ms=600000)
    before = sum(r["prefix_skipped_recurrent"]
                 for r in fluid.trace.iteration_records())
    again = eng.generate(prompt, 2, timeout_ms=600000)
    after = sum(r["prefix_skipped_recurrent"]
                for r in fluid.trace.iteration_records())
    assert first["cached_tokens"] == again["cached_tokens"] == 0
    assert again["tokens"] == first["tokens"] and after == before + 1
    assert len(eng._prefix) == 0
    # greedy, and nobody asked for a row: the picks and the expert
    # layers' probe are all a step brought back. Compiled with the
    # engine's own fetch list, each program is the executable it warmed
    assert sum(r["logit_rows_fetched"]
               for r in fluid.trace.iteration_records()
               if r["t_start"] >= t0) == 0
    assert eng.fetch_list(eng._prog) == [eng.step.picks_var,
                                         eng.step.probe_var]
    with fluid.scope_guard(eng.scope):
        for _, prog, feed, _ in eng.executables():
            eng.exe.compiled(prog, feed=feed,
                             fetch_list=eng.fetch_list(prog))
    assert eng.post_warmup_compiles() == 0, eng.cache_stats()
    with pytest.raises(ValueError, match="recurrent"):
        disagg.export_prefix(eng, prompt)
    with pytest.raises(ValueError, match="recurrent state"):
        kv_wire.pack_blocks(eng.scope, eng.step.cache_names, [1], ["a"],
                            eng.block_size, eng.step.state_names)
    cell.stop()
    with pytest.raises(ValueError, match="speculative"):
        GenerationEngine(eng.cfg, fluid.Scope(), max_slots=2, max_seq=64,
                         spec_decode=True, spec_k=2)
    with pytest.raises(ValueError, match="paged=False"):
        GenerationEngine(eng.cfg, fluid.Scope(), max_slots=2, max_seq=64,
                         paged=False)


# -- (f) GPT's programs through the same hook ---------------------------------

ENGINE_OPS = ["arg_max", "cast", "reduce_max", "reduce_min", "scale",
              "elementwise_max", "stack", "assign"]


def model_ops(prog, step):
    """The ops of the model's own step: those before the first that
    reads the step's logits. What follows is the engine's, the same
    eight for every model (`generation._pick_on_device`, PR 32: the
    positions' arg-max and largest |logit| stacked into the one fetched
    variable, and the logits assigned to the state variable that keeps
    them on the device)."""
    ops = prog.global_block().ops
    first = next(k for k, op in enumerate(ops)
                 if step.logits_var.name in op.input_names())
    assert [op.type for op in ops[first:]] == ENGINE_OPS
    assert ops[-1].output_names() == [step.logits_name]
    assert ops[-2].output_names() == [step.picks_var.name]
    return ops[:first]


def fingerprint(prog, step=None):
    """Op list, attributes and shapes, every variable named by the order
    it first appears in (the process's name counters do not show). With
    `step`, a decode or verify step's handle, of the model's own ops
    (`model_ops`): the digests held below were taken of those."""
    blk = prog.global_block()
    order = {}

    def idx(n):
        return order.setdefault(n, len(order))
    sig = []
    for op in (blk.ops if step is None else model_ops(prog, step)):
        ins = [(k, [idx(n) for n in v]) for k, v in sorted(op.inputs.items())]
        outs = [(k, [idx(n) for n in v])
                for k, v in sorted(op.outputs.items())]
        sig.append((op.type, ins, outs,
                    sorted((k, repr(v)) for k, v in op.attrs.items())))
    shapes = [(i, tuple(blk.var(n).shape or ()), str(blk.var(n).dtype),
               bool(blk.var(n).persistable)) for n, i in order.items()]
    return len(sig), hashlib.sha256(
        json.dumps([sig, shapes]).encode()).hexdigest()


GPT_CFG = dict(vocab_size=512, d_model=64, n_heads=2, n_layers=2, d_ff=128,
               max_seq_len=128, dropout=0.0)
GPT_DECODE = (73, "01f80c4c2ab1baf13200ac9eaf0b14f0"
                  "e8c2ff4e5a71d36500e32b0fe5f4fc39")
GPT_PREFILL = (73, "3a6e678459f2a0186717b32d0b76596f"
                   "864c904a009c503b116c8c5fb69e86a1")


def test_gpt_programs_are_the_parents():
    """`GenerationEngine` asks the configuration for its programs; for
    a TransformerConfig that builds what `gpt.build_paged_decode_step`
    built in the parent commit (digests taken there, PR 28's tree)."""
    eng = GenerationEngine(gpt.gpt_small(**GPT_CFG), fluid.Scope(),
                           max_slots=4, max_seq=128, paged=True)
    assert fingerprint(eng._prog, eng.step) == GPT_DECODE
    assert fingerprint(eng._prefill_prog) == GPT_PREFILL
    assert not eng.recurrent and eng.state_bytes() == 0
    assert eng.step.state_names == [] and eng.step.probe_var is None
    assert eng.kv_block_bytes() == 2 * 2 * eng.block_size * 128 * 4


def test_gpt_verify_program_is_the_parents():
    """The third executable comes through the same hook, with k + 1
    tokens a row: what `gpt.build_spec_verify_step` built in the parent
    commit (digest taken there, PR 30's tree)."""
    eng = GenerationEngine(gpt.gpt_small(**GPT_CFG), fluid.Scope(),
                           max_slots=4, max_seq=128, spec_decode=True,
                           spec_k=2)
    assert [name for name, *_ in eng.executables()] == \
        ["decode", "prefill", "spec_verify"]
    assert eng.spec_step.seq_tokens == 3
    assert fingerprint(eng._spec_prog, eng.spec_step) == (
        73, "8aa9ce5610ee3da868f8dc2d7c5f09c1"
            "7b9ce8dd84e81b27b2791ebec3dfa5c3")
    assert fingerprint(eng._prog, eng.step) == GPT_DECODE
    assert fingerprint(eng._prefill_prog) == GPT_PREFILL


@pytest.mark.parametrize("dtype,decode,prefill", [
    ("float32",
     (40, "250929f8c17f020696ebcdd82704d8ad"
          "583aa9ea86d8d357150bc2b51bc6ab23"),
     (37, "ec4da513ca58e82ef4ebd8dda5586a2b"
          "826f2b2d499787545a3b06cda4dc50ee")),
    ("bfloat16",
     (40, "2f15391df1c4b590387b14a3a79e3e54"
          "40dd4476e0d8b8d28629fc57bbf0779d"),
     (37, "d9583f03065f081378bc6e2fc8743454"
          "80dc17eb9198f91284205442575f7ff3"))])
def test_hybrid_programs_are_the_parents(dtype, decode, prefill):
    """The engine that `engine_logits` builds, not started: its two
    programs digest as they did in the parent commit (PR 30's tree)."""
    cfg, _ = small()
    cfg = dict(cfg, engine=dict(cfg["engine"], dtype=dtype, max_slots=3))
    eng = hybrid_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED).engine
    assert fingerprint(eng._prog, eng.step) == decode
    assert fingerprint(eng._prefill_prog) == prefill


@pytest.mark.parametrize("dtype,decode,prefill", [
    ("float32",
     (34, "bd5f239a05c2761a7ec3135ffbfefbda"
          "97d03c011eb8278c7a6829185ef4c87c"),
     (31, "faad0f62385f1cbbd200232f76ddc24b"
          "9e2690d18b5f0579e538e23e72efec49")),
    ("bfloat16",
     (34, "6023f17f9993ec098fca7f00182ce1c1"
          "a3f1c608387a785b6dcf817e3cb2fc4a"),
     (31, "d758592c1bf53d3c1e54a201f509ea6c"
          "a23aa8d78d08303b544761c65f12a2ff"))])
def test_latent_programs_are_the_parents(dtype, decode, prefill):
    """The latent-attention decoder's two programs (letters L, D, G with
    a query latent and rotary attributes) digest as they did before the
    `L` letter learned to do without either (PR 34's tree)."""
    from benchmark.families import mla_serve
    _, cfg, _, _ = manifest.cell("kimi_k2_5_ep32_l5.batch_long_ctx",
                                 rehearsal=True)
    cfg = dict(cfg, engine=dict(cfg["engine"], dtype=dtype, max_slots=3))
    eng = mla_serve.build(cfg, {"timeout_ms": 600000}, 1, SEED).engine
    assert fingerprint(eng._prog, eng.step) == decode
    assert fingerprint(eng._prefill_prog) == prefill


def test_paged_argument_selects_nothing():
    """There is one engine. The constructor's `paged` is what the
    benchmark's call passes: True and nothing build the same programs,
    False is refused and not obeyed, and the flag that chose the other
    engine is unknown, as any name that is no flag."""
    cfg = gpt.gpt_small(**GPT_CFG)
    for kw in ({}, {"paged": True}, {"paged": None}):
        eng = GenerationEngine(cfg, fluid.Scope(), max_slots=4,
                               max_seq=128, **kw)
        assert fingerprint(eng._prog, eng.step) == GPT_DECODE
        assert fingerprint(eng._prefill_prog) == GPT_PREFILL
        assert not hasattr(eng, "paged")
    with pytest.raises(ValueError, match="paged=False.*PR 31"):
        GenerationEngine(cfg, fluid.Scope(), max_slots=2, max_seq=64,
                         paged=False)
    for name in ("FLAGS_no_such_flag", "FLAGS_gen_paged_kv"):
        with pytest.raises(ValueError, match="unknown flag"):
            fluid.get_flags(name)
        with pytest.raises(ValueError, match="unknown flag"):
            fluid.set_flags({name: False})
