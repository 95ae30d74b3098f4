"""The `kda_serve` cell on the CPU at its tiny size: the rehearsal
through benchmark/run.py comes out correct, both controls and a planted
fault come out not correct; the new reader on hand-made traces and
records; the work model's counts at the published sizes against the
issue's arithmetic and against what the program and the reference
hold."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmark import correct, manifest, traffic
from benchmark import run as bench_run
from benchmark import workmodel_kda as wm
from benchmark.readers import hybrid_record, kda_work, mla_work
from benchmark.trace_reduce import Op, Span

ROOT = manifest.ROOT
CELL = "kimi_linear_ep8_l8.batch_rollout"
NEW = ["mfu.kda", "kda_ms_per_step.kda", "kda_roofline.kda",
       "mla_attn_roofline.kda", "moe_ms_per_step.kda", "moe_roofline.kda",
       "moe_held_share.kda", "state_bytes_share.kda"]


def last_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def tiny():
    _, cfg, mix, limits = manifest.cell(CELL, rehearsal=True)
    return cfg, mix, limits


def published():
    _, cfg, _, _ = manifest.cell(CELL)
    return cfg, manifest.reference(cfg["name"]).sizes(cfg)


def test_rehearsal_end_to_end_traced():
    cmd = manifest.benchmark_json()["command"] + [
        "--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "1",
        "--trace", "1", "--rehearsal"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable] + cmd[1:], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p.stdout)
    assert line["rehearsal"] is True and line["correct"] is True, \
        line["compared"]
    assert line["failed"] == 0
    allowed = {m["name"] for m in manifest.metrics_of(CELL, "per_layer")}
    assert set(line["metrics"]) <= allowed and set(NEW) <= allowed
    # what reads without a device trace reads on the CPU too, and every
    # metric the line reports is a number
    for name in ("slot_occupancy_mean.batch", "prefill_steps_share.batch",
                 "kv_fill_share.batch", "kv_read_share.batch",
                 "moe_held_share.kda", "state_bytes_share.kda"):
        assert line["metrics"][name]["value"] > 0, name
    assert all(m["value"] is not None for m in line["metrics"].values())
    for other in ("mfu.batch", "mfu.hybrid", "mfu.mla"):
        assert other not in allowed


def run_in_process(monkeypatch, capsys):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    rc = bench_run.main(["--workload", CELL, "--seed", "79", "--seconds",
                         "0.5", "--trace", "0", "--rehearsal"])
    assert rc == 0
    return last_line(capsys.readouterr().out)


def test_a_state_kept_over_a_slots_reuse_comes_out_not_correct(
        monkeypatch, capsys):
    """Under the timed path: no row ever reads as fresh, so a slot's
    second request starts from the KDA state and the window its first
    left (four slots, and the check draws from the trace's first
    eight)."""
    from paddle_tpu.ops import linear_attention as la
    mixer = la.kda_mixer
    monkeypatch.setattr(
        la, "kda_mixer",
        lambda u, w, conv, state, start, nvalid, **kw: mixer(
            u, w, conv, state, la.jnp.maximum(start, 1), nvalid, **kw))
    line = run_in_process(monkeypatch, capsys)
    assert line["correct"] is False, line["compared"]
    row = line["compared"]["logit_gap_var"]
    assert row["value"] > row["limit"]


def test_the_program_in_process_and_the_controls(monkeypatch, capsys):
    """No fault: correct. Then the reference's own controls in the
    program's place over prompts and tokens of the cell's lengths: the
    weights rounded to int8, and the KDA state rounded to bfloat16
    after every token, each judged by the cell's rehearsal limits."""
    assert run_in_process(monkeypatch, capsys)["correct"] is True
    cfg, _, limits = tiny()

    class Served:
        def __init__(self, i):
            row = traffic.prompt_tokens(9, i, 56, cfg["vocab_size"]).tolist()
            self.prompt, self.tokens, self.logits = row[:24], row[24:], None
    picked = [Served(i) for i in range(3)]
    ref = manifest.reference(cfg["name"])
    assert ref.CONTROLS == ("weights_int8", "state_bf16")
    for control in ref.CONTROLS:
        low = bench_run.served_numbers(cfg, 9, picked, control=control)
        ok, rows = correct.judge({**low, "compiles_in_window": 0.0,
                                  "requests_failed": 0.0}, limits)
        assert not ok, (control, rows)
        assert low["logit_gap_var"] > limits["logit_gap_var"], rows


# -- the configuration and the work model at the published sizes ---------------

def test_every_width_is_the_catalogs_and_the_cut_is_stated():
    cfg, sz = published()
    want = dict(hidden_size=2304, intermediate_size=9216, kv_lora_rank=512,
                q_lora_rank=None, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, moe_intermediate_size=1024, head_dim=72,
                num_attention_heads=32, num_key_value_heads=32,
                num_experts_per_token=8, num_shared_experts=1,
                first_k_dense_replace=1, routed_scaling_factor=2.446,
                rope_scaling=None, mla_use_nope=True, num_expert_group=1,
                topk_group=1, moe_router_activation_func="sigmoid",
                moe_renormalize=True, rms_norm_eps=1e-5)
    assert {k: cfg[k] for k in want} == want
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7]
    assert lin["full_attn_layers"] == [4, 8]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (8, 32, 20480)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "linear_attn_config",
                                   "num_experts", "vocab_size"}
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (27, 256, 163840)
    # the eight layers are the published layers 1 to 8 in their order
    assert pub["linear_attn_config"]["kda_layers"][:6] == lin["kda_layers"]
    assert pub["linear_attn_config"]["full_attn_layers"][:2] == \
        lin["full_attn_layers"]
    dep = cfg["deployment"]
    assert dep["chips_per_layer"] == 8 and dep["attention"] == "data-parallel"
    assert sz["router_width"] == 256 == 8 * sz["experts_held"]
    assert sz["num_experts_per_tok"] == 8
    assert sz["pattern"] == "KDKGKGLGKGKGKGLG"
    assert cfg["engine"] == {"max_slots": 128, "max_seq": 3072,
                             "queue_capacity": 512, "paged": True,
                             "dtype": "bfloat16"}
    for key in ("kda_gate_width", "kda_activations", "kda_state",
                "selection_bias", "cache_row", "absorbed", "weights",
                "queue_capacity", "reference_positions"):
        assert key in cfg["assumed"], key
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and \
        set(entry["reduced"]) == set(cfg["reduced"])
    mix = manifest.traffic("backlog_mid_in_mid_out")
    assert (mix["arrival"], mix["requests"], mix["queue_depth"],
            mix["max_total_len"], mix["temperature"], mix["timeout_ms"]) == \
        ("backlog", 4096, 256, 3072, 0.0, 600000)
    assert mix["prompt_len"] == {"min": 256, "max": 2048, "mean": 768}
    assert mix["output_len"] == {"min": 256, "max": 1024, "mean": 512}
    # the loader offers slots + queue_depth requests before the first
    # admission: the engine's queue has to hold them
    assert cfg["engine"]["max_slots"] + mix["queue_depth"] <= \
        cfg["engine"]["queue_capacity"]


def test_work_model_counts_equal_the_issues_arithmetic():
    cfg, sz = published()
    assert wm.layer_counts(sz) == (6, 2, 1, 7)
    assert round(sum(wm.kda_params(sz)) / 1e6, 2) == 39.51
    assert round(wm.attention_params(sz) / 1e6, 2) == 29.11
    assert wm.expert_params(sz) == 7_077_888                    # 7.078 M
    assert round(wm.dense_params(sz) / 1e6, 2) == 63.70
    # 4.19 GB of weights, 13.03 MB a slot, 2,560 B a token
    assert round(wm.weight_bytes(sz) / 1e9, 2) == 4.19
    assert wm.state_slot_bytes(sz) == 6 * 2_170_880 == 13_025_280
    assert wm.kv_token_bytes(sz) == 2560 == 2 * 640 * 2
    assert round(128 * wm.state_slot_bytes(sz) / 1e9, 2) == 1.67
    assert round(128 * 3072 * wm.kv_token_bytes(sz) / 1e9, 2) == 1.01
    # the program prices the same bytes and holds the same parameters
    from benchmark.families import kda_serve
    model = kda_serve.model_config(sz, cfg["engine"]["dtype"])
    assert model.kv_token_bytes() == wm.kv_token_bytes(sz)
    assert model.state_slot_bytes() == wm.state_slot_bytes(sz)
    ref = manifest.reference(cfg["name"])
    made = nbytes = 0
    tables = [ref.layer_table(sz, kind) for kind in sz["pattern"]] \
        + [ref.global_table(sz)]
    for table in tables:
        for _, shape, _, dtype in table:
            made += int(np.prod(shape))
            nbytes += int(np.prod(shape)) * (2 if dtype == "bfloat16" else 4)
    assert nbytes == wm.weight_bytes(sz)
    assert made == wm.stack_params(sz) + wm.norm_params(sz) \
        + 7 * sz["router_width"] + 2 * sz["vocab_size"] * sz["hidden_size"]
    # a decode step of 128 live rows: 3.8 GB of KDA state, windows and
    # mixer weights, 3.3 GB of experts at 98% of the held ones hit
    assert round(wm.kda_bytes(sz, 128) / 1e9, 1) == 3.8
    assert 3.2e9 < wm.moe_bytes(sz, round(0.98 * 7 * 32)) < 3.3e9
    assert 0.55e9 < wm.attn_bytes(sz, 128 * -(-1800 // 16), 16) < 0.62e9
    # the recurrence's own flops a token are a twentieth of the mixer's
    assert wm.kda_token_flops(sz) < 0.1 * 2 * wm.kda_params(sz)[0]


# -- the readers on hand-made traces and records -------------------------------

def hand_made(monkeypatch, with_fields=True):
    """Two decode calls and a prefill call, a module run each, and the
    engine's records of the turns that ran them."""
    _, sz = published()
    nvalid = np.array([1] * 110 + [0] * 18)
    start = np.arange(128) * 10 + 800
    calls = [("decode", start, nvalid, 0.002, 10.0, 10.02),
             ("prefill", np.zeros(128, int),
              np.array([0] * 110 + [16] * 18), 0.002, 10.03, 10.05),
             ("decode", start + 1, nvalid, 0.002, 10.06, 10.08)]
    log = types.SimpleNamespace(calls=calls, slice=(0, 3))
    mods = [Span(f"jit_step({i})", 1.0 + 0.03 * i, 0.025) for i in range(3)]
    ops = []
    for i in (0, 2):   # a decode run: KDA 8 ms, experts 9, kernel 2
        t = 1.0 + 0.03 * i
        ops += [Op("fusion.7", "jit(step)/kda_mixer:0/3/mul", "fusion",
                   t, 0.006),
                Op("fusion.8", "jit(step)/kda_mixer:0/11/dot", "fusion",
                   t + 0.006, 0.002),
                Op("ragged-dot-none.1", "ragged-dot-none", "custom-call",
                   t + 0.008, 0.007),
                Op("fusion.1", "jit(step)/gated_moe:0/18/dot", "fusion",
                   t + 0.015, 0.002),
                Op("paged_attention_read_latent",
                   "jit(step)/paged_attention:0/28/pallas", "custom-call",
                   t + 0.017, 0.002),
                Op("fusion.4", "jit(step)/mul:0/71", "convolution",
                   t + 0.019, 0.001)]
    ops.append(Op("fusion.7", "jit(step)/kda_mixer:0/3/mul", "fusion",
                  1.03, 0.02))             # the prefill run's: not counted
    ops.sort(key=lambda o: o.start)
    trace_ = types.SimpleNamespace(ops=[ops], modules=[mods], host=[])
    recs = []
    for t0, t1 in ((9.99, 10.055), (10.056, 10.09)):
        r = {"t_start": t0, "t_end": t1, "decode_rows": 110,
             "block_size": 16, "kv_tokens_resident": 128 * 1000}
        if with_fields:
            r.update(moe_selected=110 * 8 * 7, moe_selected_held=770,
                     moe_experts_hit=218, moe_load_max=70, kv_bytes_read=1,
                     state_slots_live=128,
                     state_bytes=128 * wm.state_slot_bytes(sz),
                     state_bytes_moved=2 * 128 * wm.state_slot_bytes(sz))
        recs.append(r)
    from paddle_tpu import trace
    monkeypatch.setattr(trace, "iteration_records", lambda: recs)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return {"kind": "serve", "sizes": sz, "log": log, "trace": trace_,
            "peaks": peaks, "t0": 9.0, "window_s": 2.0}, sz


def test_readers_on_a_hand_made_trace(monkeypatch):
    ctx, sz = hand_made(monkeypatch)
    assert kda_work.read(ctx, "kda_ms") == pytest.approx(8.0)
    assert mla_work.read(ctx, "moe_ms") == pytest.approx(9.0)
    # the mixers' roofline: 110 rows' state and windows read and written
    # once and the six mixers' weights, by bytes, against the time
    # under the op's scope
    nbytes = 2 * 110 * 13_025_280 + 6 * (2 * wm.kda_params(sz)[0]
                                         + 4 * wm.kda_params(sz)[1])
    assert nbytes == wm.kda_bytes(sz, 110)
    assert wm.kda_flops(sz, 110) / 197e12 < nbytes / 819e9
    assert kda_work.read(ctx, "kda") == \
        pytest.approx(100 * 2 * nbytes / 819e9 / 0.016)
    assert 0 < kda_work.read(ctx, "kda") < 100
    least = wm.moe_bytes(sz, 218) / 819e9
    assert least > wm.moe_flops(sz, 110, 770) / 197e12
    assert mla_work.read(ctx, "moe") == pytest.approx(100 * 2 * least / 0.018)
    assert 0 < mla_work.read(ctx, "attn") < 100
    flops = 0.0
    for s in (0, 1):
        held = float((np.arange(110) * 10 + 800 + s + 1).sum())
        flops += wm.forward_flops(sz, 110, held, 110, 770)
    flops += wm.forward_flops(sz, 18 * 16, 18 * 16 * 17 / 2, 0,
                              wm.expected_held(sz, 18 * 16))
    assert kda_work.read(ctx, "mfu") == \
        pytest.approx(100 * flops / 0.085 / 197e12, rel=1e-6)
    assert hybrid_record.read(ctx, "held_share") == pytest.approx(12.5)
    state = 128 * 13_025_280
    assert kda_work.read(ctx, "state_bytes_share") == \
        pytest.approx(100 * state / (state + 128 * 1000 * 2560))


def test_nothing_to_read_gives_none(monkeypatch):
    """A program from before this PR: records without the fields, no
    step log, no trace, no peaks, no `kda_mixer:` scope. The new reader
    says None and does not raise."""
    ctx, _ = hand_made(monkeypatch, with_fields=False)
    for what in ("mfu", "kda", "kda_ms", "state_bytes_share"):
        assert kda_work.read(ctx, what) is None, what
    ctx, _ = hand_made(monkeypatch)
    assert kda_work.read({**ctx, "peaks": None}, "mfu") is None
    assert kda_work.read({**ctx, "log": None}, "kda") is None
    assert kda_work.read({**ctx, "trace": None}, "kda_ms") is None
    bare = dict(ctx["trace"].__dict__, ops=[[o for o in ctx["trace"].ops[0]
                                             if "kda_mixer" not in o.tf_op]])
    assert kda_work.read({**ctx, "trace": types.SimpleNamespace(**bare)},
                         "kda") is None
    with pytest.raises(ValueError):
        kda_work.read(ctx, "ssm")
