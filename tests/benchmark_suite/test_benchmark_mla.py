"""The `mla_serve` cell on the CPU at its tiny size: the rehearsal
through benchmark/run.py comes out correct, both controls and a planted
fault come out not correct; the new readers on hand-made traces and
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
from benchmark import workmodel_mla as wm
from benchmark.readers import hybrid_record, mla_work, scope_ms_per_step
from benchmark.trace_reduce import Op, Span

ROOT = manifest.ROOT
CELL = "kimi_k2_5_ep32_l5.batch_long_ctx"
NEW = ["mfu.mla", "mla_attn_roofline.mla", "mla_proj_ms_per_step.mla",
       "moe_ms_per_step.mla", "moe_roofline.mla", "moe_held_share.mla",
       "moe_load_max_over_mean.mla"]


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
        "--workload", CELL, "--seed", str(2**31 + 33), "--seconds", "1",
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
                 "moe_held_share.mla", "moe_load_max_over_mean.mla"):
        assert line["metrics"][name]["value"] > 0, name
    assert all(m["value"] is not None for m in line["metrics"].values())
    assert "mfu.batch" not in allowed and "mfu.hybrid" not in allowed


def run_in_process(monkeypatch, capsys):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    rc = bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                         "0.5", "--trace", "0", "--rehearsal"])
    assert rc == 0
    return last_line(capsys.readouterr().out)


def test_a_rotation_from_the_wrong_position_comes_out_not_correct(
        monkeypatch, capsys):
    """Under the timed path: every row's rotary positions counted from
    0 and not from `start_pos`, so a decode step turns its query and
    its key as the first token's."""
    from paddle_tpu.ops import latent_attention as la
    monkeypatch.setattr(la, "_positions",
                        lambda start, t: la.jnp.arange(t)[None, :]
                        + 0 * start.astype(la.jnp.int32)[:, None])
    line = run_in_process(monkeypatch, capsys)
    assert line["correct"] is False, line["compared"]
    row = line["compared"]["logit_gap_var"]
    assert row["value"] > row["limit"]


def test_the_program_in_process_and_the_controls(monkeypatch, capsys):
    """No fault: correct. Then the reference's own controls in the
    program's place over prompts and tokens of the cell's lengths: the
    weights rounded to int8, and each token's latent cache row rounded
    to int8, each judged by the cell's rehearsal limits."""
    assert run_in_process(monkeypatch, capsys)["correct"] is True
    cfg, _, limits = tiny()

    class Served:
        def __init__(self, i):
            row = traffic.prompt_tokens(9, i, 56, cfg["vocab_size"]).tolist()
            self.prompt, self.tokens, self.logits = row[:24], row[24:], None
    picked = [Served(i) for i in range(3)]
    ref = manifest.reference(cfg["name"])
    assert ref.CONTROLS == ("weights_int8", "cache_int8")
    for control in ref.CONTROLS:
        low = bench_run.served_numbers(cfg, 9, picked, control=control)
        ok, rows = correct.judge({**low, "compiles_in_window": 0.0,
                                  "requests_failed": 0.0}, limits)
        assert not ok, (control, rows)
        assert low["logit_gap_var"] > limits["logit_gap_var"], rows


# -- the configuration and the work model at the published sizes ---------------

def test_every_width_is_the_catalogs_and_the_cut_is_stated():
    cfg, sz = published()
    want = dict(hidden_size=7168, intermediate_size=18432, kv_lora_rank=512,
                q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
                v_head_dim=128, moe_intermediate_size=2048,
                num_attention_heads=64, num_experts_per_tok=8,
                n_shared_experts=1, first_k_dense_replace=1,
                routed_scaling_factor=2.827, rope_theta=50000)
    assert {k: cfg[k] for k in want} == want
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 12, 20480)
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size", "vision_config"}
    assert cfg["published"]["num_hidden_layers"] == 61
    assert cfg["published"]["n_routed_experts"] == 384
    assert cfg["published"]["vocab_size"] == 163840
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["pipeline_stages"]) == (32, 8)
    assert sz["router_width"] == 384 == 32 * sz["experts_held"]
    assert sz["pattern"] == "LDLGLGLGLG"
    entry = next(c for c in manifest.benchmark_json()["configs"]
                 if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"] and \
        set(entry["reduced"]) == set(cfg["reduced"])
    mix = manifest.traffic("backlog_long_in_mid_out")
    assert (mix["arrival"], mix["requests"], mix["queue_depth"],
            mix["max_total_len"], mix["temperature"], mix["timeout_ms"]) == \
        ("backlog", 2048, 128, 9216, 0.0, 600000)
    assert mix["prompt_len"] == {"min": 1024, "max": 8192, "mean": 3072}
    assert mix["output_len"] == {"min": 256, "max": 1024, "mean": 512}


def test_work_model_counts_equal_the_issues_arithmetic():
    cfg, sz = published()
    assert wm.attention_params(sz) == 101_122_048            # 101.12 M
    assert wm.expert_params(sz) == 44_040_192                # 44.04 M
    assert wm.dense_params(sz) == 396_361_728                # 396.4 M
    assert wm.expert_layer_params(sz) == 2_752_512 + 44_040_192
    assert wm.layer_counts(sz) == (5, 1, 4)
    # 6.99 GB of weights, 6,400 B a token, 3.77 GB of pools
    assert round(wm.weight_bytes(sz) / 1e9, 2) == 6.99
    assert wm.kv_token_bytes(sz) == 6400 == 5 * 640 * 2
    tokens = 64 * 9216 + 16
    assert round(tokens * wm.kv_token_bytes(sz) / 1e9, 2) == 3.77
    # a per-head K/V row would be 32 times a latent row's bytes
    assert 64 * (192 + 128) * 2 == 32 * 1280
    # the program prices the same bytes and holds the same parameters
    from benchmark.families import mla_serve
    model = mla_serve.model_config(cfg, sz, cfg["engine"]["dtype"])
    assert model.kv_token_bytes() == wm.kv_token_bytes(sz)
    assert model.state_slot_bytes() == 0
    ref = manifest.reference(cfg["name"])
    made = sum(int(np.prod(shape)) for kind in sz["pattern"]
               for _, shape, _, _ in ref.layer_table(sz, kind))
    ends = sum(int(np.prod(shape)) for _, shape, _, _ in ref.global_table(sz))
    assert made + ends == wm.stack_params(sz) + wm.norm_params(sz) \
        + 4 * sz["router_width"] + 2 * sz["vocab_size"] * sz["hidden_size"]
    # a decode step of 47 rows at 3,300 tokens: 1.0 GB of latent pages,
    # 1.0 GB of attention weights, 5.4 GB of experts and FFNs
    pages = 47 * -(-3300 // 16)
    assert 0.95e9 < wm.attn_bytes(sz, pages, 16) < 1.05e9
    assert round(2 * 5 * wm.attention_params(sz) / 1e9, 1) == 1.0
    rest = wm.moe_bytes(sz, 48) + 2 * wm.dense_params(sz)
    assert 5.3e9 < rest < 5.5e9
    # absorbed attention's share of a prefill row's flops at 3,300 keys
    att = wm.attn_flops(sz, 3300.0)
    row = wm.forward_flops(sz, 1.0, 3300.0, 0.0, wm.expected_held(sz, 1.0))
    assert 1 / 3 < att / row < 0.55


# -- the readers on hand-made traces and records -------------------------------

def hand_made(monkeypatch, with_fields=True):
    """Two decode calls and a prefill call, a module run each, and the
    engine's records of the turns that ran them."""
    _, sz = published()
    nvalid = np.array([1] * 48 + [0] * 16)
    start = np.arange(64) * 100 + 1000
    calls = [("decode", start, nvalid, 0.002, 10.0, 10.02),
             ("prefill", np.zeros(64, int), np.array([16] * 4 + [0] * 60),
              0.002, 10.03, 10.05),
             ("decode", start + 1, nvalid, 0.002, 10.06, 10.08)]
    log = types.SimpleNamespace(calls=calls, slice=(0, 3))
    mods = [Span(f"jit_step({i})", 1.0 + 0.03 * i, 0.025) for i in range(3)]
    ops = []
    for i in (0, 2):   # a decode run: experts 9 ms, kernel 3, projections 2
        t = 1.0 + 0.03 * i
        ops += [Op("ragged-dot-none.1", "ragged-dot-none", "custom-call",
                   t, 0.007),
                Op("fusion.1", "jit(step)/gated_moe:0/18/dot", "fusion",
                   t + 0.007, 0.002),
                Op("paged_attention_read_latent",
                   "jit(step)/paged_attention:0/4/pallas", "custom-call",
                   t + 0.009, 0.003),
                Op("fusion.2", "jit(step)/mla_project:0/3/dot", "fusion",
                   t + 0.012, 0.0015),
                Op("fusion.3", "jit(step)/mla_output:0/6/dot", "fusion",
                   t + 0.0135, 0.0005),
                Op("fusion.4", "jit(step)/mul:0/31", "convolution",
                   t + 0.017, 0.001)]
    ops.append(Op("ragged-dot-none.1", "ragged-dot-none", "custom-call",
                  1.03, 0.02))             # the prefill run's: not counted
    ops.sort(key=lambda o: o.start)
    trace_ = types.SimpleNamespace(ops=[ops], modules=[mods], host=[])
    recs = []
    for t0, t1 in ((9.99, 10.055), (10.056, 10.09)):
        r = {"t_start": t0, "t_end": t1, "decode_rows": 48,
             "block_size": 16, "kv_tokens_resident": 64 * 50}
        if with_fields:
            r.update(moe_selected=48 * 8 * 4, moe_selected_held=48,
                     moe_experts_hit=30, moe_load_max=12,
                     kv_bytes_read=1)
        recs.append(r)
    from paddle_tpu import trace
    monkeypatch.setattr(trace, "iteration_records", lambda: recs)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return {"kind": "serve", "sizes": sz, "log": log, "trace": trace_,
            "peaks": peaks, "t0": 9.0, "window_s": 2.0}, sz


def test_readers_on_a_hand_made_trace(monkeypatch):
    ctx, sz = hand_made(monkeypatch)
    assert mla_work.read(ctx, "moe_ms") == pytest.approx(9.0)
    spec = manifest.metric_file("mla_proj_ms_per_step.mla")
    assert scope_ms_per_step.read(ctx, **spec["args"]) == pytest.approx(2.0)
    spec = manifest.metric_file("paged_attn_ms_per_step.batch")
    assert scope_ms_per_step.read(ctx, **spec["args"]) == pytest.approx(3.0)
    # the experts' roofline: the two decode steps' least time, by bytes
    least = max(wm.moe_flops(sz, 48, 48) / 197e12,
                wm.moe_bytes(sz, 30) / 819e9)
    assert least == wm.moe_bytes(sz, 30) / 819e9
    assert mla_work.read(ctx, "moe") == pytest.approx(100 * 2 * least / 0.018)
    # the kernel's: every held page of every layer once, 1,280 B a token
    # and layer, against the time under the op's scope
    want = 0.0
    for s in (0, 1):
        held = (np.arange(48) * 100 + 1000 + s + 1).astype(float)
        pages = float(np.ceil(held / 16).sum())
        nbytes = pages * 16 * 1280 * 5
        assert nbytes == wm.attn_bytes(sz, pages, 16)
        flops = 5 * 2 * (576 + 512) * 64 * held.sum()
        assert flops == wm.attn_flops(sz, float(held.sum()))
        want += max(nbytes / 819e9, flops / 197e12)
    assert mla_work.read(ctx, "attn") == pytest.approx(100 * want / 0.006)
    assert 0 < mla_work.read(ctx, "attn") < 100
    mfu = mla_work.read(ctx, "mfu")
    flops = 0.0
    for s in (0, 1):
        held = float((np.arange(48) * 100 + 1000 + s + 1).sum())
        flops += wm.forward_flops(sz, 48, held, 48, 48)
    flops += wm.forward_flops(sz, 64, 4 * 16 * 17 / 2, 0,
                              wm.expected_held(sz, 64))
    assert mfu == pytest.approx(100 * flops / 0.085 / 197e12, rel=1e-6)
    assert hybrid_record.read(ctx, "held_share") == pytest.approx(100 / 32)
    assert hybrid_record.read(ctx, "load_max_over_mean") == \
        pytest.approx(12 * 12 / 48)


def test_nothing_to_read_gives_none(monkeypatch):
    """A program from before this PR: records without the fields, no
    step log, no trace, no peaks. Every new reader says None."""
    ctx, _ = hand_made(monkeypatch, with_fields=False)
    for what in ("mfu", "attn", "moe", "moe_ms"):
        assert mla_work.read(ctx, what) is None, what
    for what in ("held_share", "load_max_over_mean"):
        assert hybrid_record.read(ctx, what) is None, what
    ctx, _ = hand_made(monkeypatch)
    assert mla_work.read({**ctx, "peaks": None}, "mfu") is None
    assert mla_work.read({**ctx, "log": None}, "moe") is None
    assert mla_work.read({**ctx, "trace": None}, "attn") is None
    bare = dict(ctx["trace"].__dict__, ops=[[o for o in ctx["trace"].ops[0]
                                             if "paged_att" not in o.tf_op]])
    assert mla_work.read({**ctx, "trace": types.SimpleNamespace(**bare)},
                         "attn") is None
    with pytest.raises(ValueError):
        mla_work.read(ctx, "ssm")
