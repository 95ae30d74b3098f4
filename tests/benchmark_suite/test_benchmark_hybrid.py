"""The `hybrid_serve` cell on the CPU at its tiny size: the rehearsal
through benchmark/run.py comes out correct; a planted fault (state not
zeroed on slot reuse; one held expert's output dropped) and the
bfloat16-state control come out not correct; the new readers on
hand-made traces and records; the work model's counts at the published
sizes."""
import json
import os
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, manifest, traffic
from benchmark import run as bench_run
from benchmark import workmodel_hybrid as wm
from benchmark.readers import hybrid_record, hybrid_work
from benchmark.trace_reduce import Op, Span

ROOT = manifest.ROOT
CELL = "nemotron3_super_ep4_l11.batch_reason"


def last_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def tiny():
    _, cfg, mix, limits = manifest.cell(CELL, rehearsal=True)
    return cfg, mix, limits


def test_rehearsal_end_to_end_traced():
    cmd = manifest.benchmark_json()["command"] + [
        "--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "1",
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
    assert set(line["metrics"]) <= allowed
    # what reads without a device trace reads on the CPU too: the tap
    # found the step call, and the engine's records carry the new fields
    for name in ("slot_occupancy_mean.batch", "prefill_steps_share.batch",
                 "moe_held_share.hybrid", "moe_load_max_over_mean.hybrid",
                 "state_bytes_share.hybrid", "kv_fill_share.batch"):
        assert line["metrics"][name]["value"] > 0, name
    assert "mfu.batch" not in allowed and "mfu.hybrid" in allowed
    assert "paged_attn_roofline.batch" not in allowed


def run_in_process(monkeypatch, capsys):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    rc = bench_run.main(["--workload", CELL, "--seed", "77", "--seconds",
                         "0.5", "--trace", "0", "--rehearsal"])
    assert rc == 0
    return last_line(capsys.readouterr().out)


@pytest.mark.parametrize("fault", ["state_not_zeroed", "expert_dropped",
                                   "token"])
def test_a_planted_fault_comes_out_not_correct(monkeypatch, capsys, fault):
    """Under the timed path: the recurrent state of a slot's last
    request left in place for its next one (the first request of a slot
    still starts from the zeros the engine seeds, so only reuse shows
    it); the first held expert's output dropped; every fifth token
    altered where it is sampled, which is `served_gap_max`'s to catch."""
    fails = "logit_gap_var"
    if fault == "token":
        from paddle_tpu.models import sampling
        inner, calls = sampling.sample_token, [0]

        def altered(logits, **kw):
            tok = inner(logits, **kw)
            calls[0] += 1
            return (tok + 1) % len(logits) if calls[0] % 5 == 0 else tok
        monkeypatch.setattr(sampling, "sample_token", altered)
        fails = "served_gap_max"
    elif fault == "state_not_zeroed":
        from paddle_tpu.ops import state_space
        inner = state_space.mamba2_mixer

        def never_fresh(u, w, conv, ssm, start, nvalid, **kw):
            return inner(u, w, conv, ssm, jnp.maximum(start, 1), nvalid,
                         **kw)
        monkeypatch.setattr(state_space, "mamba2_mixer", never_fresh)
    else:
        from paddle_tpu.parallel import moe
        inner = moe.experts_apply

        def dropped(rows, sizes, w1, w2, act, b1=None, b2=None):
            return inner(rows, sizes, w1, w2.at[0].set(0.0), act, b1, b2)
        monkeypatch.setattr(moe, "experts_apply", dropped)
    line = run_in_process(monkeypatch, capsys)
    assert line["correct"] is False, line["compared"]
    row = line["compared"][fails]
    assert row["value"] > row["limit"]


def test_the_program_in_process_and_the_controls(monkeypatch, capsys):
    """No fault: correct. Then the reference's own controls in the
    program's place over prompts and tokens of the cell's lengths: the
    SSM state kept in bfloat16 between tokens, and the weights rounded
    to int8, each judged by the cell's rehearsal limits."""
    assert run_in_process(monkeypatch, capsys)["correct"] is True
    cfg, _, limits = tiny()

    class Served:
        def __init__(self, i):
            row = traffic.prompt_tokens(9, i, 56, cfg["vocab_size"]).tolist()
            self.prompt, self.tokens, self.logits = row[:24], row[24:], None
    picked = [Served(i) for i in range(3)]
    ref = manifest.reference(cfg["name"])
    assert ref.CONTROLS == ("ssm_bfloat16", "weights_int8")
    for control in ref.CONTROLS:
        low = bench_run.served_numbers(cfg, 9, picked, control=control)
        ok, rows = correct.judge({**low, "compiles_in_window": 0.0,
                                  "requests_failed": 0.0}, limits)
        assert not ok, (control, rows)
        assert low["logit_gap_var"] > limits["logit_gap_var"], rows


# -- the work model at the published sizes ------------------------------------

def published():
    _, cfg, _, _ = manifest.cell(CELL)
    return cfg, manifest.reference(cfg["name"]).sizes(cfg)


def test_work_model_counts_equal_the_issues_table():
    cfg, sz = published()
    assert wm.expert_params(sz) == 5_505_024                 # 5.505 M
    assert sum(wm.mamba_params(sz)) == 109_640_064           # 109.6 M
    assert sum(wm.attention_params(sz)) == 35_655_680        # 35.7 M
    assert sum(wm.expert_layer_params(sz)) == 54_530_560     # 54.5 M
    assert wm.layer_counts(sz) == (5, 5, 1)
    assert round(wm.stack_params(sz) / 1e6) == 4380          # a period
    assert wm.kv_token_bytes(sz) == 1024
    assert wm.state_slot_bytes(sz) == 5 * (128 * 64 * 128 * 4
                                           + 3 * 10240 * 2)
    assert round(64 * wm.state_slot_bytes(sz) / 1e9, 2) == 1.36
    # the program prices the same bytes
    from benchmark.families import hybrid_serve
    model = hybrid_serve.model_config(sz, cfg["engine"]["dtype"])
    assert model.kv_token_bytes() == wm.kv_token_bytes(sz)
    assert model.state_slot_bytes() == wm.state_slot_bytes(sz)
    # every leaf the reference makes is a parameter the model counts
    ref = manifest.reference(cfg["name"])
    made = sum(int(np.prod(shape)) for kind in sz["pattern"]
               for _, shape, _, _ in ref.layer_table(sz, kind))
    assert made == wm.stack_params(sz)
    # a decode step of 64 rows, 94% of the held experts hit: the bytes
    # of the issue's arithmetic (6.6 GB of experts, 2.7 GB of state)
    hit = round(0.94 * 128) * 5
    assert 6.5e9 < wm.moe_bytes(sz, hit) - 5 * 2 * 54_530_560 < 6.8e9
    assert 2.6e9 < wm.ssm_bytes(sz, 64) - 5 * 2 * 109_640_064 < 2.8e9


# -- the readers on hand-made traces and records -------------------------------

def hand_made(monkeypatch, with_fields=True):
    """Two decode calls and a prefill call, a module run each, and the
    engine's records of the turns that ran them."""
    _, sz = published()
    nvalid = np.array([1] * 64)
    start = np.arange(64) + 40
    calls = [("decode", start, nvalid, 0.002, 10.0, 10.02),
             ("prefill", np.zeros(64, int), np.array([16] * 4 + [0] * 60),
              0.002, 10.03, 10.05),
             ("decode", start + 1, nvalid, 0.002, 10.06, 10.08)]
    log = types.SimpleNamespace(calls=calls, slice=(0, 3))
    mods = [Span(f"jit_step({i})", 1.0 + 0.03 * i, 0.025) for i in range(3)]
    ops = []
    for i in (0, 2):        # a decode run: experts 12 ms, mixers 5 ms
        t = 1.0 + 0.03 * i
        ops += [Op("ragged-dot-none.1", "ragged-dot-none", "custom-call",
                   t, 0.010),
                Op("fusion.1", "jit(step)/latent_moe:0/3/dot", "fusion",
                   t + 0.010, 0.002),
                Op("fusion.2", "jit(step)/mamba2_mixer:0/7/mul", "fusion",
                   t + 0.012, 0.005),
                Op("fusion.3", "jit(step)/mul:0/9", "convolution",
                   t + 0.017, 0.001)]
    ops.append(Op("ragged-dot-none.1", "ragged-dot-none", "custom-call",
                  1.03, 0.02))             # the prefill run's: not counted
    ops.sort(key=lambda o: o.start)
    trace_ = types.SimpleNamespace(ops=[ops], modules=[mods], host=[])
    recs = []
    for t0, t1, decode in ((9.99, 10.055, True), (10.056, 10.09, True)):
        r = {"t_start": t0, "t_end": t1, "decode_rows": 64,
             "kv_tokens_resident": 64 * 50}
        if with_fields:
            r.update(moe_selected=64 * 22 * 5, moe_selected_held=1760,
                     moe_experts_hit=600, moe_load_max=50,
                     state_bytes=64 * wm.state_slot_bytes(sz))
        recs.append(r)
    from paddle_tpu import trace
    monkeypatch.setattr(trace, "iteration_records", lambda: recs)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return {"kind": "serve", "sizes": sz, "log": log, "trace": trace_,
            "peaks": peaks, "t0": 9.0, "window_s": 2.0}, sz


def test_readers_on_a_hand_made_trace(monkeypatch):
    ctx, sz = hand_made(monkeypatch)
    assert hybrid_work.read(ctx, "moe_ms") == pytest.approx(12.0)
    assert hybrid_work.read(ctx, "ssm_ms") == pytest.approx(5.0)
    # roofline: the two decode steps' least time over their device time
    least = max(wm.moe_flops(sz, 64, 1760) / 197e12,
                wm.moe_bytes(sz, 600) / 819e9)
    assert hybrid_work.read(ctx, "moe") == \
        pytest.approx(100 * 2 * least / 0.024)
    assert 50 < hybrid_work.read(ctx, "moe") < 100      # bound by bytes
    least = max(wm.ssm_flops(sz, 64) / 197e12, wm.ssm_bytes(sz, 64) / 819e9)
    assert hybrid_work.read(ctx, "ssm") == \
        pytest.approx(100 * 2 * least / 0.010)
    # mfu: the slice's flops over its length on the device's clock
    flops = 2 * wm.forward_flops(sz, 64, 0, 64, 1760)
    mfu = hybrid_work.read(ctx, "mfu")
    assert 0 < mfu < 100
    chunk = wm.forward_flops(sz, 64, 4 * 16 * 17 / 2, 0,
                             wm.expected_held(sz, 64))
    ctx_sum = float(((np.arange(64) + 40) + 1).sum()
                    + ((np.arange(64) + 41) + 1).sum())
    flops += chunk + 4 * 4096 * ctx_sum
    assert mfu == pytest.approx(100 * flops / 0.085 / 197e12, rel=1e-6)
    assert hybrid_record.read(ctx, "held_share") == pytest.approx(25.0)
    assert hybrid_record.read(ctx, "load_max_over_mean") == \
        pytest.approx(50 * 128 / 1760)
    share = hybrid_record.read(ctx, "state_bytes_share")
    assert 99 < share < 100


def test_nothing_to_read_gives_none(monkeypatch):
    """A program from before this PR: records without the fields, no
    step log, no trace, no peaks. Every new reader says None."""
    ctx, _ = hand_made(monkeypatch, with_fields=False)
    for what in ("mfu", "moe", "ssm", "moe_ms", "ssm_ms"):
        assert hybrid_work.read(ctx, what) is None, what
    for what in ("held_share", "load_max_over_mean", "state_bytes_share"):
        assert hybrid_record.read(ctx, what) is None, what
    ctx, _ = hand_made(monkeypatch)
    assert hybrid_work.read({**ctx, "peaks": None}, "mfu") is None
    assert hybrid_work.read({**ctx, "log": None}, "moe") is None
    assert hybrid_work.read({**ctx, "trace": None}, "ssm") is None
    bare = dict(ctx["trace"].__dict__, ops=[[o for o in ctx["trace"].ops[0]
                                             if "mamba2" not in o.tf_op]])
    assert hybrid_work.read({**ctx, "trace": types.SimpleNamespace(**bare)},
                            "ssm") is None
