"""benchmark/run.py end to end on the CPU at the cells' tiny sizes,
behind its own --rehearsal switch; the training window laid out over
four virtual devices; and `correct` shown to come out false: for the
control in the next precision below, and for each fault a cell can
have, planted under the timed path."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import correct, manifest, traffic, train_window
from benchmark import reference_layers as rl
from benchmark import run as bench_run

ROOT = manifest.ROOT
TRAIN, SERVE = "bert_base_nodropout.pretrain", "gpt2_medium.batch_gen_v2"
OPEN = "gpt2_medium.long_in_open_v2"


def command(workload, *more):
    cmd = manifest.benchmark_json()["command"] + [
        "--workload", workload, "--seed", str(2**31 + 11), "--seconds", "1",
        "--trace", "0", *more]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable] + cmd[1:], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


def last_line(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


def test_without_a_chip_no_result_line():
    p = command(TRAIN)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if '"metrics"' in ln]


@pytest.mark.parametrize("workload,trace", [(TRAIN, "1"), (SERVE, "0"),
                                            (OPEN, "1"), (OPEN, "0")])
def test_rehearsal_end_to_end(workload, trace):
    p = command(workload, "--rehearsal", "--trace", trace)
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p.stdout)
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert list(line)[-1] == "compared"
    bench = manifest.benchmark_json()
    group = "per_layer" if trace == "1" else "end_to_end"
    allowed = {m["name"] for m in manifest.metrics_of(workload, group, bench)}
    assert line["metrics"] and set(line["metrics"]) <= allowed
    if trace == "0":
        assert set(line["metrics"]) == allowed
        assert all(v["value"] > 0 for v in line["metrics"].values())
    for name, row in line["compared"].items():
        assert f"compared {name}: " in p.stderr
    if workload != TRAIN:
        # the window's own line: room in the trace, said out loud, and
        # a backlog closed on the first boundary past --seconds
        window = next(ph for ph in map(json.loads, p.stdout.splitlines()[:-1])
                      if ph.get("phase") == "window")
        assert 0 < window["sent"] <= window["requests"]
        if workload == SERVE:
            # a turn or two of a loaded CPU past --seconds, never the
            # grace of 5 s that only an error may reach
            assert 1.0 < window["window_s"] < 3.5
            assert "warning" not in window
        else:
            assert window["ttft_last_third_mean_ms"] > 0


def tiny(workload):
    _, cfg, mix, _ = manifest.cell(workload, rehearsal=True)
    return cfg, mix


def limits(workload):
    """The limits a rehearsal is judged by: the cell's own, and where
    the tiny size reads otherwise than the chip, the rehearsal's."""
    return manifest.cell(workload, rehearsal=True)[3]


def four_virtual_devices():
    """What a cell with `chips: 4` lays out: data parallel over the
    mesh `4` through CompiledProgram under FLAGS_sharded_exec, the same
    mix file, four times the rows. Runs in a process of its own that
    has four devices (the mesh takes every device there is)."""
    import jax
    assert len(jax.devices()) == 4
    cfg, mix = tiny(TRAIN)
    family = manifest.family(cfg["family"])
    cell = family.build(cfg, mix, 4, seed=5)
    try:
        assert cell.rows == 4 * mix["rows_per_chip"]
        batches = traffic.train_batches(5, mix, cell.rows, cfg["vocab_size"])
        prog = cell.check_steps(batches[:3])
        win = train_window.run(cell, batches[3:], 0.5, 2)
        on = {len(v.sharding.device_set) for n in cell.param_names
              if isinstance(v := cell.scope.find_var(n), jax.Array)}
        assert on == {4}, on
    finally:
        cell.free()
    assert win["steps"] >= 2 and win["tokens"] == win["steps"] * cell.rows * 32
    ref = family.reference_readings(cfg, 5, batches[:3])
    ok, rows = correct.judge(correct.train_numbers(prog, ref)[0],
                             limits(TRAIN))
    assert ok, rows
    # the exchange between chips left out: the mean over one chip's rows
    one = family.reference_readings(cfg, 5, batches[:3],
                                    rows_used=cell.rows // 4)
    assert not correct.judge(correct.train_numbers(one, ref)[0],
                             limits(TRAIN))[0]


def test_training_window_on_four_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]


def test_controls_come_out_not_correct():
    """The reference in the program's place, in the nearest precision
    below the one the configuration states."""
    cfg, mix = tiny(TRAIN)
    family = manifest.family(cfg["family"])
    batches = traffic.train_batches(9, mix, mix["rows_per_chip"],
                                    cfg["vocab_size"])[:3]
    ref = family.reference_readings(cfg, 9, batches)
    low = family.reference_readings(cfg, 9, batches, prec=rl.INT8)
    assert not correct.judge(correct.train_numbers(low, ref)[0],
                             limits(TRAIN))[0]


@pytest.mark.parametrize("workload", [SERVE, OPEN])
def test_serving_controls_come_out_not_correct(workload):
    """The reference in bfloat16 throughout (and in int8) stands in the
    program's place over the same prompts and tokens, and its logits
    rows are judged as the program's are, by the cell's own limits."""
    cfg, mix = tiny(workload)

    class Served:
        def __init__(self, i):
            row = traffic.prompt_tokens(9, i, 48, cfg["vocab_size"]).tolist()
            self.prompt, self.tokens, self.logits = row[:32], row[32:], None
    picked = [Served(i) for i in range(3)]
    for prec in (rl.BFLOAT16, rl.INT8):
        low = bench_run.served_numbers(cfg, 9, picked, control=prec)
        ok, rows = correct.judge({**low, "compiles_in_window": 0.0,
                                  "requests_failed": 0.0}, limits(workload))
        assert not ok, (type(prec).__name__, rows)
        assert low["logit_gap_var"] > limits(workload)["logit_gap_var"], rows
    # no rows, or not one a token: no number, and not correct
    picked[0].logits = []
    assert bench_run.served_numbers(cfg, 9, picked) == {}
    assert not correct.judge({}, limits(workload))[0]


def run_in_process(monkeypatch, capsys, workload):
    from paddle_tpu.core import compile_cache
    monkeypatch.setattr(compile_cache, "configure_compile_cache", lambda: "")
    rc = bench_run.main(["--workload", workload, "--seed", "77",
                         "--seconds", "0.5", "--trace", "0", "--rehearsal"])
    assert rc == 0
    return last_line(capsys.readouterr().out)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_faults_come_out_not_correct(monkeypatch, capsys, fault):
    cfg, _ = tiny(TRAIN)
    family = manifest.family(cfg["family"])
    inner = family.TrainCell.step
    if fault == "state_unchanged":
        def step(self, batch):
            keep = {n: self.scope.find_var(n) for n in self.scope.names()}
            keep = {n: np.asarray(v) for n, v in keep.items()
                    if v is not None}
            out = inner(self, batch)
            for n, v in keep.items():
                self.scope.set(n, v)
            return out
    else:
        def step(self, batch):
            half = batch.shape[0] // 2
            return inner(self, np.concatenate([batch[:half], batch[:half]]))
    monkeypatch.setattr(family.TrainCell, "step", step)
    line = run_in_process(monkeypatch, capsys, TRAIN)
    assert line["correct"] is False, line["compared"]


@pytest.mark.parametrize("fault", ["token", "logits"])
def test_an_altered_answer_comes_out_not_correct(monkeypatch, capsys, fault):
    """A token altered where it is sampled fails `served_gap_max`; the
    logits computed in bfloat16 where they are produced (rounded as the
    engine fetches them) fail the logits' own numbers."""
    from paddle_tpu.models import sampling
    from paddle_tpu.serving import GenerationEngine
    if fault == "token":
        inner, calls = sampling.sample_token, [0]

        def altered(logits, **kw):
            tok = inner(logits, **kw)
            calls[0] += 1
            return (tok + 1) % len(logits) if calls[0] % 5 == 0 else tok
        monkeypatch.setattr(sampling, "sample_token", altered)
        fails = "served_gap_max"
    else:
        import jax.numpy as jnp
        inner = GenerationEngine._run_paged

        def rounded(self, *a):
            out = inner(self, *a)
            return np.asarray(jnp.asarray(out, jnp.bfloat16), np.float32)
        monkeypatch.setattr(GenerationEngine, "_run_paged", rounded)
        fails = "logit_gap_var"
    line = run_in_process(monkeypatch, capsys, SERVE)
    assert line["correct"] is False, line["compared"]
    assert line["compared"][fails]["value"] > line["compared"][fails]["limit"]


if __name__ == "__main__":
    four_virtual_devices()
