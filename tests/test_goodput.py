"""Goodput accounting (paddle_tpu/goodput.py): the category-sum ≈
wall-clock invariant on a real CPU training run (and the double-count
failure mode it exists to catch), input-starvation under a
slow_step:site=reader fault — input_wait must dominate the ledger and
the auto-installed burn-rate alert must fire exactly once with exactly
one incident bundle — TrainerGuard / RetryPolicy category attribution,
serving busy/idle counters, and the tools/goodput_report.py CLI
round-trip through the JSON validator, the perf ledger, and
metrics_report."""
import contextlib
import glob
import io
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import goodput, layers, monitor, monitor_alerts
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.monitor_alerts import AlertEngine, parse_rules
from paddle_tpu.resilience import RetryPolicy, TrainerGuard, \
    TransientFault, reset_injector
from paddle_tpu.resilience.trainer_guard import PreemptedError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tools(module):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(module)
    finally:
        sys.path.pop(0)


@pytest.fixture(autouse=True)
def _goodput_hygiene():
    """No test may leak a live ledger, an armed fault, or an appended
    alert rule into the rest of the suite."""
    yield
    goodput.reset()
    monitor_alerts.stop_alerts()
    monitor.reset_stats()
    fluid.set_flags({"FLAGS_enable_goodput": False,
                     "FLAGS_enable_monitor": False,
                     "FLAGS_alert_rules": "",
                     "FLAGS_fault_spec": "",
                     "FLAGS_fault_seed": 0})
    reset_injector()


@contextlib.contextmanager
def _goodput_on(**flag_over):
    keys = list(flag_over) + ["enable_monitor", "enable_goodput",
                              "alert_rules"]
    prev = {k: getattr(FLAGS, k) for k in keys}
    fluid.set_flags({"FLAGS_enable_monitor": True,
                     "FLAGS_enable_goodput": True,
                     **{f"FLAGS_{k}": v for k, v in flag_over.items()}})
    monitor.reset_stats()
    try:
        yield
    finally:
        goodput.reset()
        monitor.reset_stats()
        fluid.set_flags({f"FLAGS_{k}": v for k, v in prev.items()})


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


def _build_sgd():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.unique_name.guard("gpt_"):
        x = layers.data("x", shape=[-1, 3], dtype="float32",
                        append_batch_size=False)
        y = layers.data("y", shape=[-1, 1], dtype="float32",
                        append_batch_size=False)
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _clean_batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(4, 3).astype(np.float32),
            "y": rng.randn(4, 1).astype(np.float32)}


def _nan_batch():
    b = _clean_batch(1)
    b["x"] = b["x"].copy()
    b["x"][0, 0] = np.nan
    return b


# ---------------------------------------------------------------------------
# Off switch + basic ledger semantics
# ---------------------------------------------------------------------------

def test_disabled_is_total_noop():
    assert goodput.start_run("off") is None
    assert goodput.active() is None
    goodput.attribute("device_compute", 1.0)   # must not raise
    goodput.note_input_wait(1.0)
    goodput.serving_busy(1.0)
    assert goodput.snapshot() is None
    assert goodput.end_run() is None


def test_invariant_residual_vs_double_count():
    """`other` absorbs unattributed wall (sum == wall, invariant
    holds); double counting pushes the sum past wall and the invariant
    catches it via sum_frac_err."""
    with _goodput_on():
        led = goodput.start_run("inv")
        assert led is not None
        time.sleep(0.02)
        snap = goodput.end_run()
        assert set(snap["categories"]) == set(goodput.CATEGORIES)
        # nothing attributed -> everything is residual `other`
        assert snap["categories"]["other"] == pytest.approx(
            snap["wall_s"], rel=1e-6)
        assert goodput.check_invariant(snap)

        # over-attribution: categories now sum way past wall-clock
        goodput.attribute("device_compute", 10.0 * snap["wall_s"])
        bad = goodput.snapshot()
        assert bad["sum_frac_err"] > 1.0
        assert not goodput.check_invariant(bad)


def test_starved_step_counter_thresholds():
    with _goodput_on(goodput_starved_ms=20.0):
        goodput.start_run("thresh")
        goodput.note_input_wait(0.001)   # 1ms: fed
        goodput.note_input_wait(0.050)   # 50ms: starved
        snap = goodput.end_run()
        assert snap["input_batches"] == 2
        assert snap["starved_steps"] == 1
        c = monitor.get_stats_snapshot()["counters"]
        assert c["goodput.input_batches"] == 2
        assert c["goodput.input_starved_steps"] == 1


def test_serving_counters_feed_the_registry():
    with _goodput_on():
        goodput.start_run("serve")
        goodput.serving_busy(0.4)
        goodput.serving_idle(0.6)
        goodput.serving_pad_waste(0.1)
        goodput.gen_busy(0.2)
        goodput.gen_idle(0.3)
        c = monitor.get_stats_snapshot()["counters"]
        assert c["goodput.serving_busy_seconds"] == pytest.approx(0.4)
        assert c["goodput.serving_idle_seconds"] == pytest.approx(0.6)
        assert c["goodput.serving_pad_waste_seconds"] == \
            pytest.approx(0.1)
        assert c["goodput.gen_busy_seconds"] == pytest.approx(0.2)
        assert c["goodput.gen_idle_seconds"] == pytest.approx(0.3)


# ---------------------------------------------------------------------------
# Real training runs (CPU smoke): invariant, warmup, starvation
# ---------------------------------------------------------------------------

def test_smoke_clean_run_sums_to_wall_clock():
    gr = _tools("goodput_report")
    snap = gr.run_smoke(steps=8, batch=4, label="t_clean")
    assert snap["steps"] == 8
    assert goodput.check_invariant(snap, tol=0.05)
    # exactly one compile (the first dispatch); zero after warmup
    assert snap["compile_steps"] >= 1
    assert snap["post_warmup_compiles"] == 0
    assert 0.0 < snap["goodput_frac"] <= 1.0
    assert snap["categories"]["compile"] > 0.0
    assert snap["categories"]["device_compute"] > 0.0


def test_starved_smoke_input_wait_dominates():
    """The ISSUE acceptance demo: under slow_step:site=reader the
    ledger must pin the blame on input_wait, not smear it into
    other/compute."""
    gr = _tools("goodput_report")
    snap = gr.run_smoke(steps=8, batch=4, starve=True, starve_ms=50.0,
                        label="t_starved")
    assert goodput.check_invariant(snap, tol=0.05)
    cats = snap["categories"]
    # the smoke's first step compiles inside the ledger's window, and
    # how long that takes is the machine's load, not the reader's: the
    # blame is judged over the wall clock that is not compile
    steady = {k: v for k, v in cats.items() if k != "compile"}
    top = max(steady, key=lambda k: steady[k])
    assert top == "input_wait", cats
    assert cats["input_wait"] >= \
        0.5 * (snap["wall_s"] - cats["compile"]), cats
    assert snap["starved_steps"] == 8
    # the waterfall records carry the per-step wait for the report
    waits = [r["input_wait_s"] for r in snap["step_records"]]
    assert max(waits) >= 0.04


# ---------------------------------------------------------------------------
# Starvation alert: exactly one firing, exactly one incident bundle
# ---------------------------------------------------------------------------

def test_starvation_alert_fires_once_with_one_bundle(tmp_path):
    """start_run auto-installs the input_starvation burn rule; a real
    reader under slow_step:site=reader must trip it exactly once (one
    pending->firing episode == one incident bundle), and healthy
    warmup traffic must not."""
    with _goodput_on(goodput_starved_ms=20.0,
                     goodput_alert_windows="5s,15s",
                     alert_bundle_dir=str(tmp_path),
                     alert_rules=""):
        goodput.start_run("alerting")
        assert "input_starvation" in FLAGS.alert_rules
        clock = _Clock()
        eng = AlertEngine(parse_rules(FLAGS.alert_rules), clock=clock)

        # healthy warmup: 2ms waits, enough ticks to cover both windows
        for _ in range(5):
            for _ in range(20):
                goodput.note_input_wait(0.002)
            eng.evaluate_once()
            clock.t += 5
        out = eng.evaluate_once()
        r = out["rules"][0]
        assert out["firing"] == 0
        assert all(w["covered"] for w in r["window_detail"].values())

        # starve: a real DataLoader whose reader site stalls ~30ms
        fluid.set_flags(
            {"FLAGS_fault_spec": "slow_step:ms=30:site=reader"})
        reset_injector()

        def _drain_batches(n):
            loader = fluid.io.DataLoader.from_generator(capacity=2)
            loader.set_batch_generator(
                lambda: iter([{"i": k} for k in range(n)]))
            for _ in loader():
                pass

        fired_tick = None
        for tick in range(5):
            _drain_batches(10)
            clock.t += 5
            out = eng.evaluate_once()
            if out["firing"] and fired_tick is None:
                fired_tick = tick
        assert fired_tick is not None, out
        assert out["firing"] == 1

        c = monitor.get_stats_snapshot()["counters"]
        assert c["alerts.fired"] == 1
        assert c["goodput.input_starved_steps"] >= 10
        bundles = sorted(glob.glob(
            str(tmp_path / "incident_input_starvation_*.json")))
        assert len(bundles) == 1, bundles
        with open(bundles[0]) as f:
            bundle = json.load(f)
        assert bundle["rule"]["name"] == "input_starvation"
        validate = _tools("validate_bench_json").validate_incident_bundle
        assert validate(bundle, bundles[0]) == []

        # the ledger agrees with the alert: waits landed in input_wait
        snap = goodput.end_run()
        assert snap["categories"]["input_wait"] > 0.0


def test_start_run_does_not_duplicate_rule():
    with _goodput_on(alert_rules=""):
        goodput.start_run("a")
        once = FLAGS.alert_rules
        goodput.reset()
        goodput.start_run("b")
        assert FLAGS.alert_rules == once
        assert once.count("input_starvation") == 1


# ---------------------------------------------------------------------------
# Resilience-path attribution
# ---------------------------------------------------------------------------

def test_retry_backoff_attribution():
    with _goodput_on():
        goodput.start_run("retry")
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise TransientFault("transient")
            return "ok"

        pol = RetryPolicy(max_attempts=5, base_delay_ms=40.0,
                          max_delay_ms=40.0, sleep=lambda s: None)
        assert pol.call(flaky) == "ok"
        snap = goodput.end_run()
        # two backoffs were attributed even though the sleep was faked
        assert snap["categories"]["retry_backoff"] >= 0.04


def test_trainer_guard_checkpoint_restore_and_rollback(tmp_path):
    main, startup, loss = _build_sgd()
    scope = fluid.Scope()
    ckpt = str(tmp_path / "ckpt")
    with fluid.scope_guard(scope), _goodput_on():
        exe = fluid.Executor()
        exe.run(startup)
        goodput.start_run("guard")
        guard = TrainerGuard(exe, main, scope=scope, fetch_list=[loss],
                             checkpoint_dir=ckpt,
                             install_sigterm=False)
        try:
            assert guard.step(_clean_batch()) is not None
            guard.checkpoint()
            led = goodput.active()
            assert led.category_seconds("checkpoint_save") > 0.0
            assert led.category_seconds("preempt_drain") == 0.0

            assert guard.step(_nan_batch()) is None   # rollback path
            assert led.category_seconds("nan_rollback") > 0.0

            guard.resume()
            assert led.category_seconds("checkpoint_restore") > 0.0

            # preemption drain is its own category, not checkpoint_save
            save_before = led.category_seconds("checkpoint_save")
            guard.request_preemption()
            with pytest.raises(PreemptedError):
                guard.step(_clean_batch(2))
            assert led.category_seconds("preempt_drain") > 0.0
            assert led.category_seconds("checkpoint_save") == \
                pytest.approx(save_before)
        finally:
            guard.close()


# ---------------------------------------------------------------------------
# Report CLI round-trip: validator, perf ledger, metrics_report
# ---------------------------------------------------------------------------

def test_report_cli_roundtrip(tmp_path):
    gr = _tools("goodput_report")
    out = str(tmp_path / "gp.jsonl")
    rc = gr.main(["--smoke", "--steps", "6", "--batch", "4",
                  "--config", "t_roundtrip", "--check", "--out", out])
    assert rc == 0

    vb = _tools("validate_bench_json")
    assert vb.validate_file(out) == []

    recs = [json.loads(l) for l in open(out) if l.strip()]
    rep = [r for r in recs if r.get("kind") == "goodput_report"][-1]
    assert rep["config"] == "t_roundtrip"
    assert rep["post_warmup_compiles"] == 0

    pl = _tools("perf_ledger")
    rows, skipped = pl.rows_from_file(out)
    assert skipped == 0
    metrics = {r["metric"] for r in rows}
    assert {"goodput_frac", "input_wait_s"} <= metrics

    mr = _tools("metrics_report")
    buf = io.StringIO()
    mr.report(out, out=buf)
    text = buf.getvalue()
    assert "-- goodput --" in text
    assert "t_roundtrip" in text


def test_report_check_flag_fails_on_broken_snapshot(tmp_path):
    gr = _tools("goodput_report")
    bad = {"kind": "goodput_snapshot", "label": "bad", "wall_s": 1.0,
           "goodput_frac": 0.0, "sum_frac_err": 0.5, "steps": 0,
           "compile_steps": 0, "post_warmup_compiles": 0,
           "input_batches": 0, "starved_steps": 0, "step_records": [],
           "categories": {k: (2.0 if k == "other" else 0.0)
                          for k in goodput.CATEGORIES}}
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps(bad) + "\n")
    assert gr.main([str(p), "--check"]) == 1
    assert gr.main([str(p)]) == 0
