"""Resilience-subsystem tests: fault-spec parsing + deterministic
injection, the retry taxonomy, the circuit-breaker state machine,
executor/reader hook sites, TrainerGuard NaN rollback + preemption
checkpoint/resume (bit-identical), serving graceful degradation over
/healthz, atomic checkpoint writes under a mid-save kill, multiprocess
reader worker-death detection, flight-recorder install idempotency, and
the chaos loadgen acceptance harness.

The preempt/resume acceptance test drives a REAL SIGTERM through the
fault injector (preempt_at) into TrainerGuard's chained handler and
asserts the resumed run's losses and final parameters are bit-identical
to an uninterrupted run that skipped the same NaN batch.
"""
import contextlib
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, monitor
from paddle_tpu.models import gpt
from paddle_tpu.reader_decorator import ReaderWorkerDied, \
    multiprocess_reader
from paddle_tpu.resilience import (CLOSED, HALF_OPEN, OPEN,
                                   CircuitBreaker, FaultSpecError,
                                   NanStepError, PreemptedError,
                                   RetryExhausted, RetryPolicy,
                                   TrainerGuard, TransientFault,
                                   is_transient, parse_fault_spec,
                                   reset_injector)
from paddle_tpu.resilience.faults import FaultInjector
from paddle_tpu.serving import (EngineConfig, GenerationEngine,
                                GenerationRequest, OverloadedError,
                                ServingEngine, serve)

FEAT = 5


@pytest.fixture(autouse=True)
def _fault_hygiene():
    """No test may leak an armed fault spec into the rest of the
    suite."""
    yield
    fluid.set_flags({"FLAGS_fault_spec": "", "FLAGS_fault_seed": 0})
    reset_injector()


@contextlib.contextmanager
def _flags(**kv):
    from paddle_tpu.core.flags import FLAGS
    old = {k: getattr(FLAGS, k) for k in kv}
    fluid.set_flags({f"FLAGS_{k}": v for k, v in kv.items()})
    try:
        yield
    finally:
        fluid.set_flags({f"FLAGS_{k}": v for k, v in old.items()})


@contextlib.contextmanager
def _stats():
    """Monitor on + clean slate (STAT_* are no-ops when the monitor is
    off, so every stats assertion needs this)."""
    with _flags(enable_monitor=True):
        monitor.STAT_RESET()
        try:
            yield
        finally:
            monitor.STAT_RESET()


def _arm(spec, seed=0):
    fluid.set_flags({"FLAGS_fault_spec": spec, "FLAGS_fault_seed": seed})
    reset_injector()


def _disarm():
    fluid.set_flags({"FLAGS_fault_spec": ""})
    reset_injector()


# ---------------------------------------------------------------------------
# fault spec parsing + deterministic decisions
# ---------------------------------------------------------------------------

def test_parse_fault_spec_roundtrip_and_errors():
    specs = parse_fault_spec("step_nan:p=0.01,slow_step:ms=500,"
                             "transient_fail:p=0.02,preempt_at:step=40")
    assert [s.kind for s in specs] == ["step_nan", "slow_step",
                                      "transient_fail", "preempt_at"]
    assert specs[0].p == 0.01 and specs[1].ms == 500.0
    assert specs[3].step == 40
    s = parse_fault_spec("transient_fail:at=3:site=executor")[0]
    assert s.at == 3 and s.site == "executor"
    assert parse_fault_spec("") == []

    for bad in ("bogus_kind:p=0.1",          # unknown kind
                "transient_fail",             # needs p= or at=
                "slow_step:p=0.5",            # needs ms=
                "preempt_at:p=0.5",           # needs step=
                "step_nan:p=1.5",             # p out of range
                "step_nan:at=0",              # at is 1-based
                "transient_fail:p=0.1:site=gpu",  # unknown site
                "transient_fail:frobnicate"):     # malformed param
        with pytest.raises(FaultSpecError):
            parse_fault_spec(bad)


def _fire_pattern(inj, n=60, site="serving"):
    out = []
    for _ in range(n):
        try:
            inj.pre_step(site)
            out.append(False)
        except TransientFault:
            out.append(True)
    return out


def test_fault_decisions_deterministic_per_seed():
    a = _fire_pattern(FaultInjector("transient_fail:p=0.3", seed=123))
    b = _fire_pattern(FaultInjector("transient_fail:p=0.3", seed=123))
    assert a == b
    assert any(a) and not all(a)
    c = _fire_pattern(FaultInjector("transient_fail:p=0.3", seed=124))
    assert c != a
    # at=N fires exactly once, on the Nth invocation
    d = _fire_pattern(FaultInjector("transient_fail:at=4", seed=0), n=10)
    assert d == [False] * 3 + [True] + [False] * 6
    # site restriction: a serving-only fault never fires at the executor
    e = FaultInjector("transient_fail:p=1.0:site=serving", seed=0)
    for _ in range(5):
        e.pre_step("executor")
    with pytest.raises(TransientFault):
        e.pre_step("serving")


# ---------------------------------------------------------------------------
# retry taxonomy + policy
# ---------------------------------------------------------------------------

def test_is_transient_taxonomy():
    assert is_transient(TransientFault("x"))
    assert is_transient(RetryExhausted("x"))
    assert is_transient(OSError("connection reset"))
    assert is_transient(TimeoutError("stuck"))
    for poison in (ValueError("bad shape"), TypeError("bad type"),
                   KeyError("missing feed"), AssertionError("no"),
                   FloatingPointError("nan"), NotImplementedError("op")):
        assert not is_transient(poison)
    # unknown RuntimeErrors default to NOT retryable
    assert not is_transient(RuntimeError("who knows"))


def test_retry_policy_poison_fails_fast():
    calls = []

    def poison():
        calls.append(1)
        raise ValueError("malformed")

    policy = RetryPolicy(max_attempts=5, sleep=lambda s: None)
    with pytest.raises(ValueError):
        policy.call(poison)
    assert len(calls) == 1


def test_retry_policy_transient_then_success():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientFault("glitch")
        return "ok"

    slept = []
    policy = RetryPolicy(max_attempts=5, base_delay_ms=4.0,
                         sleep=slept.append)
    assert policy.call(flaky) == "ok"
    assert len(calls) == 3 and len(slept) == 2
    # jittered exponential: attempt-2 backoff in [half, full] of 2*base
    assert 0.002 <= slept[1] <= 0.008


def test_retry_policy_exhaustion_and_deadline():
    def always():
        raise TransientFault("still down")

    policy = RetryPolicy(max_attempts=3, sleep=lambda s: None)
    with pytest.raises(RetryExhausted) as ei:
        policy.call(always)
    assert isinstance(ei.value.__cause__, TransientFault)

    # a deadline shorter than the next backoff gives up without sleeping
    slept = []
    tight = RetryPolicy(max_attempts=10, base_delay_ms=500.0,
                        deadline_ms=1.0, sleep=slept.append)
    with pytest.raises(RetryExhausted):
        tight.call(always)
    assert slept == []


# ---------------------------------------------------------------------------
# circuit breaker state machine (fake clock)
# ---------------------------------------------------------------------------

def test_breaker_state_cycle_fake_clock():
    t = [0.0]
    b = CircuitBreaker(failure_threshold=2, cooldown_ms=1000.0,
                       clock=lambda: t[0])
    assert b.state == CLOSED and b.allow()
    b.record_failure()
    assert b.state == CLOSED          # below threshold
    b.record_success()
    b.record_failure()
    assert b.state == CLOSED          # success reset the streak
    b.record_failure()
    b.record_failure()
    assert b.state == OPEN
    assert not b.allow()
    assert b.retry_after_s() == pytest.approx(1.0)

    t[0] = 1.1                        # cooldown elapsed -> HALF_OPEN
    assert b.state == HALF_OPEN
    assert b.allow()                  # one probe admitted
    assert not b.allow()              # second concurrent probe shed
    b.record_failure()                # probe failed -> OPEN, fresh clock
    assert b.state == OPEN
    assert b.retry_after_s() == pytest.approx(1.0)

    t[0] = 2.3
    assert b.allow()                  # half-open probe again
    b.record_success()
    assert b.state == CLOSED and b.allow()

    # threshold=0 disables the breaker entirely
    off = CircuitBreaker(failure_threshold=0)
    for _ in range(10):
        off.record_failure()
    assert off.allow() and off.state == CLOSED


# ---------------------------------------------------------------------------
# executor + reader hook sites
# ---------------------------------------------------------------------------

def _scale_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[-1, 3], dtype="float32",
                        append_batch_size=False)
        out = layers.scale(x, scale=2.0)
    return main, startup, out


def test_executor_transient_fault_retried_invisibly():
    main, startup, out = _scale_program()
    scope = fluid.Scope()
    arr = np.arange(6, dtype=np.float32).reshape(2, 3)
    with fluid.scope_guard(scope), _stats():
        exe = fluid.Executor()
        exe.run(startup)
        _arm("transient_fail:at=1:site=executor")
        res = exe.run(main, feed={"x": arr}, fetch_list=[out])
        np.testing.assert_allclose(res[0], arr * 2)
        snap = monitor.get_stats_snapshot()
        assert snap["counters"].get("resilience.fault_transient") == 1
        assert snap["counters"].get("resilience.retries", 0) >= 1


def test_executor_step_nan_corrupts_fetches_then_clean_rerun():
    main, startup, out = _scale_program()
    scope = fluid.Scope()
    arr = np.ones((2, 3), np.float32)
    with fluid.scope_guard(scope), _stats():
        exe = fluid.Executor()
        exe.run(startup)
        _arm("step_nan:at=1:site=executor")
        res = exe.run(main, feed={"x": arr}, fetch_list=[out])
        assert np.isnan(res[0]).any()
        snap = monitor.get_stats_snapshot()
        assert snap["counters"].get("resilience.fault_nan") == 1
        _disarm()
        # device state was never touched: the rerun is clean
        res2 = exe.run(main, feed={"x": arr}, fetch_list=[out])
        np.testing.assert_allclose(res2[0], arr * 2)


def test_reader_fault_site_and_worker_error_propagation():
    loader = fluid.io.DataLoader.from_generator(capacity=2)
    loader.set_batch_generator(
        lambda: iter([{"a": 1}, {"a": 2}, {"a": 3}]))
    _arm("transient_fail:at=2:site=reader")
    it = iter(loader)
    assert next(it) == {"a": 1}
    with pytest.raises(TransientFault):
        next(it)
    _disarm()

    # a prefetch-worker exception surfaces on the training thread
    def bad():
        yield {"a": 1}
        raise OSError("decode died")

    loader2 = fluid.io.DataLoader.from_generator(capacity=2)
    loader2.set_batch_generator(bad)
    it2 = iter(loader2)
    assert next(it2) == {"a": 1}
    with pytest.raises(OSError, match="decode died"):
        next(it2)


# ---------------------------------------------------------------------------
# TrainerGuard: NaN rollback, watchdog, preempt/resume
# ---------------------------------------------------------------------------

def _build_sgd():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), \
            fluid.unique_name.guard("tg_"):
        x = layers.data("x", shape=[-1, 3], dtype="float32",
                        append_batch_size=False)
        y = layers.data("y", shape=[-1, 1], dtype="float32",
                        append_batch_size=False)
        pred = layers.fc(x, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _persist_names(program, scope):
    return [v.name for v in program.list_vars()
            if v.persistable and not v.is_data and scope.has(v.name)]


def _clean_batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(4, 3).astype(np.float32),
            "y": rng.randn(4, 1).astype(np.float32)}


def _nan_batch():
    b = _clean_batch(1)
    b["x"] = b["x"].copy()
    b["x"][0, 0] = np.nan
    return b


def test_trainer_guard_nan_skip_rolls_back():
    main, startup, loss = _build_sgd()
    scope = fluid.Scope()
    with fluid.scope_guard(scope), _stats():
        exe = fluid.Executor()
        exe.run(startup)
        guard = TrainerGuard(exe, main, scope=scope, fetch_list=[loss],
                             install_sigterm=False)
        try:
            out = guard.step(_clean_batch())
            assert out is not None and np.isfinite(out[0]).all()
            names = _persist_names(main, scope)
            before = {n: scope.get_numpy(n).copy() for n in names}
            assert guard.step(_nan_batch()) is None   # skipped
            for n in names:   # SGD applied NaN, rollback undid it
                np.testing.assert_array_equal(scope.get_numpy(n),
                                              before[n])
            assert guard.global_step == 2 and guard.nan_skips == 1
            out2 = guard.step(_clean_batch(2))
            assert out2 is not None and np.isfinite(out2[0]).all()
            snap = monitor.get_stats_snapshot()
            assert snap["counters"].get(
                "resilience.nan_steps_skipped") == 1
            assert snap["counters"].get("resilience.rollbacks") == 1
        finally:
            guard.close()


def test_trainer_guard_max_nan_skips_raises():
    main, startup, loss = _build_sgd()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        guard = TrainerGuard(exe, main, scope=scope, fetch_list=[loss],
                             max_nan_skips=2, install_sigterm=False)
        try:
            assert guard.step(_nan_batch()) is None
            assert guard.step(_nan_batch()) is None
            with pytest.raises(NanStepError):
                guard.step(_nan_batch())
        finally:
            guard.close()


def test_trainer_guard_watchdog_dumps_flight_recorder(tmp_path):
    main, startup, loss = _build_sgd()
    scope = fluid.Scope()
    fr = str(tmp_path / "fr.jsonl")
    with fluid.scope_guard(scope), _stats(), \
            _flags(flight_recorder_path=fr):
        exe = fluid.Executor()
        exe.run(startup)   # compile before the slow_step is armed
        exe.run(main, feed=_clean_batch(), fetch_list=[loss])
        guard = TrainerGuard(exe, main, scope=scope, fetch_list=[loss],
                             watchdog_timeout_s=0.15,
                             install_sigterm=False)
        try:
            _arm("slow_step:ms=700:site=executor")
            guard.step(_clean_batch())
            _disarm()
        finally:
            guard.close()
        snap = monitor.get_stats_snapshot()
        assert snap["counters"].get(
            "resilience.watchdog_fires", 0) >= 1
        assert os.path.exists(fr)
        head = json.loads(open(fr).readline())
        assert head["kind"] == "flight_dump"
        assert head["reason"] == "watchdog_stuck_step"


def test_trainer_guard_preempt_checkpoint_resume_bit_identical(tmp_path):
    """Acceptance: a training run with an injected NaN step AND an
    injected SIGTERM preemption resumes from its checkpoint to
    bit-identical losses and final parameters vs an uninterrupted run
    that skipped the same batch."""
    NB, NAN_AT, PREEMPT_STEP = 8, 2, 4
    rng = np.random.RandomState(7)
    batches = []
    for i in range(NB):
        b = {"x": rng.randn(4, 3).astype(np.float32),
             "y": rng.randn(4, 1).astype(np.float32)}
        if i == NAN_AT:
            b["x"][0, 0] = np.nan
        batches.append(b)

    def fresh():
        main, startup, loss = _build_sgd()
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(startup)
        return main, loss, scope, exe

    # pin identical initial weights across all three program instances
    # (unique_name.guard in _build_sgd makes the names line up)
    main0, loss0, scope0, exe0 = fresh()
    names = _persist_names(main0, scope0)
    init = {n: scope0.get_numpy(n).copy() for n in names}

    def seed_params(scope):
        for n, a in init.items():
            scope.set(n, a.copy())

    def run(guard, stream):
        losses = []
        for b in stream:
            out = guard.step(b)
            losses.append(None if out is None else out[0].copy())
        return losses

    # --- reference: uninterrupted, skips the NaN batch ---------------
    mainA, lossA, scopeA, exeA = fresh()
    seed_params(scopeA)
    guardA = TrainerGuard(exeA, mainA, scope=scopeA,
                          fetch_list=[lossA], install_sigterm=False)
    try:
        lossesA = run(guardA, batches)
    finally:
        guardA.close()
    assert lossesA[NAN_AT] is None
    assert all(v is not None for i, v in enumerate(lossesA)
               if i != NAN_AT)

    # --- interrupted: injected SIGTERM via preempt_at ----------------
    ck = str(tmp_path / "ck")
    mainB, lossB, scopeB, exeB = fresh()
    seed_params(scopeB)
    guardB = TrainerGuard(exeB, mainB, scope=scopeB,
                          fetch_list=[lossB], checkpoint_dir=ck,
                          snapshot_every=1)
    _arm(f"preempt_at:step={PREEMPT_STEP}:site=executor")
    consumed = None
    try:
        with pytest.raises(PreemptedError) as ei:
            run(guardB, batches)
        consumed = ei.value.global_step
        assert ei.value.checkpoint_dir == ck
    finally:
        guardB.close()
        _disarm()
    # the executor's per-program counter is 0-based: step=4 fires
    # during the 5th batch, which completes before the checkpoint
    assert consumed == PREEMPT_STEP + 1
    assert TrainerGuard.has_checkpoint(ck)

    # --- resumed: fresh process state, restore, finish the stream ----
    mainC, lossC, scopeC, exeC = fresh()
    guardC = TrainerGuard(exeC, mainC, scope=scopeC,
                          fetch_list=[lossC], checkpoint_dir=ck,
                          install_sigterm=False)
    try:
        skip = guardC.resume(ck)
        assert skip == consumed
        lossesC = run(guardC, batches[skip:])
    finally:
        guardC.close()

    # bit-identical: losses after the preemption point and the final
    # parameters match the uninterrupted run exactly
    assert len(lossesC) == NB - consumed
    for got, want in zip(lossesC, lossesA[consumed:]):
        np.testing.assert_array_equal(got, want)
    for n in names:
        np.testing.assert_array_equal(scopeC.get_numpy(n),
                                      scopeA.get_numpy(n))


# ---------------------------------------------------------------------------
# atomic checkpoint writes (satellite: kill-mid-save)
# ---------------------------------------------------------------------------

def test_atomic_write_helpers_replace_not_append(tmp_path):
    from paddle_tpu.io import atomic_np_save, atomic_write_text
    p = str(tmp_path / "a.npy")
    atomic_np_save(p, np.arange(3))
    atomic_np_save(p, np.arange(4))
    assert np.load(p).shape == (4,)          # no .npy suffix doubling
    t = str(tmp_path / "s.json")
    atomic_write_text(t, "one")
    atomic_write_text(t, "two")
    assert open(t).read() == "two"
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


_KILL_MID_SAVE = """
import os, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import paddle_tpu as fluid
from paddle_tpu import layers

d = sys.argv[2]
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = layers.data("x", shape=[-1, 2], dtype="float32",
                    append_batch_size=False)
    layers.fc(x, size=2)
scope = fluid.Scope()
with fluid.scope_guard(scope):
    names = [v for v in main.list_vars()
             if v.persistable and not v.is_data]
    for v in names:
        scope.set(v.name, np.full([abs(s) for s in v.shape], 1.0,
                                  np.float32))
    fluid.io.save_persistables(None, d, main, filename="params.npz")
    for v in names:
        scope.set(v.name, np.full([abs(s) for s in v.shape], 2.0,
                                  np.float32))
    # die mid-save of v2: after the tmp file is written but before it
    # is fsynced/renamed over the v1 checkpoint
    os.fsync = lambda fd: os._exit(9)
    fluid.io.save_persistables(None, d, main, filename="params.npz")
os._exit(1)  # unreachable: the patched fsync must have killed us
"""


def test_kill_mid_save_leaves_previous_checkpoint_intact(tmp_path):
    repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    script = tmp_path / "kill_mid_save.py"
    script.write_text(textwrap.dedent(_KILL_MID_SAVE))
    d = str(tmp_path / "ckpt")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(script), repo, d],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 9, (p.stdout, p.stderr)
    blob = np.load(os.path.join(d, "params.npz"))
    assert blob.files
    for k in blob.files:   # v1 everywhere: the torn v2 never landed
        np.testing.assert_array_equal(blob[k],
                                      np.full(blob[k].shape, 1.0,
                                              np.float32))


# ---------------------------------------------------------------------------
# multiprocess reader worker death (satellite: SIGKILL a worker)
# ---------------------------------------------------------------------------

def _pid_then_hang_reader():
    """Module-level so the spawn context can pickle it by name."""
    yield os.getpid()
    time.sleep(300)
    yield -1


def test_multiprocess_reader_detects_sigkilled_worker():
    with _stats():
        gen = multiprocess_reader([_pid_then_hang_reader],
                                  queue_size=4, get_timeout_s=0.3)
        it = gen()
        pid = next(it)
        assert isinstance(pid, int) and pid != os.getpid()
        os.kill(pid, signal.SIGKILL)
        with pytest.raises(ReaderWorkerDied, match="exit code"):
            next(it)
        snap = monitor.get_stats_snapshot()
        assert snap["counters"].get("reader.worker_deaths") == 1


def test_multiprocess_reader_clean_end_of_stream():
    got = list(multiprocess_reader([_range_reader], queue_size=8,
                                   get_timeout_s=0.5)())
    assert sorted(got) == [0, 1, 2, 3]


def _range_reader():
    for i in range(4):
        yield i


# ---------------------------------------------------------------------------
# flight-recorder install idempotency (satellite: SIGTERM chaining)
# ---------------------------------------------------------------------------

def test_flight_recorder_double_install_single_dump(tmp_path,
                                                    monkeypatch):
    dumps = []
    monkeypatch.setattr(monitor, "dump_flight_recorder",
                        lambda path=None, reason="explicit":
                        dumps.append(reason) or str(path))
    prev_exc, prev_term = [], []

    def prev_hook(tp, val, tb):
        prev_exc.append(tp)

    def prev_handler(signum, frame):
        prev_term.append(signum)

    old_hook = sys.excepthook
    old_term = signal.getsignal(signal.SIGTERM)
    sys.excepthook = prev_hook
    signal.signal(signal.SIGTERM, prev_handler)
    try:
        # bench and monitor both install: second must REPLACE, not chain
        monitor.install_flight_recorder(str(tmp_path / "fr.jsonl"))
        monitor.install_flight_recorder(str(tmp_path / "fr.jsonl"))

        sys.excepthook(RuntimeError, RuntimeError("boom"), None)
        assert dumps.count("unhandled RuntimeError") == 1
        assert prev_exc == [RuntimeError]   # previous hook still ran

        signal.raise_signal(signal.SIGTERM)
        sigs = [r for r in dumps if r.startswith("signal")]
        assert sigs == [f"signal {int(signal.SIGTERM)}"]
        assert prev_term == [int(signal.SIGTERM)]  # chained handler ran
    finally:
        sys.excepthook = old_hook
        signal.signal(signal.SIGTERM, old_term)


# ---------------------------------------------------------------------------
# serving graceful degradation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("resilience_model"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[-1, -1, FEAT], dtype="float32",
                        append_batch_size=False)
        s = layers.reduce_sum(x, dim=1)
        pred = layers.fc(s, size=3, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
    return d


def _http(url, payload=None):
    try:
        if payload is None:
            req = urllib.request.Request(url)
        else:
            req = urllib.request.Request(
                url, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read() or b"{}"), \
                dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _x(batch=1, seq=4):
    return np.random.RandomState(0).randn(
        batch, seq, FEAT).astype(np.float32)


def test_serving_breaker_cycle_and_healthz(model_dir):
    """Acceptance: CLOSED -> OPEN -> HALF_OPEN -> CLOSED, observable
    through resilience.* stats and /healthz, driven by real threaded
    serving traffic."""
    stats_ctx = _stats()
    stats_ctx.__enter__()
    with _flags(serving_breaker_threshold=2,
                serving_breaker_cooldown_ms=400.0,
                retry_max_attempts=1):
        eng = ServingEngine(EngineConfig(
            model_dir, max_batch_size=2, seq_buckets=(4,),
            max_wait_us=1000, queue_capacity=16,
            default_timeout_ms=10000))
        srv = serve(eng, port=0)
    try:
        code, body, _ = _http(srv.url + "/healthz")
        assert code == 200 and body["state"] == "ok"
        out = eng.predict({"x": _x()})
        assert np.isfinite(out[0]).all()

        _arm("transient_fail:p=1.0:site=serving")
        for _ in range(2):           # threshold=2 consecutive failures
            with pytest.raises(RuntimeError):
                eng.predict({"x": _x()})
        assert eng.breaker.state == OPEN

        # shedding: direct submit AND the HTTP route answer 503 +
        # Retry-After while OPEN
        with pytest.raises(OverloadedError):
            eng.predict({"x": _x()})
        code, body, hdrs = _http(srv.url + "/v1/predict",
                                 {"inputs": {"x": _x().tolist()}})
        assert code == 503 and body["retryable"] is True
        assert int(hdrs["Retry-After"]) >= 1
        code, body, hdrs = _http(srv.url + "/healthz")
        assert code == 503 and body["state"] == "open"
        assert int(hdrs["Retry-After"]) >= 1
        snap = monitor.get_stats_snapshot()
        assert snap["counters"].get("resilience.breaker_opens") == 1
        assert snap["counters"].get("resilience.breaker_shed", 0) >= 2
        assert snap["counters"].get("resilience.fault_transient",
                                    0) >= 2
        assert snap["gauges"].get("resilience.breaker_state") == 2.0

        _disarm()
        time.sleep(0.45)             # cooldown -> HALF_OPEN (lazily)
        code, body, _ = _http(srv.url + "/healthz")
        assert code == 200 and body["state"] == "degraded"
        assert eng.breaker.state == HALF_OPEN

        out = eng.predict({"x": _x()})   # successful half-open probe
        assert np.isfinite(out[0]).all()
        assert eng.breaker.state == CLOSED
        code, body, _ = _http(srv.url + "/healthz")
        assert code == 200 and body["state"] == "ok"
        snap = monitor.get_stats_snapshot()
        assert snap["gauges"].get("resilience.breaker_state") == 0.0
    finally:
        srv.close()
        eng.stop()
        stats_ctx.__exit__(None, None, None)


def test_serving_nan_guard_retries_corrupted_batch(model_dir):
    """A step_nan corruption at the serving site is cured by the
    engine-level re-run: the client still gets a clean answer."""
    with _stats():
        eng = ServingEngine(EngineConfig(
            model_dir, max_batch_size=2, seq_buckets=(4,),
            max_wait_us=1000, queue_capacity=16,
            default_timeout_ms=10000))
        eng.start()
        try:
            want = eng.predict({"x": _x()})
            _arm("step_nan:at=1:site=serving")
            got = eng.predict({"x": _x()})
            _disarm()
            np.testing.assert_allclose(got[0], want[0],
                                       rtol=1e-5, atol=1e-6)
            snap = monitor.get_stats_snapshot()
            assert snap["counters"].get(
                "resilience.nan_batches_retried") == 1
            assert snap["counters"].get("resilience.fault_nan") == 1
        finally:
            eng.stop()


def test_healthz_warming_until_async_start_completes(model_dir):
    # slow the warmup compiles so the warming window is observable
    _arm("slow_step:ms=400:site=executor")
    eng = ServingEngine(EngineConfig(
        model_dir, max_batch_size=2, seq_buckets=(4,),
        max_wait_us=1000, queue_capacity=16,
        default_timeout_ms=10000))
    srv = serve(eng, port=0, async_start=True)
    try:
        code, body, _ = _http(srv.url + "/healthz")
        assert code == 503 and body["state"] == "warming"
        assert body["engines"]["predict"]["state"] == "warming"
        deadline = time.time() + 60
        while time.time() < deadline:
            code, body, _ = _http(srv.url + "/healthz")
            if code == 200:
                break
            assert code == 503 and body["state"] == "warming"
            time.sleep(0.05)
        assert code == 200 and body["state"] == "ok"
        _disarm()
        code, body, _ = _http(srv.url + "/v1/predict",
                              {"inputs": {"x": _x().tolist()}})
        assert code == 200 and "outputs" in body
    finally:
        _disarm()
        srv.close()
        eng.stop()


# ---------------------------------------------------------------------------
# generation: a failed decode step fails its requests, not the worker
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gen_setup():
    cfg = gpt.gpt_small(vocab_size=8, d_model=16, n_heads=2,
                        n_layers=1, d_ff=32, max_seq_len=8,
                        dropout=0.0, use_flash=False)
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        gpt.build_train(cfg, batch=2, seq_len=8, lr=1e-2)
        exe = fluid.Executor()
        exe.run(startup)
    return cfg, scope


def test_generation_step_failure_fails_requests_not_worker(gen_setup):
    cfg, scope = gen_setup
    with _stats():
        eng = GenerationEngine(cfg, scope, exe=fluid.Executor(),
                               max_slots=2, max_seq=8)
        eng.start()
        try:
            assert eng.health()["state"] == "ready"
            _arm("transient_fail:p=1.0:site=generation")
            r = eng.submit(GenerationRequest([1, 2], 3))
            # the paged-KV engine hits the injected fault on the
            # request's first step (prefill); the legacy path on decode
            with pytest.raises(RuntimeError,
                               match="(decode|prefill) step"):
                r.result(timeout=60.0)
            _disarm()
            # the worker survived: a clean request still completes
            out = eng.generate([1, 2], 3)
            assert len(out["tokens"]) == 3
            snap = monitor.get_stats_snapshot()
            assert snap["counters"].get(
                "resilience.gen_step_failures", 0) >= 1
        finally:
            eng.stop()
        assert eng.health()["state"] == "stopped"


# ---------------------------------------------------------------------------
# chaos loadgen acceptance harness
# ---------------------------------------------------------------------------

def _load_tool(name):
    tools = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "tools"))
    sys.path.insert(0, tools)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tools)


def test_chaos_loadgen_zero_wrong_answers_and_schema(tmp_path):
    """Acceptance: --chaos with injected transient+NaN faults completes
    with zero incorrect responses, zero worker deaths, and a bounded
    p99 inflation, all recorded in schema-valid JSONL."""
    lg = _load_tool("serving_loadgen")
    out = str(tmp_path / "chaos.jsonl")
    rc = lg.main(["--chaos", "--requests", "24", "--concurrency", "3",
                  "--fault-spec", "transient_fail:p=0.05,step_nan:p=0.01",
                  "--out", out])
    assert rc == 0

    vb = _load_tool("validate_bench_json")
    assert vb.validate_file(out) == []
    rec = [json.loads(ln) for ln in open(out)][-1]
    assert rec["kind"] == "chaos_loadgen"
    assert rec["wrong_answers"] == 0
    assert rec["worker_deaths"] == 0
    assert rec["p99_inflation"] is None or \
        rec["p99_inflation"] <= rec["p99_bound"]

    # the schema enforces the zero-incorrect-responses contract
    assert vb.validate_chaos_loadgen(dict(rec, wrong_answers=1), "x")
    assert vb.validate_chaos_loadgen(dict(rec, worker_deaths=2), "x")
    assert vb.validate_chaos_loadgen(
        dict(rec, p99_inflation=(rec["p99_bound"] or 50.0) + 1), "x")
