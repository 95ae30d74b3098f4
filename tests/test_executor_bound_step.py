"""Executor.run's bound step (executor._BoundStep): the first call of a
signature keeps what it resolved to, every later call of that signature
stages its feeds, hands over the written state and runs. These tests
hold the bound path to the path of before (`_resolve_step` every call,
forced through the private `_bind_steps`), to its invalidation rules,
and to what the benchmark reads of a call."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, trace
from paddle_tpu.models import gpt
from paddle_tpu.serving import GenerationEngine, GenerationRequest


def _bound(exe):
    s = exe.cache_stats()
    return (s["bound_step_hits"], s["bound_step_binds"],
            s["bound_step_rebinds"], s["misses"])


def _scope_arrays(scope):
    return {n: np.asarray(scope.find_var(n)) for n in sorted(scope.names())
            if scope.find_var(n) is not None}


# ---------------------------------------------------------------------------
# (a) bit-identical to the path of before
# ---------------------------------------------------------------------------

def _train_program(dropout=0.3):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        h = layers.fc(x, size=16, act="relu")
        if dropout:
            h = layers.dropout(h, dropout_prob=dropout)
        pred = layers.fc(h, size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _train_run(bind, steps=50):
    main, startup, loss = _train_program()
    scope, exe = fluid.Scope(), fluid.Executor()
    exe._bind_steps = bind
    rng = np.random.default_rng(0)
    losses = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(steps):
            feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
                    "y": rng.standard_normal((4, 1)).astype(np.float32)}
            losses.append(exe.run(main, feed=feed, fetch_list=[loss])[0])
    return losses, _scope_arrays(scope), exe


def test_training_with_dropout_is_bit_identical_to_the_unbound_path():
    """50 steps with dropout: the step counter goes in as a host uint32
    and fold_in must see the sequence the jitted jnp.uint32 gave."""
    got, got_scope, exe = _train_run(bind=True)
    want, want_scope, slow = _train_run(bind=False)
    assert _bound(slow)[:3] == (0, 0, 0)
    # startup and main bind on their first call; 49 bound steps follow
    assert _bound(exe) == (49, 2, 0, 2)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert len({float(np.ravel(v)[0]) for v in got}) > 40  # it trains
    assert got_scope.keys() == want_scope.keys()
    for n in got_scope:
        np.testing.assert_array_equal(got_scope[n], want_scope[n], n)


def _tiny_engine(exe=None, seed=3):
    cfg = gpt.gpt_small(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_seq_len=128, dropout=0.0)
    scope = fluid.Scope()
    with fluid.unique_name.guard():
        eng = GenerationEngine(cfg, scope, exe=exe or fluid.Executor(),
                               max_slots=4, max_seq=128)
    rng = np.random.default_rng(seed)
    blk = eng._prog.global_block()
    for p in blk.all_parameters():
        shape = tuple(abs(int(s)) for s in p.shape)
        scope.set(p.name, (rng.standard_normal(shape) * 0.2)
                  .astype(np.float32))
    gpt._ensure_decode_state(scope, blk, eng.step.cache_names
                             + eng.step.state_names)
    return eng, scope


def _engine_steps(bind, steps=50):
    """Drive the paged engine's two executables by hand: a chunk
    prefill of 16 tokens a row, then decode steps, each row on pages of
    its own."""
    eng, scope = _tiny_engine()
    eng.exe._bind_steps = bind
    B, bs = eng.max_slots, eng.block_size
    mb = eng.step.max_blocks_per_slot
    table = np.zeros((B, mb), np.int64)
    for i in range(B):
        table[i] = 1 + i * mb + np.arange(mb)
    rng = np.random.default_rng(1)
    outs = []

    def run(prog, step, tokens, start, nvalid):
        out = eng.exe.run(
            prog, feed={step.token_var.name: tokens,
                        step.table_var.name: table,
                        step.start_var.name: start,
                        step.nvalid_var.name: nvalid},
            fetch_list=step.fetch_vars, scope=scope)
        outs.append(out[0])

    run(eng._prefill_prog, eng.prefill_step,
        rng.integers(0, 64, (B, bs)).astype(np.int64),
        np.zeros(B, np.int64), np.full(B, bs, np.int64))
    for t in range(steps):
        if t == 20:   # a second prefill step between decode steps
            run(eng._prefill_prog, eng.prefill_step,
                np.zeros((B, bs), np.int64), np.zeros(B, np.int64),
                np.zeros(B, np.int64))
        run(eng._prog, eng.step,
            rng.integers(0, 64, (B, 1)).astype(np.int64),
            np.full(B, bs + t, np.int64), np.ones(B, np.int64))
    return outs, _scope_arrays(scope), eng.exe


def test_paged_decode_is_bit_identical_to_the_unbound_path():
    got, got_scope, exe = _engine_steps(bind=True)
    want, want_scope, _ = _engine_steps(bind=False)
    hits, binds, rebinds, misses = _bound(exe)
    assert (binds, rebinds, misses) == (2, 0, 2)
    assert hits == len(got) - 2
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert np.abs(got[-1]).max() > 0
    for n in got_scope:
        np.testing.assert_array_equal(got_scope[n], want_scope[n], n)


# ---------------------------------------------------------------------------
# (b) what unbinds a step, a cause a case
# ---------------------------------------------------------------------------

def _fc_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = layers.data("x", shape=[4], dtype="float32")
        idx = layers.data("idx", shape=[1], dtype="int64")
        y = layers.fc(x, size=3)
        z = layers.scale(y, scale=2.0)
        k = layers.cast(idx, "float32")
    return main, startup, y, z, k


def _op_appended(c):
    with fluid.program_guard(c["main"], c["startup"]):
        c["fetch"] = [layers.scale(c["y"], scale=3.0)]
    c["want"] = lambda y, z, k: 3.0 * y


def _flag(name, value):
    def cause(c):
        c["restore"] = fluid.get_flags([name])
        fluid.set_flags({name: value})
    return cause


def _feed_shape(c):
    c["feed"] = {"x": np.ones((5, 4), np.float32),
                 "idx": np.ones((5, 1), np.int64)}


def _feed_dtype(c):
    # the same logical batch, device-resident: int64 numpy -> int32 Array
    c["feed"] = {"x": c["feed"]["x"],
                 "idx": jnp.asarray(c["feed"]["idx"], jnp.int32)}


def _fetch_list(c):
    c["fetch"] = [c["z"]]
    c["want"] = lambda y, z, k: z


def _other_scope(c):
    new = fluid.Scope()
    for n in c["scope"].names():
        v = c["scope"].find_var(n)
        new.set(n, None if v is None else np.asarray(v) * 2.0)
    c["scope"] = new
    c["want"] = lambda y, z, k: 2.0 * y


def _compiled_program(c):
    c["run"] = fluid.CompiledProgram(c["main"])


@pytest.mark.parametrize("cause", [
    _op_appended,
    _flag("FLAGS_check_nan_inf", True),          # a traced flag
    _flag("FLAGS_program_verify", "off"),        # a gate's flag
    _feed_shape, _feed_dtype, _fetch_list, _other_scope,
    _compiled_program,
], ids=["op_appended", "traced_flag", "program_verify_flag", "feed_shape",
        "feed_dtype", "fetch_list", "other_scope", "compiled_program"])
def test_what_unbinds_a_step(cause):
    main, startup, y, z, k = _fc_program()
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    c = {"main": main, "startup": startup, "scope": scope, "run": main,
         "y": y, "z": z, "k": k, "fetch": [y],
         "feed": {"x": np.ones((2, 4), np.float32),
                  "idx": np.ones((2, 1), np.int64)},
         "want": lambda y, z, k: y, "restore": None}

    def run():
        return exe.run(c["run"], feed=c["feed"], fetch_list=c["fetch"],
                       scope=c["scope"])[0]

    base = run()                       # resolves and binds
    np.testing.assert_array_equal(run(), base)
    hits0 = _bound(exe)[0]
    assert hits0 == 1
    try:
        cause(c)
        got = run()                    # must NOT be a bound call
        assert _bound(exe)[0] == hits0, "the stale binding ran"
        w = np.asarray(scope.find_var(
            [p.name for p in main.all_parameters()
             if len(p.shape) == 2][0]))
        b = np.asarray(scope.find_var(
            [p.name for p in main.all_parameters()
             if len(p.shape) == 1][0]))
        ref_y = np.asarray(c["feed"]["x"]) @ w + b
        want = c["want"](ref_y, 2.0 * ref_y, None)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # and the new signature binds in its turn
        np.testing.assert_array_equal(run(), got)
        assert _bound(exe)[0] == hits0 + 1
    finally:
        if c["restore"]:
            fluid.set_flags(c["restore"])


# ---------------------------------------------------------------------------
# (c) the scope's side: pinned weights, written state
# ---------------------------------------------------------------------------

def test_a_weight_set_between_two_runs_is_what_the_next_run_computes_with():
    main, startup, y, _, _ = _fc_program()
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    feed = {"x": np.arange(8, dtype=np.float32).reshape(2, 4),
            "idx": np.ones((2, 1), np.int64)}
    wname = [p.name for p in main.all_parameters() if len(p.shape) == 2][0]
    bname = [p.name for p in main.all_parameters() if len(p.shape) == 1][0]
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    before = _bound(exe)
    assert before[2] == 0
    w = np.full((4, 3), 0.5, np.float32)
    scope.set(wname, w)                      # a host array, as io.load_* sets
    got, = exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    np.testing.assert_allclose(
        got, feed["x"] @ w + np.asarray(scope.find_var(bname)), rtol=1e-6)
    hits, binds, rebinds, misses = _bound(exe)
    assert (hits, binds, rebinds, misses) == (
        before[0] + 1, before[1], 1, before[3])
    # the view holds again: no further rebind
    exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    assert _bound(exe)[2] == 1
    # io.load_persistables goes through scope.set too
    import tempfile
    with tempfile.TemporaryDirectory() as d, fluid.scope_guard(scope):
        fluid.io.save_persistables(exe, d, main)
        scope.set(wname, np.zeros((4, 3), np.float32))
        exe.run(main, feed=feed, fetch_list=[y])
        fluid.io.load_persistables(exe, d, main)
        back, = exe.run(main, feed=feed, fetch_list=[y])
    np.testing.assert_array_equal(back, got)


def test_a_test_program_sees_the_weights_of_the_train_step_before_it():
    """Two programs on one scope: the train step's own write-back of a
    weight that the test program's binding holds pinned must move it."""
    main, startup, loss = _train_program(dropout=0.0)
    test = main.clone(for_test=True)
    scope, exe = fluid.Scope(), fluid.Executor()
    probe = fluid.Executor()
    probe._bind_steps = False
    rng = np.random.default_rng(2)
    feed = {"x": rng.standard_normal((4, 8)).astype(np.float32),
            "y": rng.standard_normal((4, 1)).astype(np.float32)}
    seen = []
    with fluid.scope_guard(scope):
        exe.run(startup)
        for _ in range(6):
            exe.run(main, feed=feed, fetch_list=[loss])
            got, = exe.run(test, feed=feed, fetch_list=[loss])
            want, = probe.run(test, feed=feed, fetch_list=[loss])
            np.testing.assert_array_equal(got, want)
            seen.append(float(got))
    assert len(set(seen)) == 6 and seen[-1] < seen[0]
    hits, binds, rebinds, misses = _bound(exe)
    # the test program's binding gathered its weights again after every
    # train step but the first (its first call bound it)
    assert rebinds == 5 and misses == 3 and binds == 3


def test_prefill_and_decode_alternating_on_one_scope_never_rebind():
    """Both executables write the KV pools back every call; neither
    holds them pinned, so neither costs the other anything."""
    eng, scope = _tiny_engine()
    cells = [(p, f, eng.fetch_list(p)) for _, p, f, _ in eng.executables()]
    for _ in range(10):
        for prog, feed, fetch in cells:
            eng.exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    hits, binds, rebinds, misses = _bound(eng.exe)
    assert (hits, binds, rebinds, misses) == (18, 2, 0, 2)
    pinned = set(scope._pinned)
    assert pinned and not pinned & set(eng.step.cache_names)


def test_a_deleted_state_variable_raises_not_initialised():
    eng, scope = _tiny_engine()
    prog, feed, fetch = [(p, f, eng.fetch_list(p))
                         for _, p, f, _ in eng.executables()][0]
    for _ in range(2):
        eng.exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    assert _bound(eng.exe)[0] == 1
    weight = prog.global_block().all_parameters()[0].name
    scope.delete(weight)                        # a pinned name
    with pytest.raises(RuntimeError, match="not initialised"):
        eng.exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    scope.set(weight, np.zeros(
        [abs(int(s)) for s in prog.global_block().var(weight).shape],
        np.float32))
    eng.exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    scope.delete(eng.step.cache_names[0])       # a written (donated) name
    with pytest.raises(RuntimeError, match="not initialised"):
        eng.exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)


def test_a_scope_with_a_parent_is_never_bound():
    main, startup, y, _, _ = _fc_program()
    parent, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=parent)
    kid = parent.new_scope()
    feed = {"x": np.ones((2, 4), np.float32),
            "idx": np.ones((2, 1), np.int64)}
    first, = exe.run(main, feed=feed, fetch_list=[y], scope=kid)
    wname = [p.name for p in main.all_parameters() if len(p.shape) == 2][0]
    parent.set(wname, np.zeros((4, 3), np.float32))
    kid.delete(wname)       # the step's write-back had shadowed it
    second, = exe.run(main, feed=feed, fetch_list=[y], scope=kid)
    assert _bound(exe)[0] == 0
    assert np.abs(first - second).max() > 0


# ---------------------------------------------------------------------------
# (d) the counters in a serving window
# ---------------------------------------------------------------------------

def test_after_start_every_call_of_the_engine_is_a_bound_hit():
    eng, _ = _tiny_engine()
    eng.start()
    try:
        s0 = eng.cache_stats()
        resps = [eng.submit(GenerationRequest(list(range(1, 3 + 5 * i)), 6))
                 for i in range(5)]
        for r in resps:
            assert len(r.result(timeout=60.0)["tokens"]) == 6
        s1 = eng.cache_stats()
    finally:
        eng.stop()
    calls = s1["hits"] - s0["hits"]
    assert calls > 10
    assert s1["bound_step_hits"] - s0["bound_step_hits"] == calls
    assert s1["bound_step_rebinds"] == 0
    assert s1["misses"] == s0["misses"] == 2
    assert eng.post_warmup_compiles() == 0


# ---------------------------------------------------------------------------
# (e) what the benchmark reads of a call
# ---------------------------------------------------------------------------

def test_the_jitted_step_is_still_named_jit_step():
    main, startup, y, _, _ = _fc_program()
    train, tstart, loss = _train_program()
    for prog, start, feed, fetch, level in (
            (main, startup, {"x": np.ones((2, 4), np.float32),
                             "idx": np.ones((2, 1), np.int64)}, y, 1),
            (train, tstart, {"x": np.ones((2, 8), np.float32),
                             "y": np.ones((2, 1), np.float32)}, loss, 2)):
        prev = fluid.get_flags(["FLAGS_graph_opt_level"])
        fluid.set_flags({"FLAGS_graph_opt_level": level})
        try:
            scope, exe = fluid.Scope(), fluid.Executor()
            exe.run(start, scope=scope)
            text = exe.lowered_stablehlo(prog, feed=feed,
                                         fetch_list=[fetch], scope=scope)
        finally:
            fluid.set_flags(prev)
        assert "module @jit_step" in text.splitlines()[0], \
            text.splitlines()[0]


def test_a_bound_call_keeps_its_regions_and_timings_and_runs_one_jit(
        tmp_path):
    main, startup, y, _, _ = _fc_program()
    scope, exe = fluid.Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4), np.float32),
            "idx": np.ones((2, 1), np.int64)}
    for _ in range(2):
        exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    hits = _bound(exe)[0]

    rec = trace.begin_iteration(1, 1, 0)
    exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    trace.end_iteration(rec, keep=False)
    assert _bound(exe)[0] == hits + 1
    assert {"executor.resolve", "executor.feed", "executor.dispatch",
            "executor.fetch"} <= set(rec.host_s)
    assert "executor.compile" not in rec.host_s
    lt = exe.last_step_timings
    assert set(lt) == {"feed_s", "dispatch_s", "fetch_s", "total_s"}
    assert lt["total_s"] >= lt["dispatch_s"] + lt["fetch_s"] > 0
    assert 0 < lt["feed_s"] < lt["total_s"]

    # the profiler's host plane names every jitted call of the thread:
    # a bound call makes one, `step`, and no convert_element_type (the
    # jitted jnp.uint32(step) of before)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[y], scope=scope)
    finally:
        jax.profiler.stop_trace()
    assert _bound(exe)[0] == hits + 4
    pb, = glob.glob(os.path.join(str(tmp_path),
                                 "plugins/profile/*/*.xplane.pb"))
    names = [ev.name
             for plane in jax.profiler.ProfileData.from_file(pb).planes
             for line in plane.lines for ev in line.events]
    jitted = [n for n in names if n.startswith("PjitFunction(")]
    assert jitted.count("PjitFunction(step)") >= 3, sorted(set(jitted))
    assert not [n for n in names if "convert_element_type" in n]


# ---------------------------------------------------------------------------
# (f) a parameter-server program is never bound
# ---------------------------------------------------------------------------

def test_a_listen_and_serv_program_is_never_bound(monkeypatch):
    from paddle_tpu.distributed import ps_server
    served = []
    monkeypatch.setattr(ps_server, "run_pserver",
                        lambda program, scope=None: served.append(program))
    prog = fluid.Program()
    prog.global_block().append_op("listen_and_serv", infer_shape=False)
    exe = fluid.Executor()
    for _ in range(3):
        assert exe.run(prog, scope=fluid.Scope()) == []
    assert served == [prog] * 3
    assert _bound(exe) == (0, 0, 0, 0) and not exe._bound
