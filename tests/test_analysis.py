"""Static program verifier (paddle_tpu/analysis): rule fixtures, clean
passes over the bench model builders and the whole op registry, and the
FLAGS_program_verify pre-compile gate in Executor.run and
ServingEngine.warmup.

Rule catalog: docs/static_analysis.md.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import (Diagnostic, ProgramVerificationError,
                                 RULES, verify_program)
from paddle_tpu.analysis import verifier as verifier_mod
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.core.registry import REGISTRY
from paddle_tpu.framework import Operator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tools(module):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(module)
    finally:
        sys.path.pop(0)


def _rules(result):
    return {d.rule for d in result.findings}


def _raw_program(var_specs, op_specs):
    """Program from raw Operator appends (append_op would reject some
    fixtures at build time — the verifier must catch them statically)."""
    prog = fluid.Program()
    blk = prog.global_block()
    for name, kw in var_specs:
        blk.create_var(name=name, **kw)
    for op_type, ins, outs, attrs in op_specs:
        blk.ops.append(Operator(blk, op_type, ins, outs, attrs))
    return prog


_F32_23 = dict(shape=[2, 3], dtype="float32")


# ---------------------------------------------------------------------------
# one purpose-built bad program per lint rule
# ---------------------------------------------------------------------------

def test_ptv001_unregistered_op_with_suggestion():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("b", dict(**_F32_23))],
        [("reluu", {"X": ["a"]}, {"Out": ["b"]}, {})])
    res = verify_program(prog, check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV001"]
    assert hits and hits[0].severity == "error"
    assert "relu" in hits[0].message and "did you mean" in hits[0].message
    assert hits[0].where == "reluu:0/0"


def test_ptv002_op_version_mismatch():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("b", dict(**_F32_23))],
        [("relu", {"X": ["a"]}, {"Out": ["b"]}, {})])
    res = verify_program(prog, op_versions={"relu": 999},
                         check_shapes=False)
    assert "PTV002" in _rules(res)
    assert any(d.severity == "error" for d in res.findings
               if d.rule == "PTV002")


def test_ptv010_undefined_var():
    prog = _raw_program(
        [("b", dict(**_F32_23))],
        [("relu", {"X": ["ghost"]}, {"Out": ["b"]}, {})])
    res = verify_program(prog, check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV010"]
    assert hits and hits[0].var == "ghost"


def test_ptv011_use_before_def():
    # "b" is declared but neither data/persistable nor written first
    prog = _raw_program(
        [("b", dict(**_F32_23)), ("c", dict(**_F32_23))],
        [("relu", {"X": ["b"]}, {"Out": ["c"]}, {})])
    res = verify_program(prog, check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV011"]
    assert hits and hits[0].var == "b"


def test_ptv012_dead_op():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("b", dict(**_F32_23)),
         ("dead", dict(**_F32_23))],
        [("relu", {"X": ["a"]}, {"Out": ["b"]}, {}),
         ("tanh", {"X": ["a"]}, {"Out": ["dead"]}, {})])
    res = verify_program(prog, fetch_names=["b"], check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV012"]
    assert hits and hits[0].op_type == "tanh" \
        and hits[0].severity == "warn"
    # without a fetch list the reachability lint cannot run
    res2 = verify_program(prog, check_shapes=False)
    assert "PTV012" not in _rules(res2)


def test_ptv013_unused_multi_output():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("b", dict(**_F32_23)),
         ("mask", dict(**_F32_23))],
        [("dropout", {"X": ["a"]}, {"Out": ["b"], "Mask": ["mask"]},
          {"dropout_prob": 0.5})])
    res = verify_program(prog, fetch_names=["b"], check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV013"]
    assert hits and hits[0].var == "mask" and hits[0].severity == "warn"


def test_ptv014_write_after_write():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("c", dict(**_F32_23))],
        [("relu", {"X": ["a"]}, {"Out": ["c"]}, {}),
         ("tanh", {"X": ["a"]}, {"Out": ["c"]}, {})])
    res = verify_program(prog, fetch_names=["c"], check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV014"]
    assert hits and hits[0].var == "c" and hits[0].op_type == "tanh"


def test_ptv014_not_fired_when_read_between():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("c", dict(**_F32_23)),
         ("d", dict(**_F32_23))],
        [("relu", {"X": ["a"]}, {"Out": ["c"]}, {}),
         ("tanh", {"X": ["c"]}, {"Out": ["d"]}, {}),
         ("relu", {"X": ["a"]}, {"Out": ["c"]}, {})])
    res = verify_program(prog, check_shapes=False)
    assert "PTV014" not in _rules(res)


def test_ptv015_inplace_alias_read_after_update():
    prog = _raw_program(
        [("w", dict(persistable=True, **_F32_23)),
         ("g", dict(is_data=True, **_F32_23)),
         ("lr", dict(is_data=True, shape=[1], dtype="float32")),
         ("r", dict(**_F32_23))],
        [("sgd", {"Param": ["w"], "Grad": ["g"], "LearningRate": ["lr"]},
          {"ParamOut": ["w"]}, {}),
         ("relu", {"X": ["w"]}, {"Out": ["r"]}, {})])
    res = verify_program(prog, check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV015"]
    assert hits and hits[0].var == "w" and "sgd" in hits[0].message


def test_ptv020_shape_mismatch():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)),
         ("c", dict(shape=[9, 9], dtype="float32"))],
        [("relu", {"X": ["a"]}, {"Out": ["c"]}, {})])
    res = verify_program(prog)
    hits = [d for d in res.findings if d.rule == "PTV020"]
    assert hits and hits[0].severity == "error"
    assert "[2, 3]" in hits[0].message and "[9, 9]" in hits[0].message


def test_ptv021_dtype_mismatch():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)),
         ("c", dict(shape=[2, 3], dtype="int32"))],
        [("relu", {"X": ["a"]}, {"Out": ["c"]}, {})])
    res = verify_program(prog)
    hits = [d for d in res.findings if d.rule == "PTV021"]
    assert hits and "float32" in hits[0].message \
        and "int32" in hits[0].message


def _counting_eval(monkeypatch):
    from paddle_tpu.analysis import shape_infer
    calls = []
    inner = shape_infer._eval_op

    def counted(op, in_specs):
        calls.append(op.type)
        return inner(op, in_specs)

    monkeypatch.setattr(shape_infer, "_eval_op", counted)
    return calls


def test_shape_inference_evaluates_a_repeated_op_once(monkeypatch):
    """Ops of one program that differ in their variables' names only
    (a model's layers) are abstract-evaluated once; every one of them
    is still checked against its own declaration."""
    calls = _counting_eval(monkeypatch)
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)),
         ("b", dict(**_F32_23)), ("c", dict(**_F32_23)),
         ("d", dict(shape=[9, 9], dtype="float32")),
         ("e", dict(shape=[2, 3], dtype="float32"))],
        [("relu", {"X": ["a"]}, {"Out": ["b"]}, {}),
         ("relu", {"X": ["b"]}, {"Out": ["c"]}, {}),
         ("relu", {"X": ["c"]}, {"Out": ["d"]}, {}),       # PTV020
         ("scale", {"X": ["c"]}, {"Out": ["e"]}, {"scale": 2.0}),
         ("scale", {"X": ["e"]}, {"Out": ["e"]}, {"scale": 3.0})])
    res = verify_program(prog)
    assert calls == ["relu", "scale", "scale"]   # another attr: again
    hits = [d for d in res.findings if d.rule == "PTV020"]
    assert len(hits) == 1 and hits[0].var == "d" and hits[0].op_idx == 2


@pytest.mark.parametrize("attrs,evaluated", [
    ({"scale": 2.0}, 1), ({"scale": 2.0, "bias": np.float32(1.0)}, 1),
    ({"scale": 2.0, "note": np.zeros(3)}, 2)],
    ids=["plain", "numpy_scalar", "array_attr_is_not_memoised"])
def test_shape_inference_memo_key_takes_plain_attrs_only(
        monkeypatch, attrs, evaluated):
    calls = _counting_eval(monkeypatch)
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)),
         ("b", dict(**_F32_23)), ("c", dict(**_F32_23))],
        [("scale", {"X": ["a"]}, {"Out": ["b"]}, dict(attrs)),
         ("scale", {"X": ["b"]}, {"Out": ["c"]}, dict(attrs))])
    res = verify_program(prog)
    assert not [d for d in res.findings if d.rule.startswith("PTV02")]
    assert len(calls) == evaluated


def test_ptv022_abstract_eval_failure():
    opdef = REGISTRY.get("relu")
    assert opdef.abstract_eval is None

    def boom(op, in_specs, block):
        raise ValueError("synthetic abstract-eval failure")

    opdef.abstract_eval = boom
    try:
        prog = _raw_program(
            [("a", dict(is_data=True, **_F32_23)),
             ("c", dict(**_F32_23))],
            [("relu", {"X": ["a"]}, {"Out": ["c"]}, {})])
        res = verify_program(prog)
        hits = [d for d in res.findings if d.rule == "PTV022"]
        assert hits and hits[0].severity == "error"
        assert "synthetic abstract-eval failure" in hits[0].message
    finally:
        opdef.abstract_eval = None
        verifier_mod.reset_memo()


def test_ptv030_feed_not_in_program():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23))], [])
    res = verify_program(prog, feed_names=["nope"], check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV030"]
    assert hits and hits[0].var == "nope"


def test_ptv031_fetch_unreachable():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)),
         ("limbo", dict(**_F32_23))], [])
    res = verify_program(prog, fetch_names=["never_declared"],
                         check_shapes=False)
    assert any(d.rule == "PTV031" and d.var == "never_declared"
               for d in res.findings)
    # declared but never produced, not data/persistable, not fed
    res2 = verify_program(prog, fetch_names=["limbo"], check_shapes=False)
    assert any(d.rule == "PTV031" and d.var == "limbo"
               for d in res2.findings)
    # a data var is materialized by the feed path: no finding
    res3 = verify_program(prog, fetch_names=["a"], check_shapes=False)
    assert "PTV031" not in _rules(res3)


def test_ptv040_sub_block_inconsistency():
    prog = _raw_program(
        [("a", dict(is_data=True, **_F32_23)), ("b", dict(**_F32_23))],
        [("while", {"X": ["a"]}, {"Out": ["b"]},
          {"sub_block": 7, "output_vars": ["b"], "carried_vars": ["a"],
           "condition": "cond"})])
    res = verify_program(prog, check_shapes=False)
    hits = [d for d in res.findings if d.rule == "PTV040"]
    assert hits and hits[0].severity == "error" \
        and "sub_block" in hits[0].message


def test_diagnostic_provenance_and_serialization():
    d = Diagnostic(rule="PTV020", message="m", op_type="relu",
                   block=1, op_idx=4, var="x")
    assert d.where == "relu:1/4"
    rec = d.to_dict()
    assert rec["rule"] == "PTV020" and rec["where"] == "relu:1/4" \
        and rec["severity"] == RULES["PTV020"][0]
    assert Diagnostic(rule="PTV030", message="m").where == "program"


# ---------------------------------------------------------------------------
# known-good programs must verify clean
# ---------------------------------------------------------------------------

def test_bench_model_builders_verify_clean():
    """Every tiny bench builder (the models bench.py certifies on CPU)
    produces a program with ZERO error-severity findings."""
    sys.path.insert(0, REPO)
    os.environ.setdefault("BENCH_FLASH", "0")
    import bench
    for model, build in bench._CPU_TINY_BUILDS.items():
        exe, prog, scope, feed, loss, cfg = build()
        res = verify_program(prog, feed_names=list(feed),
                             fetch_names=[loss.name])
        errs = res.errors()
        assert not errs, (
            f"{model}: {len(errs)} error finding(s): "
            + "; ".join(f"{d.rule} {d.where}: {d.message}"
                        for d in errs[:5]))


def test_registry_wide_op_sweep_verifies_clean():
    """One-op programs for every op the committed OP_TEST_MATRIX.json
    certifies as passing: the abstract-evaluation pass must run the
    registered lowering under jax.eval_shape without error findings."""
    from op_specs import SKIPS, SPECS
    import test_op_sweep as sweep

    matrix = json.load(open(os.path.join(REPO, "OP_TEST_MATRIX.json")))
    ops = [op for op, rec in matrix["ops"].items()
           if rec.get("status") == "pass"
           and op in SPECS and op not in SKIPS]
    assert len(ops) > 250, f"matrix shrank unexpectedly: {len(ops)}"
    bad = {}
    for op in ops:
        main, feeds, out_map, _direct, _ = sweep._build_program(
            op, SPECS[op])
        fetch = [nm for names in out_map.values() for nm in names]
        res = verify_program(main, feed_names=list(feeds),
                             fetch_names=fetch)
        if res.errors():
            bad[op] = [f"{d.rule} {d.message[:120]}"
                       for d in res.errors()[:3]]
    assert not bad, f"{len(bad)} op(s) with verifier errors: {bad}"


# ---------------------------------------------------------------------------
# the pre-compile gate
# ---------------------------------------------------------------------------

def _bad_training_program():
    """Feedable program whose compile would crash (undefined input):
    error mode must reject it BEFORE any executable is built."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
    blk = main.global_block()
    blk.create_var(name="y", shape=[-1, 4], dtype="float32")
    blk.ops.append(Operator(blk, "relu", {"X": ["ghost"]},
                            {"Out": ["y"]}))
    return main


def test_executor_gate_error_mode_raises_before_compile():
    verifier_mod.reset_memo()
    fluid.set_flags({"FLAGS_program_verify": "error"})
    try:
        main = _bad_training_program()
        exe = fluid.Executor()
        with fluid.scope_guard(fluid.Scope()):
            with pytest.raises(ProgramVerificationError) as ei:
                exe.run(main, feed={"x": np.ones((2, 4), np.float32)},
                        fetch_list=["y"])
        msg = str(ei.value)
        assert "PTV010" in msg and "relu:0/" in msg \
            and "FLAGS_program_verify" in msg
        stats = exe.cache_stats()
        assert stats["misses"] == 0 and stats["size"] == 0, stats
    finally:
        fluid.set_flags({"FLAGS_program_verify": "warn"})
        verifier_mod.reset_memo()


def test_executor_gate_warn_mode_warns_once_then_memoizes():
    verifier_mod.reset_memo()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[3], dtype="float32")
    blk = main.global_block()
    blk.create_var(name="c", shape=[-1, 3], dtype="float32")
    # WAW: first write never read -> one PTV014 warn finding, but the
    # program still executes fine
    blk.ops.append(Operator(blk, "relu", {"X": [x.name]}, {"Out": ["c"]}))
    blk.ops.append(Operator(blk, "tanh", {"X": [x.name]}, {"Out": ["c"]}))
    exe = fluid.Executor()
    feed = {"x": np.ones((2, 3), np.float32)}
    with fluid.scope_guard(fluid.Scope()):
        with pytest.warns(UserWarning, match="PTV014"):
            out1 = exe.run(main, feed=feed, fetch_list=["c"])
        # memoized: the second identical run must NOT warn again
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            out2 = exe.run(main, feed=feed, fetch_list=["c"])
        assert not [w for w in rec if "PTV" in str(w.message)], \
            [str(w.message) for w in rec]
    np.testing.assert_allclose(out1[0], np.tanh(feed["x"]), rtol=1e-6)
    np.testing.assert_allclose(out2[0], out1[0])
    verifier_mod.reset_memo()


def test_gate_off_mode_skips_and_bad_flag_value_raises():
    verifier_mod.reset_memo()
    from paddle_tpu.analysis import verify_gate
    main = _bad_training_program()
    fluid.set_flags({"FLAGS_program_verify": "off"})
    try:
        assert verify_gate(main) is None
        fluid.set_flags({"FLAGS_program_verify": "everything"})
        with pytest.raises(ValueError, match="program_verify"):
            verify_gate(main)
    finally:
        fluid.set_flags({"FLAGS_program_verify": "warn"})
        verifier_mod.reset_memo()


def test_serving_warmup_gate_rejects_corrupt_model(tmp_path):
    """A saved model corrupted on disk is rejected by the warmup gate in
    error mode — before a single ladder-cell compile is spent."""
    from paddle_tpu import io
    from paddle_tpu.serving import EngineConfig, ServingEngine

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[6], dtype="float32")
        out = layers.fc(x, size=3, act="relu")
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        mdir = str(tmp_path / "model")
        io.save_inference_model(mdir, ["x"], [out], exe,
                                main_program=main)
    # corrupt: an op reading a var that exists nowhere
    mpath = os.path.join(mdir, "__model__.json")
    with open(mpath) as f:
        meta = json.load(f)
    meta["program"]["blocks"][0]["ops"].insert(
        0, {"type": "relu", "inputs": {"X": ["ghost"]},
            "outputs": {"Out": [out.name]}, "attrs": {}, "id": 999})
    with open(mpath, "w") as f:
        json.dump(meta, f)

    verifier_mod.reset_memo()
    fluid.set_flags({"FLAGS_program_verify": "error"})
    try:
        engine = ServingEngine(EngineConfig(model_dir=mdir,
                                            max_batch_size=2))
        with pytest.raises(ProgramVerificationError, match="PTV010"):
            engine.start()
        stats = engine.cache_stats()
        assert stats["misses"] == 0, stats
    finally:
        fluid.set_flags({"FLAGS_program_verify": "warn"})
        verifier_mod.reset_memo()


# ---------------------------------------------------------------------------
# satellite: feed rank validation + registry suggestions
# ---------------------------------------------------------------------------

def test_feed_rank_mismatch_diagnostic():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[2, 3], dtype="float32")
        y = layers.relu(x)
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        with pytest.raises(ValueError) as ei:
            exe.run(main, feed={"x": np.ones((6,), np.float32)},
                    fetch_list=[y.name])
    msg = str(ei.value)
    assert "'x'" in msg and "rank 1" in msg and "rank 3" in msg, msg


def test_registry_get_suggests_and_carries_provenance():
    with pytest.raises(NotImplementedError) as ei:
        REGISTRY.get("reluu")
    assert "did you mean" in str(ei.value) and "'relu'" in str(ei.value)
    with pytest.raises(NotImplementedError) as ei2:
        REGISTRY.get("reluu", where="2/17")
    assert "at block/op 2/17" in str(ei2.value)


# ---------------------------------------------------------------------------
# satellite: CLI + artifact schema + report rendering
# ---------------------------------------------------------------------------

def test_program_lint_self_check_exits_zero():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         "--self-check"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "self-check ok" in r.stdout


def test_program_lint_cli_end_to_end(tmp_path):
    from paddle_tpu import io

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[6], dtype="float32")
        out = layers.fc(x, size=3, act="relu")
    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        good = str(tmp_path / "good")
        io.save_inference_model(good, ["x"], [out], exe,
                                main_program=main)
    bad = str(tmp_path / "bad")
    os.makedirs(bad)
    with open(os.path.join(good, "__model__.json")) as f:
        meta = json.load(f)
    meta["program"]["blocks"][0]["ops"].append(
        {"type": "reluu", "inputs": {"X": ["x"]},
         "outputs": {"Out": ["x"]}, "attrs": {}, "id": 999})
    with open(os.path.join(bad, "__model__.json"), "w") as f:
        json.dump(meta, f)

    log = str(tmp_path / "lint.jsonl")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "program_lint.py"),
         good, bad, "--jsonl", "--out", log],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 1, r.stdout + r.stderr  # bad model -> findings
    recs = [json.loads(l) for l in r.stdout.splitlines() if l.strip()]
    assert len(recs) == 2
    assert recs[0]["ok"] and recs[0]["counts"]["error"] == 0
    assert not recs[1]["ok"] and recs[1]["counts"]["error"] >= 1
    assert any(f["rule"] == "PTV001" for f in recs[1]["findings"])

    # the appended JSONL satisfies the artifact schema ...
    assert _tools("validate_bench_json").validate_file(log) == []
    # ... and metrics_report renders a lint section from it
    import io as pyio
    metrics_report = _tools("metrics_report")
    buf = pyio.StringIO()
    rc = metrics_report.report(log, out=buf)
    text = buf.getvalue()
    assert rc == 0 and "program lint" in text and "PTV001" in text


def test_validate_program_lint_schema():
    validate_program_lint = _tools("validate_bench_json") \
        .validate_program_lint
    good = {"kind": "program_lint", "model": "m", "ok": True,
            "counts": {"error": 0, "warn": 1},
            "findings": [{"rule": "PTV013", "severity": "warn",
                          "where": "dropout:0/3", "message": "x"}]}
    assert validate_program_lint(good) == []
    bad = dict(good, ok=True, counts={"error": 2, "warn": 0})
    errs = validate_program_lint(bad)
    assert errs and any("contradicts" in e for e in errs)
    assert validate_program_lint({"kind": "program_lint"})  # all missing
