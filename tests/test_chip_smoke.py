"""chip_smoke.py and the rules it rests on, checked on the CPU: the
rehearsal walks the same code at tiny sizes, a bare run without a chip
fails, and nothing on that path falls back quietly (the peak table, the
compile-cache rule, default_place, the memory gate, Pallas interpret
mode, the tile sweep). The full-size run is the chip's: see PERF.md.
"""
import json
import os
import sys
import warnings

import jax
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402


@pytest.fixture
def cache_dir_config():
    """Whatever a test does to jax's cache directory is undone: the rest
    of the suite runs without a persistent cache."""
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _rehearse(phases, tmp_path, monkeypatch, capsys):
    # with the variable set the helper sets nothing in code, so the
    # rehearsal leaves this process's jax config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = chip_smoke.main(["--rehearsal", "--phases", phases])
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.splitlines() if ln.startswith("{")]
    assert rc == 0
    want = phases.split(",")
    assert [ln["phase"] for ln in lines[:-1]] == want
    for ln in lines[:-1]:
        assert ln["ok"] is True and ln["platform"] == "cpu" \
            and ln["rehearsal"] is True
        assert ln["compile_cache_dir"] == str(tmp_path)
        for key in ("device_kind", "device_count", "jax", "jaxlib",
                    "libtpu", "compile_s", "run_s"):
            assert key in ln, (ln["phase"], key)
    # the last line is the rehearsal's own, never the chip's result line
    assert lines[-1] == {"rehearsal_passed": True, "phases": want,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": len(jax.devices())}}
    return {ln["phase"]: ln for ln in lines[:-1]}


def test_rehearsal_trainer_and_server(tmp_path, monkeypatch, capsys):
    """The tiny CPU walk through the trainer and the server passes
    in-process (flash and multichip: the slow test below)."""
    out = _rehearse("device,train,serve", tmp_path, monkeypatch, capsys)
    train = out["train"]
    assert train["losses"][-1] < train["losses"][0]
    assert train["compiles"]["executor_first_step"] == 1
    assert train["compiles"]["jax_later_steps"] == 0
    serve = out["serve"]
    assert serve["post_warmup_compiles"] == 0
    assert serve["requests_equal_to_serial"] + serve["near_tie_flips"] \
        == serve["requests"]
    for exe in train["executables"] + serve["executables"]:
        assert exe["est_peak_bytes"] > 0 and exe["xla_temp_bytes"] >= 0
        assert exe["peak_bytes_in_use"] is None  # the CPU reports none


@pytest.mark.slow
def test_rehearsal_all_phases(tmp_path, monkeypatch, capsys):
    """Every phase, the eight virtual devices standing in for four
    chips: mesh "8" and "4,2"."""
    out = _rehearse(",".join(chip_smoke.PHASES), tmp_path, monkeypatch,
                    capsys)
    assert all(c["interpret"] for c in out["flash"]["cases"])
    meshes = out["multichip"]["meshes"]
    assert [m["mesh"] for m in meshes] == ["8", "4,2"]
    assert all(m["collectives"]["all-reduce"] > 0 for m in meshes)


def test_bare_invocation_fails_without_a_chip(monkeypatch, capsys):
    assert chip_smoke.main([]) == 2
    monkeypatch.delenv("BENCH_PLATFORM", raising=False)
    assert bench.main([]) == 2
    assert capsys.readouterr().out == ""  # no result of any kind


def test_unknown_device_kind_raises_from_the_peak_table():
    assert bench.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert bench.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peak"):
        bench.device_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError, match="'cpu'"):
        bench.peak_flops_per_chip()  # the local device is a CPU here


def test_compile_cache_rule(tmp_path, monkeypatch, cache_dir_config):
    from paddle_tpu.core import compile_cache
    from paddle_tpu.ops.pallas import autotune
    jax.config.update("jax_compilation_cache_dir", None)
    # set from outside: the code sets no directory of its own
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
    assert autotune.default_cache_path() == \
        str(tmp_path / "flash_autotune.json")
    # not set: <checkout>/.jax_cache, a fixed path
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert autotune.default_cache_path() == \
        os.path.join(want, "flash_autotune.json")


def test_default_place_does_not_swallow(monkeypatch):
    from paddle_tpu.core import place

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(place.jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        place.default_place()


def test_memory_gate_verdict_ignores_a_reported_bytes_limit(monkeypatch):
    """Default flags: the same program passes the gate whether or not
    the backend reports a bytes_limit it does not fit; only an explicit
    budget refuses it."""
    from paddle_tpu.analysis import ProgramVerificationError, memory_gate
    from paddle_tpu.analysis import memory as memory_mod
    from paddle_tpu.core import memory as core_memory
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[256], dtype="float32")
        y = layers.relu(layers.scale(x, scale=2.0))
    shapes = {"x": ((64, 256), "float32")}
    assert fluid.get_flags(["FLAGS_memory_gate"]) == \
        {"FLAGS_memory_gate": "error"}
    memory_mod.reset_memo()
    try:
        verdicts = []
        for stats in ({}, {"bytes_limit": 4096}):
            monkeypatch.setattr(core_memory, "device_memory_stats",
                                lambda device=None, _s=stats: _s)
            with warnings.catch_warnings(record=True) as rec:
                warnings.simplefilter("always")
                plan = memory_gate(main, feed_shapes=shapes,
                                   fetch_names=[y.name], where="test")
            verdicts.append(plan.peak_bytes)
            warned = any("PTV050" in str(w.message) for w in rec)
            assert warned == bool(stats)  # over the limit: said, not raised
        assert verdicts[0] == verdicts[1] > 4096
        fluid.set_flags({"FLAGS_memory_budget_bytes": 4096})
        with pytest.raises(ProgramVerificationError, match="PTV050"):
            memory_gate(main, feed_shapes=shapes, fetch_names=[y.name],
                        where="test")
    finally:
        fluid.set_flags({"FLAGS_memory_budget_bytes": 0})
        memory_mod.reset_memo()


def test_pallas_interprets_only_on_the_cpu_backend(monkeypatch):
    from paddle_tpu.ops.pallas.flash_attention import _interpret
    assert _interpret() is True  # the CPU backend of the test suite
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="neither a TPU nor the CPU"):
        _interpret()


def test_tile_sweep_does_not_hide_a_kernel_failure(monkeypatch):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    from paddle_tpu.ops.pallas import autotune

    def broken(*args, **kwargs):
        raise NotImplementedError("Mosaic failed to compile the kernel")
    monkeypatch.setattr(fa, "flash_attention", broken)
    with pytest.raises(NotImplementedError, match="Mosaic failed"):
        autotune._sweep(256, 8, "float32", False, iters=1)
