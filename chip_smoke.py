"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at the full width of models the repo supports, in ONE process (a chip
belongs to one process at a time):

  device     the chip is found and is in bench.DEVICE_PEAKS; a jitted
             matmul returns after block_until_ready
  train      BERT-base, batch 32 x seq 512, bf16 AMP, use_flash="auto"
             (composed attention at this length), built by
             bench.build_bert_bench and run by fluid.Executor().run:
             six steps on one batch, loss finite and falling, exactly
             one compile of the main program
  flash      the same step with the Pallas kernel on the device: forced
             at seq 512 with 512 tiles (12 layers), and at seq 4096
             (batch 2, depth cut to 2 layers so that the composed
             reference fits) where "auto" selects it. Mosaic custom
             calls in the compiled HLO; first-step loss against the
             composed path on the same weights and batch
  serve      GPT-small (12 layers, d 768, vocab 32000, max_seq 1024),
             GenerationEngine with 8 slots behind
             serving.serve(port=0): eight POST /v1/generate, four at a
             time, prompts of 5..300 tokens, 32 new tokens each;
             /healthz, /metrics, zero post-warmup compiles, tokens equal
             to the serial gpt.kv_generate reference except at near-ties
             of its logits (LOGIT_RTOL below)
  multichip  with four devices: the train program through
             CompiledProgram.with_data_parallel under FLAGS_sharded_exec
             on mesh "4" and "2,2"; with one device it prints
             skipped: device_count=1

Default flags: it sets no FLAGS_* to get past a gate. Weights are
random, made from the programs' seeds. The first failure ends the run
with its traceback and a non-zero code; no phase is retried or skipped
over. Every phase prints one JSON line; the step times in them are
smoke timings of a few steps with a host sync in each, not benchmark
results.

A bare run needs a TPU and exits non-zero without one. `--rehearsal` is
the explicit tiny CPU walk through the same code (tests, and before a
chip call): its lines say platform "cpu" and rehearsal true, and its
last line is not the chip's result line.

Last line of stdout on the chip:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PHASES = ("device", "train", "flash", "serve", "multichip")

# One bf16 rounding step (8 significant bits): what two correct
# orderings of the same bf16 arithmetic may differ by, relative to the
# loss. The measured differences are in PERF.md's bring-up table.
LOSS_RTOL = 2.0 ** -8

# The engine's 8-row matmuls run on the MXU, which at jax's default
# precision rounds f32 operands to bf16; the batch-1 serial reference is
# matrix-vector work that XLA computes in full f32 (its logits are
# bit-equal at default and at "highest" precision). Measured on the v5e
# (PERF.md, bring-up, "serve"): the two paths' logits differ by up to
# 1.6e-2 at |logit| <= 2.8 (2^-7.4), by 1.5e-6 with both at "highest",
# where every token agrees. So the paged path may pick another token
# than the reference only where the reference's own top-2 logits are
# within twice that rounding of each other.
LOGIT_RTOL = 2.0 ** -7

# a request's own deadline, sent with it (not a flag)
REQUEST_TIMEOUT_MS = 120_000

FULL = {
    "train": {"batch": 32, "seq_len": 512, "n_layers": None, "steps": 6},
    "flash_forced": {"batch": 32, "seq_len": 512, "n_layers": None,
                     "use_flash": True, "flash_block": 512},
    # composed attention keeps B*H*T^2 scores per layer for the backward
    # pass: 12 layers of them do not fit 16 GB at T=4096, 2 layers do
    "flash_long": {"batch": 2, "seq_len": 4096, "n_layers": 2,
                   "use_flash": "auto"},
    "serve": {"cfg": {}, "slots": 8, "concurrency": 4, "new_tokens": 32,
              "prompt_lens": (5, 33, 64, 100, 150, 200, 257, 300)},
    "multichip_steps": 3,
}

# the rehearsal's BERT: narrow and shallow, so that a CPU compiles it in
# about a second (bench.build_bert_bench forwards these to the config)
_TINY = {"n_layers": 1, "vocab_size": 512, "d_model": 64, "n_heads": 2,
         "d_ff": 128}

REHEARSAL = {
    "train": {"batch": 2, "seq_len": 128, "steps": 6, **_TINY},
    "flash_forced": {"batch": 2, "seq_len": 128, "use_flash": True,
                     "flash_block": 128, **_TINY},
    "flash_long": {"batch": 1, "seq_len": 256, "use_flash": True,
                   **_TINY},
    "serve": {"cfg": {"vocab_size": 128, "d_model": 32, "n_heads": 4,
                      "n_layers": 2, "d_ff": 64, "max_seq_len": 64},
              "slots": 4, "concurrency": 2, "new_tokens": 4,
              "prompt_lens": (3, 9, 20, 37)},
    "multichip_steps": 2,
}


class Mark(NamedTuple):
    """A position in a CompileMeter's counts."""
    spans: int
    backend_compiles: int
    cache_hits: int
    cache_misses: int


class CompileMeter:
    """What jax spent tracing, lowering and compiling, and what the
    persistent cache did, read from jax.monitoring. Events nest (an
    inner jit is traced inside an outer trace), so compile seconds are
    the union of the events' intervals, not the sum of their durations.
    `mark()` is a position; a phase reports what came after its mark."""

    def __init__(self):
        import jax
        self.spans = []  # (start, end) of every /jax/core/compile/ event
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            end = time.perf_counter()
            self.spans.append((end - duration, end))
            if event.endswith("backend_compile_duration"):
                self.backend_compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self):
        return Mark(len(self.spans), self.backend_compiles,
                    self.cache_hits, self.cache_misses)

    def seconds_since(self, mark):
        total, reach = 0.0, float("-inf")
        for start, end in sorted(self.spans[mark.spans:]):
            total += max(0.0, end - max(start, reach))
            reach = max(reach, end)
        return total


class Smoke:
    """One run: the device, the sizes, and what later phases need of
    earlier ones (the one-chip first-step loss)."""

    def __init__(self, rehearsal):
        import jax
        import jaxlib
        from importlib import metadata

        import bench
        from paddle_tpu.core.compile_cache import configure_compile_cache
        self.rehearsal = rehearsal
        self.sizes = REHEARSAL if rehearsal else FULL
        self.meter = CompileMeter()
        self.devices = jax.devices()
        self.stamp = {
            **bench.device_stamp(),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": metadata.version("libtpu"),
            "compile_cache_dir": configure_compile_cache(),
        }
        if rehearsal:
            self.stamp["rehearsal"] = True
        self.train_loss0 = None
        self._t0 = None
        self._m0 = None

    # -- reporting -------------------------------------------------------
    def begin(self):
        self._t0 = time.perf_counter()
        self._m0 = self.meter.mark()

    def report(self, phase, **fields):
        """Print the phase's JSON line: the device stamp, seconds
        compiling apart from the rest of the phase, the fields."""
        wall = time.perf_counter() - self._t0
        m1 = self.meter.mark()
        compile_s = self.meter.seconds_since(self._m0)
        line = {"phase": phase, **self.stamp,
                "compile_s": round(compile_s, 2),
                "run_s": round(wall - compile_s, 2),
                "backend_compiles":
                m1.backend_compiles - self._m0.backend_compiles,
                "persistent_cache_hits": m1.cache_hits - self._m0.cache_hits,
                "persistent_cache_misses":
                m1.cache_misses - self._m0.cache_misses,
                **fields}
        print(json.dumps(line), flush=True)

    def mem(self, key, device=None):
        """One PJRT allocator statistic of a device (default: the
        first), None where the backend reports none (the CPU).
        peak_bytes_in_use is the process's high-water mark there, so
        only a rise is attributable to the last executable."""
        return ((device or self.devices[0]).memory_stats() or {}).get(key)

    def executable(self, name, exe, program, feed, fetch_list, scope):
        """Estimate / compiled / measured bytes of one executable that
        has already run: the static planner's peak for the program the
        executor compiles, XLA's memory_analysis() of the compiled
        step, and the device's peak_bytes_in_use."""
        import bench
        import paddle_tpu as fluid
        from paddle_tpu.compiler import CompiledProgram
        prog = program.program if isinstance(program, CompiledProgram) \
            else program
        _, plan = bench.planner_estimate(
            prog, feed, [v.name for v in fetch_list], where="chip_smoke")
        with fluid.scope_guard(scope):
            compiled = exe.compiled(program, feed=feed,
                                    fetch_list=fetch_list)
        mem = compiled.memory_analysis()
        return compiled, {
            "name": name,
            "est_peak_bytes": int(plan.peak_bytes),
            "xla_argument_bytes": int(mem.argument_size_in_bytes),
            "xla_output_bytes": int(mem.output_size_in_bytes),
            "xla_alias_bytes": int(mem.alias_size_in_bytes),
            "xla_temp_bytes": int(mem.temp_size_in_bytes),
            "xla_code_bytes": int(mem.generated_code_size_in_bytes),
            "peak_bytes_in_use": self.mem("peak_bytes_in_use"),
        }


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _finite_loss(x):
    v = float(np.asarray(x).reshape(-1)[0])
    _check(math.isfinite(v), f"loss is not finite: {v}")
    return v


def _close(a, b, what):
    tol = LOSS_RTOL * max(abs(a), abs(b))
    _check(abs(a - b) <= tol,
           f"{what}: {a} vs {b} differ by {abs(a - b):.3g} > {tol:.3g} "
           f"(one bf16 rounding step of the loss)")
    return abs(a - b)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(smoke):
    import jax
    import jax.numpy as jnp

    import bench
    from paddle_tpu import native
    smoke.begin()
    kind = smoke.stamp["device_kind"]
    # an unknown device_kind raises here; the CPU has no row by design
    peaks = None if smoke.rehearsal else bench.device_peaks(kind)
    n = 1024
    x = jnp.ones((n, n), jnp.bfloat16)
    y = jax.jit(lambda a, b: jnp.dot(
        a, b, preferred_element_type=jnp.float32))(x, x)
    y = jax.block_until_ready(y)
    _check(y.shape == (n, n) and float(y[0, 0]) == float(n)
           and bool(jnp.all(jnp.isfinite(y))), "jitted matmul is wrong")
    _check(y.devices() == {smoke.devices[0]},
           f"matmul ran on {y.devices()}, not on {smoke.devices[0]}")
    smoke.report("device", ok=True, peaks=peaks,
                 bytes_limit=smoke.mem("bytes_limit"),
                 native_loaded=native.AVAILABLE,
                 native_load_error=native.LOAD_ERROR)


# ---------------------------------------------------------------------------
# train / flash: BERT through bench.build_bert_bench and Executor.run
# ---------------------------------------------------------------------------

def _run_steps(exe, program, scope, feed, loss, steps, meter):
    """`steps` runs on one batch with the loss fetched. Returns the
    losses, the step seconds, and the compiles the executor and jax saw
    in the first step and in the rest."""
    import paddle_tpu as fluid
    losses, secs = [], []

    def counts():
        return (exe.cache_stats()["misses"],
                meter.mark().backend_compiles)

    with fluid.scope_guard(scope):
        miss0, jax0 = counts()
        for i in range(steps):
            t0 = time.perf_counter()
            out, = exe.run(program, feed=feed, fetch_list=[loss])
            secs.append(time.perf_counter() - t0)
            losses.append(_finite_loss(out))
            if i == 0:
                miss1, jax1 = counts()
        miss2, jax2 = counts()
    compiles = {"executor_first_step": miss1 - miss0,
                "executor_later_steps": miss2 - miss1,
                "jax_first_step": jax1 - jax0,
                "jax_later_steps": jax2 - jax1}
    _check(compiles["executor_first_step"] == 1
           and compiles["executor_later_steps"] == 0
           and compiles["jax_later_steps"] == 0,
           f"expected one compile, in the first step: {compiles}")
    return losses, secs, compiles


def _smoke_ms(secs):
    return {"smoke_first_step_s": round(secs[0], 2),
            "smoke_step_ms": [round(s * 1000, 1) for s in secs[1:]]}


def phase_train(smoke):
    import bench
    smoke.begin()
    size = dict(smoke.sizes["train"])
    steps = size.pop("steps")
    exe, main, scope, feed, loss, cfg = bench.build_bert_bench(
        use_flash="auto", **size)
    _check(cfg.use_flash is False,
           "use_flash='auto' chose the kernel below FLASH_AUTO_MIN_SEQ")
    losses, secs, compiles = _run_steps(exe, main, scope, feed, loss,
                                        steps, smoke.meter)
    _check(losses[-1] < losses[0],
           f"loss did not fall over {steps} steps: {losses}")
    _, triple = smoke.executable("bert_train_composed", exe, main, feed,
                                 [loss], scope)
    smoke.train_loss0 = losses[0]
    smoke.report("train", ok=True, model="bert_base",
                 batch=size["batch"], seq_len=size["seq_len"],
                 n_layers=cfg.n_layers, attention="composed",
                 losses=[round(v, 4) for v in losses], compiles=compiles,
                 **_smoke_ms(secs), executables=[triple])
    exe.close()


def _mosaic_calls(hlo, kernel):
    """How many Mosaic custom calls of `kernel` the compiled HLO holds
    (the kernels carry their pallas_call names)."""
    return sum(kernel in ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln)


def _mosaic_kernels(hlo):
    return {k: _mosaic_calls(hlo, f"flash_attention_{k}")
            for k in ("fwd", "bwd_dq", "bwd_dkv")}


def _flash_case(smoke, name, size, reference_loss0=None):
    """One flash step against the composed path on the same weights
    (the two builds share every seed and every op id but the attention
    attrs, so dropout masks agree too) and the same batch."""
    import bench
    from paddle_tpu.ops.pallas.flash_attention import _interpret
    size = dict(size)
    use_flash = size.pop("use_flash")
    triples = []
    if reference_loss0 is None:
        exe, main, scope, feed, loss, _ = bench.build_bert_bench(
            use_flash=False, **size)
        ref_losses, _, _ = _run_steps(exe, main, scope, feed, loss, 1,
                                      smoke.meter)
        _, triple = smoke.executable(f"{name}_composed", exe, main, feed,
                                     [loss], scope)
        triples.append(triple)
        reference_loss0 = ref_losses[0]
        exe.close()
        del exe, main, scope
        gc.collect()
    exe, main, scope, feed, loss, cfg = bench.build_bert_bench(
        use_flash=use_flash, **size)
    _check(cfg.use_flash is True,
           f"{name}: use_flash={use_flash!r} did not select the kernel")
    interpret = _interpret()
    _check(interpret == smoke.rehearsal,
           f"{name}: Pallas interpret mode is {interpret} on "
           f"{smoke.stamp['platform']}")
    losses, secs, compiles = _run_steps(exe, main, scope, feed, loss, 2,
                                        smoke.meter)
    compiled, triple = smoke.executable(f"{name}_flash", exe, main, feed,
                                        [loss], scope)
    triples.append(triple)
    kernels = _mosaic_kernels(compiled.as_text())
    if not interpret:
        _check(all(n >= cfg.n_layers for n in kernels.values()),
               f"{name}: Mosaic custom calls missing from the compiled "
               f"HLO: {kernels} for {cfg.n_layers} layers")
    diff = _close(losses[0], reference_loss0,
                  f"{name}: flash vs composed first-step loss")
    exe.close()
    return {"case": name, "batch": size["batch"],
            "seq_len": size["seq_len"], "n_layers": cfg.n_layers,
            "use_flash": use_flash if isinstance(use_flash, str) else
            "forced", "flash_block": size.get("flash_block", "flags"),
            "interpret": interpret, "mosaic_custom_calls": kernels,
            "loss_flash": round(losses[0], 5),
            "loss_composed": round(reference_loss0, 5),
            "loss_abs_diff": round(diff, 6),
            "loss_tolerance": round(LOSS_RTOL * abs(reference_loss0), 6),
            "compiles": compiles, **_smoke_ms(secs)}, triples


def phase_flash(smoke):
    smoke.begin()
    _check(smoke.train_loss0 is not None,
           "flash compares against the train phase: run train first")
    # forced at the train phase's own shape: its first-step loss is the
    # composed reference, no second composed compile
    forced, t1 = _flash_case(smoke, "bert_s512_forced",
                             smoke.sizes["flash_forced"],
                             reference_loss0=smoke.train_loss0)
    gc.collect()
    long, t2 = _flash_case(smoke, "bert_s4096_auto",
                           smoke.sizes["flash_long"])
    smoke.report("flash", ok=True, cases=[forced, long],
                 executables=t1 + t2)


# ---------------------------------------------------------------------------
# serve: GPT-small behind the HTTP front end
# ---------------------------------------------------------------------------

def _http(url, payload=None, timeout=300):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body = r.read().decode()
        return r.status, body


def phase_serve(smoke):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationEngine
    smoke.begin()
    size = smoke.sizes["serve"]
    cfg = gpt.gpt_small(dropout=0.0, **size["cfg"])
    scope = fluid.Scope()
    engine = GenerationEngine(cfg, scope, max_slots=size["slots"],
                              max_seq=cfg.max_seq_len)
    engine.init_scope()  # random weights from the program's seed
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in size["prompt_lens"]]
    new = size["new_tokens"]
    _check(max(size["prompt_lens"]) > 2 * engine.block_size,
           "the longest prompt must span several prefill chunks")

    t_warm0 = time.perf_counter()
    srv = serving.serve(gen_engine=engine, port=0)  # warms the engine
    warm_s = time.perf_counter() - t_warm0
    try:
        status, body = _http(srv.url + "/healthz")
        health = json.loads(body)
        _check(status == 200 and health["engines"]["generate"]["state"]
               == "ready", f"/healthz: {status} {body}")

        def generate(prompt):
            t0 = time.perf_counter()
            # the engine's default deadline (1 s, queue wait and decode
            # together) is shorter than a 300-token prompt takes here
            status, body = _http(srv.url + "/v1/generate",
                                 {"prompt": prompt, "max_new_tokens": new,
                                  "timeout_ms": REQUEST_TIMEOUT_MS})
            out = json.loads(body)
            _check(status == 200 and len(out["tokens"]) == new
                   and out["finish_reason"] == "length",
                   f"/v1/generate: {status} {body[:200]}")
            return out["tokens"], time.perf_counter() - t0

        with ThreadPoolExecutor(size["concurrency"]) as pool:
            results = list(pool.map(generate, prompts))
        together = [r[0] for r in results]
        # the longest prompt again, alone in the engine
        alone, _ = generate(prompts[-1])
        _check(alone == together[-1],
               "a request gave other tokens alone than among others: "
               f"{alone} vs {together[-1]}")

        status, metrics = _http(srv.url + "/metrics")
        _check(status == 200, f"/metrics: {status}")

        # the same shapes fed as device arrays (int64 on the host is
        # int32 on the device, x64 being off) hit the same executables
        triples = []
        paged_kernels = {}
        for name, prog, feed, fetch in engine.executables():
            dev_feed = {k: jax.device_put(v) for k, v in feed.items()}
            engine.exe.run(prog, feed=dev_feed, fetch_list=[fetch],
                           scope=scope)
            compiled, triple = smoke.executable(
                f"gpt_paged_{name}", engine.exe, prog, feed, [fetch],
                scope)
            triples.append(triple)
            # paged_attention's read is a Mosaic kernel in the
            # executables that served the requests above: one a layer
            # on the chip, none where the rehearsal interprets it
            paged_kernels[name] = _mosaic_calls(compiled.as_text(),
                                                "paged_attention_read")
            _check(paged_kernels[name] ==
                   (0 if smoke.rehearsal else cfg.n_layers),
                   f"gpt_paged_{name}: {paged_kernels[name]} Mosaic "
                   f"paged_attention_read calls in the compiled HLO for "
                   f"{cfg.n_layers} layers on {smoke.stamp['platform']}")
        _check(engine.post_warmup_compiles() == 0,
               f"{engine.post_warmup_compiles()} compiles after warmup "
               f"(requests, then device-array feeds of the same shapes)")
        status, body = _http(srv.url + "/healthz")
        _check(json.loads(body)["engines"]["generate"]
               ["post_warmup_compiles"] == 0, f"/healthz: {body}")
    finally:
        srv.close()
        engine.stop()

    # serial reference: batch-1 slab decode, one token a step, on the
    # same weights; its logits say how close each choice was
    dec_main, dec_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_startup):
        step = gpt.build_decode_step(cfg, batch=1,
                                     max_seq=cfg.max_seq_len)
    ref_exe = fluid.Executor()
    exact = flips = 0
    min_margin, flip_margins = float("inf"), []
    for i, prompt in enumerate(prompts):
        margins, scales = [], []

        def top2(row):
            row = np.asarray(row, np.float64)
            a, b = np.partition(row, -2)[-2:]
            margins.append(float(b - a))
            scales.append(float(np.abs(row).max()))

        want = gpt.kv_generate(ref_exe, scope, dec_main, step.token_var,
                               step.logits_var, step.cache_names,
                               prompt=prompt, max_new_tokens=new,
                               logits_cb=top2)
        got = together[i]
        min_margin = min(min_margin, *margins)
        if got == want:
            exact += 1
            continue
        j = next(k for k in range(new) if got[k] != want[k])
        # before j both paths saw the same tokens, so step j's logits
        # are comparable; after a flip the two sequences differ by right
        tie = 2 * LOGIT_RTOL * scales[j]
        _check(margins[j] <= tie,
               f"request {i} (prompt {len(prompt)}): token {j} is "
               f"{got[j]}, the serial reference says {want[j]} with a "
               f"top-2 margin of {margins[j]:.3g} > {tie:.3g} (twice the "
               f"bf16 rounding of a logit of {scales[j]:.3g}): not a "
               f"near-tie, the paged path is wrong")
        flips += 1
        flip_margins.append(round(margins[j], 6))
    feed = {step.token_var.name: np.zeros((1, 1), np.int64),
            "slot_reset": np.ones(1, np.float32),
            "slot_active": np.ones(1, np.float32)}
    _, triple = smoke.executable("gpt_serial_decode", ref_exe, dec_main,
                                 feed, [step.logits_var], scope)
    triples.append(triple)
    smoke.report("serve", ok=True, model="gpt_small",
                 n_layers=cfg.n_layers, d_model=cfg.d_model,
                 vocab_size=cfg.vocab_size, max_seq=cfg.max_seq_len,
                 slots=size["slots"], block_size=engine.block_size,
                 kv_pool_bytes=engine.kv_pool_bytes(),
                 paged_attention_read="interpreted" if smoke.rehearsal
                 else "compiled (Mosaic)",
                 paged_attention_mosaic_calls=paged_kernels,
                 requests=len(prompts), concurrency=size["concurrency"],
                 prompt_lens=list(size["prompt_lens"]), new_tokens=new,
                 smoke_warmup_s=round(warm_s, 2),
                 smoke_request_s=[round(r[1], 2) for r in results],
                 post_warmup_compiles=0,
                 same_alone_and_together=True,
                 requests_equal_to_serial=exact,
                 near_tie_flips=flips, flip_top2_margins=flip_margins,
                 logit_rtol=LOGIT_RTOL,
                 min_top2_margin=round(min_margin, 6),
                 metrics_bytes=len(metrics), executables=triples)
    ref_exe.close()


# ---------------------------------------------------------------------------
# multichip: the train program under FLAGS_sharded_exec
# ---------------------------------------------------------------------------

def phase_multichip(smoke):
    import jax

    import bench
    import paddle_tpu as fluid
    smoke.begin()
    n = len(smoke.devices)
    if n < 4:
        smoke.report("multichip", ok=True,
                     skipped=f"device_count={n}")
        return
    _check(smoke.train_loss0 is not None,
           "multichip compares against the train phase: run train first")
    size = dict(smoke.sizes["train"])
    size.pop("steps")
    steps = smoke.sizes["multichip_steps"]
    meshes, triples = [], []
    prev = fluid.get_flags(["FLAGS_sharded_exec", "FLAGS_sharded_mesh"])
    try:
        for spec in (str(n), f"{n // 2},2"):
            gc.collect()
            fluid.set_flags({"FLAGS_sharded_exec": True,
                             "FLAGS_sharded_mesh": spec})
            exe, main, scope, feed, loss, _ = bench.build_bert_bench(
                use_flash="auto", **size)
            prog = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
            losses, secs, compiles = _run_steps(exe, prog, scope, feed,
                                                loss, steps, smoke.meter)
            diff = _close(losses[0], smoke.train_loss0,
                          f"mesh {spec}: sharded vs one-chip first-step "
                          f"loss")
            compiled, triple = smoke.executable(
                f"bert_train_mesh_{spec}", exe, prog, feed, [loss], scope)
            hlo = compiled.as_text()
            collectives = {k: hlo.count(f" {k}(") + hlo.count(f" {k}-start(")
                           for k in ("all-reduce", "all-gather",
                                     "reduce-scatter", "all-to-all",
                                     "collective-permute")}
            _check(collectives["all-reduce"] > 0,
                   f"mesh {spec}: no all-reduce in the compiled HLO: "
                   f"{collectives}")
            state = {name: scope.find_var(name) for name in scope.names()}
            state = {k: v for k, v in state.items()
                     if isinstance(v, jax.Array)}
            partial = [k for k, v in state.items()
                       if len(v.sharding.device_set) != n]
            _check(state and not partial,
                   f"mesh {spec}: state arrays not on all {n} devices: "
                   f"{partial[:5]} (+{max(len(partial) - 5, 0)})")
            n_state = len(state)
            sharded = sum(not v.sharding.is_fully_replicated
                          for v in state.values())
            del state
            in_use = [smoke.mem("bytes_in_use", d) for d in smoke.devices]
            peaks = [smoke.mem("peak_bytes_in_use", d)
                     for d in smoke.devices]
            if all(b is not None for b in in_use):
                _check(all(p for p in peaks),
                       f"mesh {spec}: a device never held anything: "
                       f"{peaks}")
                _check(max(in_use) <= 2 * min(in_use),
                       f"mesh {spec}: one chip holds more than twice "
                       f"another: {in_use}")
            triple["per_device_bytes_in_use"] = in_use
            triple["per_device_peak_bytes_in_use"] = peaks
            triples.append(triple)
            meshes.append({"mesh": spec, "steps": steps,
                           "losses": [round(v, 4) for v in losses],
                           "loss_one_chip": round(smoke.train_loss0, 4),
                           "loss_abs_diff": round(diff, 6),
                           "loss_tolerance": round(
                               LOSS_RTOL * abs(smoke.train_loss0), 6),
                           "state_arrays": n_state,
                           "state_arrays_sharded": sharded,
                           "collectives": collectives,
                           "compiles": compiles, **_smoke_ms(secs)})
            exe.close()
            del exe, main, scope, prog, compiled
    finally:
        fluid.set_flags(prev)
    smoke.report("multichip", ok=True, meshes=meshes, executables=triples)


RUN = {"device": phase_device, "train": phase_train, "flash": phase_flash,
       "serve": phase_serve, "multichip": phase_multichip}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Run the trainer and the generation server once on "
                    "the chip (see the module docstring).")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend: a walk through "
                         "the same code, never a result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset, run in the order "
                         f"{','.join(PHASES)}")
    args = ap.parse_args(argv)
    want = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = [p for p in want if p not in PHASES]
    if unknown:
        ap.error(f"unknown phases {unknown}; known: {list(PHASES)}")

    import jax
    if args.rehearsal:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != ("cpu" if args.rehearsal else "tpu"):
        print(f"chip_smoke.py: jax's first device is on platform "
              f"{platform!r}. A bare run needs a TPU; --rehearsal is "
              f"the CPU's.", file=sys.stderr)
        return 2

    smoke = Smoke(args.rehearsal)
    for phase in PHASES:
        if phase in want:
            RUN[phase](smoke)
    device = {"platform": smoke.stamp["platform"],
              "kind": smoke.stamp["device_kind"],
              "count": smoke.stamp["device_count"]}
    if args.rehearsal:
        print(json.dumps({"rehearsal_passed": True, "phases": want,
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
