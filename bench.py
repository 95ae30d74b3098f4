"""Benchmark: single-chip training-step throughput on real TPU.

Matches BASELINE.json: the primary metric is BERT-base pretraining
tokens/sec/chip (config 3); BENCH_MODEL=resnet50 measures the ResNet-50
ImageNet config (the north-star MFU workload, config 0). Each step
(fwd + vjp-backward + optimizer) is ONE XLA program produced by the
Executor. vs_baseline = measured MFU / 0.50 (the ">=50% MFU" north
star; the reference publishes no numeric baseline — BASELINE.md).

Prints ONE JSON line for the selected model (default: bert), stamped
with the device it ran on. BENCH_MODEL selects bert | resnet50 | gpt
(causal flash path) | transformer (Transformer-big En-De NMT, config 3)
| deeplab (DeepLabv3+ dilated convs, config 5) | both (bert +
resnet50) | all (all five).

Runs on the accelerator jax finds and fails without one: a device that
is not in DEVICE_PEAKS raises, and a model that raised makes the exit
code non-zero. BENCH_PLATFORM=cpu is the explicit switch the tests use
to drive the plumbing on the CPU; its lines carry platform "cpu" and no
MFU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def _log_path() -> str:
    """Where result lines + monitor snapshots go (JSONL, append mode):
    BENCH_LOG env > FLAGS_monitor_export_path > bench_log.jsonl. Every
    record is flushed the moment it exists, so a harness timeout-kill
    cannot lose completed configs."""
    p = os.environ.get("BENCH_LOG")
    if p:
        return p
    try:
        from paddle_tpu.core.flags import FLAGS
        if FLAGS.monitor_export_path:
            return FLAGS.monitor_export_path
    except Exception:  # noqa: BLE001 — log path must never kill bench
        pass
    return "bench_log.jsonl"


def _emit(log_path, record):
    """Append one JSON line to the log (and leave stdout untouched)."""
    try:
        with open(log_path, "a") as f:
            f.write(json.dumps(record) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError as e:
        print(f"# bench log write failed: {e}", file=sys.stderr)


def _summary_path() -> str:
    """The top-level JSON summary artifact (BENCH_SUMMARY env, default
    bench_summary.json). Unlike the JSONL log this is ONE json.load-able
    document: written ahead (status "running") before any bench starts
    and atomically replaced after every result, so the file parses at
    every instant of the run — including the instant `timeout -k` kills
    it."""
    return os.environ.get("BENCH_SUMMARY", "bench_summary.json")


def _write_summary(path, obj):
    """Atomic replace (tmp + fsync + os.replace): readers never observe
    a torn or truncated summary."""
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(obj, f, indent=1)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError as e:
        print(f"# bench summary write failed: {e}", file=sys.stderr)


def _flight_path() -> str:
    """Crash flight-recorder dump target: BENCH_FLIGHT env >
    FLAGS_flight_recorder_path > bench_flight.jsonl."""
    p = os.environ.get("BENCH_FLIGHT")
    if p:
        return p
    try:
        from paddle_tpu.core.flags import FLAGS
        if FLAGS.flight_recorder_path:
            return FLAGS.flight_recorder_path
    except Exception:  # noqa: BLE001 — path lookup must never kill bench
        pass
    return "bench_flight.jsonl"


def _perf_ledger():
    """Import tools/perf_ledger.py (lightweight: no paddle_tpu/jax
    import) for provenance stamping and the BENCH_LEDGER hook."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    import perf_ledger
    return perf_ledger


def _ledger_and_gate(summary, log, platform_hint=""):
    """BENCH_LEDGER=path.jsonl auto-ingests this run's results into
    the longitudinal perf ledger (provenance stamped); BENCH_GATE=1
    additionally gates them against the EXISTING history first
    (tools/perf_gate.py) and emits the perf_gate record to stdout +
    the JSONL log. Informational: bench's exit code stays the
    one-artifact-per-model contract — CI that wants a failing gate
    runs tools/perf_gate.py on the summary itself."""
    ledger = os.environ.get("BENCH_LEDGER", "")
    if not ledger:
        return
    try:
        pl = _perf_ledger()
        rows, _skipped = pl.rows_from_record(summary)
        if not rows:
            return
        if os.environ.get("BENCH_GATE") == "1":
            import perf_gate
            results = perf_gate.gate_rows(rows, pl.load_rows(ledger))
            report = perf_gate.gate_report(results, ledger, 4.0, 3, 20)
            print(json.dumps(report), flush=True)
            _emit(log, report)
        pl.append_rows(ledger, rows,
                       pl.provenance(platform=platform_hint or None))
    except Exception as e:  # noqa: BLE001 — ledger must never kill bench
        print(f"# perf ledger unavailable: {e}", file=sys.stderr)


def _record_bench_stats(flops_per_step):
    """Feed the monitor the model's per-step flops + the chip peak so
    tools/metrics_report.py can derive MFU from the step-time histogram
    (no-ops unless FLAGS_enable_monitor, and on a device without a
    published peak)."""
    import jax
    from paddle_tpu import monitor
    if not monitor.enabled() or jax.devices()[0].platform == "cpu":
        return
    monitor.STAT_SET("bench.model_flops_per_step", flops_per_step)
    monitor.STAT_SET("bench.peak_flops_per_chip", peak_flops_per_chip())


# Published per-chip peaks, keyed by jax's device_kind. The one table:
# chip_smoke.py reads it too. A device that is not here is an error,
# never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, \"TPU v5e\"",
    },
}


def device_peaks(device_kind=None):
    """DEVICE_PEAKS row of `device_kind` (default: the local chip)."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add a sourced row to "
            f"bench.DEVICE_PEAKS") from None


def peak_flops_per_chip():
    """bf16 peak of the local chip; raises for an unknown device."""
    return device_peaks()["bf16_flops_per_s"]


def _mfu_fields(flops, dt):
    """(mfu, vs_baseline) for a result line, rounded; (None, None) on
    the explicit CPU switch (BENCH_PLATFORM=cpu): a CPU has no row in
    DEVICE_PEAKS, and a CPU time is not a device metric."""
    import jax
    if jax.devices()[0].platform == "cpu":
        return None, None
    mfu = flops / dt / peak_flops_per_chip()
    return round(mfu, 4), round(mfu / 0.50, 4)


def device_stamp():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d)}


def model_flops_per_token(cfg, seq_len):
    """Matmul flops per token, fwd+bwd (3x fwd): dense 6*N_mat +
    attention 12*L*T*d (scores+context, fwd+bwd). The vocab projection
    counts only at the positions it actually runs on (mask_frac < 1
    under the MLM objective, where the lm head is gathered to the
    masked positions) — MFU stays honest about work NOT done."""
    d, L = cfg.d_model, cfg.n_layers
    n_mat = (L * (4 * d * d + 2 * d * cfg.d_ff)
             + getattr(cfg, "mask_frac", 1.0) * cfg.vocab_size * d)
    dense = 6 * n_mat
    attn = 12 * L * seq_len * d
    return dense + attn


def planner_estimate(prog, feed, fetch_names, where="bench"):
    """(optimized program, MemoryPlan) of the program the executor will
    actually compile for this feed: the graph-optimization gate
    memoizes per (fingerprint, level, feeds, fetches), so this primes —
    or reuses — the executor's own entry, and the static peak estimate
    is sized with the concrete feed shapes (analysis/memory,
    docs/memory_planning.md). chip_smoke.py prints the same estimate
    next to XLA's memory_analysis()."""
    from paddle_tpu.analysis import analyze_program_memory, optimize_gate
    opt_prog, _ = optimize_gate(prog, feed_names=sorted(feed),
                                fetch_names=fetch_names, where=where)
    plan = analyze_program_memory(
        opt_prog, feed_names=sorted(feed), fetch_names=fetch_names,
        feed_shapes={k: (tuple(v.shape), str(v.dtype))
                     for k, v in feed.items()})
    return opt_prog, plan


def _timed_steps(exe, prog, feed, loss, steps):
    """Device step time: enqueue `steps` async steps (they serialize
    on-device via the donated state dict) and end the window with
    `jax.block_until_ready` on the last loss, so the clock stops when
    the device does and not when the last step was enqueued. Fetching
    the loss to numpy every iteration would add a host sync to every
    step. The measurement runs as TWO independent windows whose
    relative spread is reported, so a later delta carries an error bar.

    Returns (dt_seconds, last_loss, stats_dict).
    """
    import jax

    # BENCH_MESH ('8' dp-only, '4,2' dp x tp): run the step through the
    # GSPMD sharded path — a SpecLayout table over the mesh (ZeRO
    # moments on the data axis, params on the model axis, feeds batch-
    # sharded), one compile per signature exactly like the single-chip
    # path. Ledger rows then report tok/s/chip next to the single-chip
    # numbers (docs/sharding.md).
    mesh_env = os.environ.get("BENCH_MESH", "")
    mesh = layout = None
    run_prog = prog
    if mesh_env:
        from paddle_tpu.compiler import CompiledProgram
        from paddle_tpu.parallel.layout import SpecLayout, mesh_from_spec
        mesh = mesh_from_spec(mesh_env)
        layout = SpecLayout(mesh).add_program(prog)
        run_prog = CompiledProgram(prog).with_distributed(
            mesh, state_spec_fn=layout,
            batch_axes=(layout.data_axis,) if layout.data_axis else ())

    # Stage the batch on device ONCE: the executor passes jax.Array
    # feeds straight to the jitted step, so the timed loop measures the
    # training step, not a per-step host->device reupload of the batch
    # (38 MB/step for ResNet images; a production input pipeline
    # double-buffers batches onto device the same way, reference
    # reader/buffered_reader.cc). Under a mesh each batch is device_put
    # straight into its batch-sharded layout, so no chip ever holds the
    # full host batch.
    def _stage(v):
        arr = np.asarray(v)
        ns = run_prog.feed_sharding(arr.shape) if mesh is not None \
            else None
        return jax.device_put(arr, ns) if ns is not None \
            else jax.device_put(arr)
    feed = {k: _stage(v) for k, v in feed.items()}

    # Record what the graph-optimization pipeline does to this program
    # (FLAGS_graph_opt_level, analysis/passes) and the static peak
    # estimate, recorded next to the measured device stats below so
    # every ledger row calibrates the estimator.
    from paddle_tpu.core.flags import FLAGS
    opt_level = int(FLAGS.graph_opt_level)
    ops_pre = len(prog.global_block().ops)
    opt_prog, plan = planner_estimate(prog, feed, [loss.name])
    ops_post = len(opt_prog.global_block().ops)

    # compile + warmup (synced)
    exe.run(run_prog, feed=feed, fetch_list=[loss])
    x, = exe.run(run_prog, feed=feed, fetch_list=[loss],
                 return_numpy=False)
    jax.block_until_ready(x)  # drain the queue

    def window(n):
        t0 = time.perf_counter()
        for _ in range(n):
            x, = exe.run(run_prog, feed=feed, fetch_list=[loss],
                         return_numpy=False)
        jax.block_until_ready(x)
        return (time.perf_counter() - t0) / n, np.asarray(x)

    n1 = max(1, steps // 2)
    n2 = max(1, steps - n1)
    dt1, _ = window(n1)
    dt2, lv = window(n2)
    dt = (dt1 * n1 + dt2 * n2) / (n1 + n2)
    stats = {"windows_ms": [round(dt1 * 1000, 2), round(dt2 * 1000, 2)],
             "window_spread": round(abs(dt1 - dt2) / dt, 4),
             **device_stamp(),
             "graph_opt_level": opt_level,
             "ops_pre_opt": ops_pre, "ops_post_opt": ops_post}
    if mesh is not None:
        stats["mesh_shape"] = [int(mesh.shape[a])
                               for a in mesh.axis_names]
        stats["mesh_axes"] = list(mesh.axis_names)
        stats["mesh_devices"] = int(mesh.size)
        stats["collective_bytes_per_step"] = \
            int(layout.collective_bytes_estimate(prog))
        # closed-form gradient-sync reference (arxiv 2004.13336): the
        # perf ledger flags drift between this and the per-op model's
        # prediction above
        stats["grad_sync_bytes_per_step"] = \
            int(layout.gradient_sync_bytes(prog))
    stats["est_peak_bytes"] = int(plan.peak_bytes)
    stats["est_peak_dynamic"] = bool(plan.dynamic)
    # measured counterpart: PJRT per-device stats after the timed
    # windows (empty {} on backends that don't report, e.g. CPU)
    from paddle_tpu.core.memory import device_memory_stats
    mem = device_memory_stats()
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if mem.get(key) is not None:
            stats[f"measured_{key}"] = int(mem[key])
    return dt, lv, stats


def _bench_layers(n_layers=None):
    """Optional depth override (BENCH_LAYERS env or explicit arg): the
    tiny CPU builds compile a 2-layer model so driving the bench code
    path costs seconds, not the minute+ a 12-layer XLA CPU compile
    takes. Unset -> each model's reference depth."""
    if n_layers is not None:
        return {"n_layers": int(n_layers)}
    env = os.environ.get("BENCH_LAYERS", "")
    return {"n_layers": int(env)} if env else {}


def _bench_flash_blocks():
    """BENCH_FLASH_BLOCK env -> explicit flash tile attrs on the model
    config: "512" pins block_q=block_k=512, "512,256" pins q,k
    separately. Unset -> {} so the op attrs stay absent and the
    flags/autotuner choose the tile (ops/pallas/autotune.py)."""
    env = os.environ.get("BENCH_FLASH_BLOCK", "")
    if not env:
        return {}
    parts = [int(p) for p in env.split(",") if p.strip()]
    if not parts:
        return {}
    bq = parts[0]
    bk = parts[1] if len(parts) > 1 else parts[0]
    return {"flash_block_q": bq, "flash_block_k": bk}


def build_bert_bench(batch=None, seq_len=None, n_layers=None,
                     use_flash=None, flash_block=None, **cfg_kw):
    """Build the BERT pretraining step per the BENCH_* env config.
    Returns (exe, program, scope, feed, loss, cfg) — shared by bench.py,
    chip_smoke.py and tools/profile_step.py so the program they run is
    exactly the benchmarked one. `use_flash` (True / False / "auto")
    and `flash_block` (q=k tile) override BENCH_FLASH /
    BENCH_FLASH_BLOCK for callers that pick the attention path
    themselves; `cfg_kw` are TransformerConfig overrides (chip_smoke's
    CPU rehearsal narrows the model with them)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    batch = batch or int(os.environ.get("BENCH_BATCH", "32"))
    seq_len = seq_len or int(os.environ.get("BENCH_SEQ", "512"))
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    if use_flash is None:
        use_flash = os.environ.get("BENCH_FLASH", "1") == "1"
    blocks = _bench_flash_blocks() if flash_block is None else \
        {"flash_block_q": flash_block, "flash_block_k": flash_block}
    mlm = os.environ.get("BENCH_MLM", "0") == "1"
    # max_seq_len only feeds the use_flash="auto" crossover: positions
    # are sinusoidal, so there is no table to size
    cfg = transformer.bert_base(dropout=0.1, attn_dropout=0.0,
                                use_flash=use_flash,
                                max_seq_len=max(seq_len, 512),
                                **blocks,
                                **_bench_layers(n_layers), **cfg_kw)
    # BERT's actual objective: predict the ~15% masked positions, not
    # all T (rounded up to a multiple of 8 for clean TPU tiling)
    n_mask = -(-int(seq_len * 0.15) // 8) * 8
    main_prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main_prog, startup), fluid.scope_guard(scope):
        if mlm:
            loss, feeds = transformer.build_train_mlm(
                cfg, batch, seq_len, n_mask, lr=1e-4, amp=amp)
        else:
            loss, feeds = transformer.build_train(cfg, batch, seq_len,
                                                  lr=1e-4, amp=amp)
        exe = fluid.Executor()
        exe.run(startup)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype(np.int64)
    if mlm:
        pos = np.stack([rng.choice(seq_len, n_mask, replace=False)
                        + i * seq_len for i in range(batch)])
        pos = pos.reshape(-1).astype(np.int32)
        feed = {"tokens": toks, "mask_pos": pos,
                "mask_label": toks.reshape(-1)[pos].reshape(-1, 1)}
        cfg.mask_frac = n_mask / seq_len
    else:
        feed = {"tokens": toks, "labels": toks}
        cfg.mask_frac = 1.0
    return exe, main_prog, scope, feed, loss, cfg


def build_resnet50_bench(batch=None):
    """ResNet-50 ImageNet step per the BENCH_* env config; same return
    contract as build_bert_bench (cfg slot is None)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    batch = batch or int(os.environ.get("BENCH_BATCH", "64"))
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    main_prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main_prog, startup), fluid.scope_guard(scope):
        loss, acc, feeds = resnet.build_train(amp=amp)
        exe = fluid.Executor()
        exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {"image": rng.randn(batch, 3, 224, 224).astype(np.float32),
            "label": rng.randint(0, 1000, (batch, 1)).astype(np.int64)}
    return exe, main_prog, scope, feed, loss, None


def bench_bert():
    import paddle_tpu as fluid

    steps = int(os.environ.get("BENCH_STEPS", "20"))
    prior_flash = os.environ.get("BENCH_FLASH")
    probes_ms = None
    try:
        if prior_flash is None:
            # unset: probe both attention implementations briefly and
            # run the full measurement with the winner (the framework's
            # job is the fastest correct step, not a fixed kernel
            # choice)
            probes = {}
            for flag in ("1", "0"):
                os.environ["BENCH_FLASH"] = flag
                exe, prog, scope, feed, loss, cfg = build_bert_bench()
                with fluid.scope_guard(scope):
                    dt, _, _ = _timed_steps(exe, prog, feed, loss,
                                            max(4, steps // 4))
                probes[flag] = dt
                exe.close()
            best = min(probes, key=probes.get)
            os.environ["BENCH_FLASH"] = best
            probes_ms = {k: round(v * 1000, 2) for k, v in probes.items()}
        exe, main_prog, scope, feed, loss, cfg = build_bert_bench()
        flash_used = os.environ.get("BENCH_FLASH", "1")
        batch, seq_len = feed["tokens"].shape
        with fluid.scope_guard(scope):
            dt, lv, stats = _timed_steps(exe, main_prog, feed, loss, steps)
    finally:
        # the probe must not leak its winner into later benches
        # (BENCH_MODEL=all runs gpt after bert with its own default)
        if prior_flash is None:
            os.environ.pop("BENCH_FLASH", None)
        else:
            os.environ["BENCH_FLASH"] = prior_flash

    tokens_per_sec = batch * seq_len / dt
    flops = model_flops_per_token(cfg, seq_len) * batch * seq_len
    mfu, vs_baseline = _mfu_fields(flops, dt)
    _record_bench_stats(flops)
    extra = {"step_ms": round(dt * 1000, 2), "mfu": mfu,
             "batch": batch, "seq_len": seq_len,
             "flash": flash_used,
             "flash_block": os.environ.get("BENCH_FLASH_BLOCK", "auto"),
             "loss": float(np.asarray(lv)),
             "mlm": os.environ.get("BENCH_MLM", "0"), **stats}
    if probes_ms is not None:
        extra["flash_probe_ms"] = probes_ms
    if stats.get("mesh_devices"):
        extra["tok_s_per_chip"] = round(
            tokens_per_sec / stats["mesh_devices"], 1)
    return {
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "extra": extra,
    }


def bench_resnet50():
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    steps = int(os.environ.get("BENCH_STEPS", "20"))
    exe, main_prog, scope, feed, loss, _ = build_resnet50_bench()
    batch = feed["image"].shape[0]
    with fluid.scope_guard(scope):
        dt, lv, stats = _timed_steps(exe, main_prog, feed, loss, steps)

    images_per_sec = batch / dt
    flops = 3 * resnet.flops_per_image() * batch  # fwd + 2x bwd
    mfu, vs_baseline = _mfu_fields(flops, dt)
    _record_bench_stats(flops)
    return {
        "metric": "resnet50_imagenet_images_per_sec_per_chip",
        "value": round(images_per_sec, 1),
        "unit": "images/s",
        "vs_baseline": vs_baseline,
        "extra": {"step_ms": round(dt * 1000, 2), "mfu": mfu,
                  "batch": batch, "loss": float(np.asarray(lv)), **stats},
    }


def build_gpt_bench(batch=None, seq_len=None, n_layers=None):
    """GPT-small causal-LM step per the BENCH_* env config (third
    headline workload: exercises the causal flash-kernel path)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt

    batch = batch or int(os.environ.get("BENCH_BATCH", "32"))
    seq_len = seq_len or int(os.environ.get("BENCH_SEQ", "512"))
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    use_flash = os.environ.get("BENCH_FLASH", "1") == "1"
    cfg = gpt.gpt_small(dropout=0.1, attn_dropout=0.0,
                        use_flash=use_flash, max_seq_len=seq_len,
                        **_bench_flash_blocks(),
                        **_bench_layers(n_layers))
    main_prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main_prog, startup), fluid.scope_guard(scope):
        loss, logits, tokens = gpt.build_train(cfg, batch, seq_len,
                                               lr=3e-4, amp=amp)
        exe = fluid.Executor()
        exe.run(startup)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype(np.int64)
    return exe, main_prog, scope, {"tokens": toks}, loss, cfg


def bench_gpt():
    import paddle_tpu as fluid

    steps = int(os.environ.get("BENCH_STEPS", "20"))
    exe, main_prog, scope, feed, loss, cfg = build_gpt_bench()
    batch, seq_len = feed["tokens"].shape
    with fluid.scope_guard(scope):
        dt, lv, stats = _timed_steps(exe, main_prog, feed, loss, steps)
    t_eff = seq_len - 1  # in-graph next-token shift
    tokens_per_sec = batch * t_eff / dt
    # causal attention does half the score/context flops: subtract half
    # of the attention term from the shared full-attention accounting
    flops_tok = model_flops_per_token(cfg, t_eff) \
        - 6 * cfg.n_layers * t_eff * cfg.d_model
    flops = flops_tok * batch * t_eff
    mfu, vs_baseline = _mfu_fields(flops, dt)
    _record_bench_stats(flops)
    extra = {"step_ms": round(dt * 1000, 2), "mfu": mfu,
             "batch": int(batch), "seq_len": int(seq_len),
             "loss": float(np.asarray(lv)), **stats}
    if stats.get("mesh_devices"):
        extra["tok_s_per_chip"] = round(
            tokens_per_sec / stats["mesh_devices"], 1)
    return {
        "metric": "gpt_small_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "extra": extra,
    }


def build_transformer_bench(batch=None, src_len=None, trg_len=None,
                            n_layers=None):
    """Transformer-big En-De NMT step (BASELINE config 3); same return
    contract as build_bert_bench."""
    import paddle_tpu as fluid
    from paddle_tpu.models import nmt

    batch = batch or int(os.environ.get("BENCH_BATCH", "32"))
    src_len = src_len or int(os.environ.get("BENCH_SEQ", "256"))
    trg_len = trg_len or src_len
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    use_flash = os.environ.get("BENCH_FLASH", "1") == "1"
    cfg = nmt.transformer_big_nmt(dropout=0.1, attn_dropout=0.0,
                                  use_flash=use_flash,
                                  **_bench_flash_blocks(),
                                  **_bench_layers(n_layers))
    main_prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main_prog, startup), fluid.scope_guard(scope):
        loss, feeds = nmt.build_train(cfg, batch, src_len, trg_len,
                                      lr=1e-4, amp=amp)
        exe = fluid.Executor()
        exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {
        "src_tokens": rng.randint(0, cfg.vocab_size,
                                  (batch, src_len)).astype(np.int64),
        "trg_tokens": rng.randint(0, cfg.vocab_size,
                                  (batch, trg_len + 1)).astype(np.int64),
    }
    return exe, main_prog, scope, feed, loss, cfg


def bench_transformer():
    import paddle_tpu as fluid
    from paddle_tpu.models import nmt

    steps = int(os.environ.get("BENCH_STEPS", "20"))
    exe, main_prog, scope, feed, loss, cfg = build_transformer_bench()
    batch, src_len = feed["src_tokens"].shape
    trg_len = feed["trg_tokens"].shape[1] - 1
    with fluid.scope_guard(scope):
        dt, lv, stats = _timed_steps(exe, main_prog, feed, loss, steps)
    tokens_per_sec = batch * trg_len / dt
    flops = nmt.flops_per_step(cfg, batch, src_len, trg_len)
    mfu, vs_baseline = _mfu_fields(flops, dt)
    _record_bench_stats(flops)
    extra = {"step_ms": round(dt * 1000, 2), "mfu": mfu,
             "batch": int(batch), "src_len": int(src_len),
             "trg_len": int(trg_len),
             "loss": float(np.asarray(lv)), **stats}
    if stats.get("mesh_devices"):
        extra["tok_s_per_chip"] = round(
            tokens_per_sec / stats["mesh_devices"], 1)
    return {
        "metric": "transformer_big_ende_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        "extra": extra,
    }


def build_deeplab_bench(batch=None, img_hw=None):
    """DeepLabv3+ Cityscapes step (BASELINE config 5 — dilated convs +
    large activations); same return contract as build_bert_bench."""
    import paddle_tpu as fluid
    from paddle_tpu.models import deeplab

    batch = batch or int(os.environ.get("BENCH_BATCH", "8"))
    img_hw = img_hw or int(os.environ.get("BENCH_IMG", "513"))
    amp = os.environ.get("BENCH_AMP", "1") == "1"
    main_prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main_prog, startup), fluid.scope_guard(scope):
        loss, feeds = deeplab.build_train(img_hw=img_hw, batch=batch,
                                          amp=amp)
        exe = fluid.Executor()
        exe.run(startup)
    rng = np.random.RandomState(0)
    feed = {
        "image": rng.randn(batch, 3, img_hw, img_hw).astype(np.float32),
        "label": rng.randint(0, deeplab.N_CLASSES,
                             (batch, img_hw, img_hw)).astype(np.int64),
    }
    return exe, main_prog, scope, feed, loss, None


def bench_deeplab():
    import paddle_tpu as fluid
    from paddle_tpu.models import deeplab

    steps = int(os.environ.get("BENCH_STEPS", "20"))
    exe, main_prog, scope, feed, loss, _ = build_deeplab_bench()
    batch = feed["image"].shape[0]
    img_hw = feed["image"].shape[2]
    with fluid.scope_guard(scope):
        dt, lv, stats = _timed_steps(exe, main_prog, feed, loss, steps)
    images_per_sec = batch / dt
    flops = 3 * deeplab.flops_per_image(img_hw) * batch  # fwd + 2x bwd
    mfu, vs_baseline = _mfu_fields(flops, dt)
    _record_bench_stats(flops)
    return {
        "metric": "deeplabv3p_cityscapes_images_per_sec_per_chip",
        "value": round(images_per_sec, 1),
        "unit": "images/s",
        "vs_baseline": vs_baseline,
        "extra": {"step_ms": round(dt * 1000, 2), "mfu": mfu,
                  "batch": int(batch), "img_hw": int(img_hw),
                  "loss": float(np.asarray(lv)), **stats},
    }


# tiny-shape builders: the tests (and tools/hlo_audit.py, op_profile.py
# --tiny, program_lint.py) drive every model's bench code path on the
# CPU through these. Transformer families build 2 layers — the layer
# loop is homogeneous, and a 12-layer fwd+bwd XLA CPU compile alone
# takes about a minute.
_CPU_TINY_BUILDS = {
    "bert": lambda: build_bert_bench(batch=2, seq_len=64, n_layers=2),
    "resnet50": lambda: build_resnet50_bench(batch=2),
    "gpt": lambda: build_gpt_bench(batch=2, seq_len=64, n_layers=2),
    "transformer": lambda: build_transformer_bench(batch=2, src_len=32,
                                                   trg_len=24,
                                                   n_layers=2),
    "deeplab": lambda: build_deeplab_bench(batch=1, img_hw=65),
}


_METRICS = {
    "bert": ("bert_base_pretrain_tokens_per_sec_per_chip", "tokens/s"),
    "resnet50": ("resnet50_imagenet_images_per_sec_per_chip", "images/s"),
    "gpt": ("gpt_small_pretrain_tokens_per_sec_per_chip", "tokens/s"),
    "transformer": ("transformer_big_ende_tokens_per_sec_per_chip",
                    "tokens/s"),
    "deeplab": ("deeplabv3p_cityscapes_images_per_sec_per_chip",
                "images/s"),
}


def _error_line(model, err):
    metric, unit = _METRICS[model]
    return {"metric": metric, "value": 0.0, "unit": unit,
            "vs_baseline": 0.0, "error": err}


def _partial_lines(models, done, reason):
    """Result lines owed when the run is cut short (SIGTERM from the
    harness `timeout -k`, etc.): one error line per model that has not
    printed yet, plus a bench_partial_summary record. Pure function so
    the signal path is unit-testable (the real handler os._exits)."""
    done = set(done)
    lines = [_error_line(m, reason) for m in models if m not in done]
    summary = {"kind": "bench_partial_summary",
               "models": list(models),
               "completed": [m for m in models if m in done],
               "reason": reason}
    return lines, summary


def main(argv=None):
    """Prints one parseable JSON line per selected model; a model that
    raised prints an error line and the traceback, and makes the exit
    code 1. Without an accelerator (and without BENCH_PLATFORM) nothing
    runs and the exit code is 2. Every result line is ALSO appended to
    the JSONL log the moment it exists (with monitor snapshots
    interleaved when FLAGS_enable_monitor), and --time-budget stops the
    run cleanly between configs before an external `timeout` can kill
    it mid-config."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--time-budget", type=float,
                    default=float(os.environ.get("BENCH_TIME_BUDGET",
                                                 "0")),
                    help="soft wall-clock cap in seconds (0 = none): "
                         "bench stops cleanly between configs once "
                         "exceeded, emitting skip lines for the rest")
    args = ap.parse_args(argv)
    t_start = time.time()
    deadline = t_start + args.time_budget if args.time_budget > 0 else None

    def budget_left():
        return None if deadline is None else deadline - time.time()

    model = os.environ.get("BENCH_MODEL", "bert")
    models = {"both": ["bert", "resnet50"],
              "all": ["bert", "resnet50", "gpt", "transformer",
                      "deeplab"]}.get(model, [model])
    models = [m for m in models if m in _METRICS] or ["bert"]

    # BENCH_PLATFORM=cpu is the explicit CPU switch: the kill-resilience
    # test and plumbing work without a chip. Without it the bench runs
    # on the accelerator jax finds, in this one process, or not at all.
    import jax
    forced_platform = os.environ.get("BENCH_PLATFORM", "")
    if forced_platform:
        jax.config.update("jax_platforms", forced_platform)
    # goodput ledger (FLAGS_enable_goodput): classify the whole bench
    # run's wall-clock — the wait for the device to attach and the
    # warmup compiles land in their own categories, and the category
    # table is stamped into bench_summary.json by _finalize_summary
    _goodput = None
    try:
        from paddle_tpu import goodput as _gp
        if _gp.start_run("bench") is not None:
            _goodput = _gp
    except Exception as e:  # noqa: BLE001 — goodput must never kill bench
        print(f"# goodput unavailable: {e}", file=sys.stderr)
    t_attach0 = time.perf_counter()
    platform = jax.devices()[0].platform
    if _goodput is not None:
        _goodput.attribute("probe_wait",
                           time.perf_counter() - t_attach0)
    if platform == "cpu" and forced_platform != "cpu":
        print("bench.py: jax found no accelerator (platform cpu). A "
              "benchmark number comes from a chip; set BENCH_PLATFORM=cpu "
              "to drive the plumbing on the CPU.", file=sys.stderr)
        return 2

    if args.time_budget <= 0 and not forced_platform \
            and "BENCH_TIME_BUDGET" not in os.environ:
        # A driver may run plain `python bench.py` under an external
        # `timeout -k 10 870`: self-budget safely below that so the run
        # ends cleanly between configs with a parseable artifact instead
        # of dying rc=124. Forced-platform runs (CPU tests, plumbing
        # work) keep the no-budget default.
        args.time_budget = float(os.environ.get(
            "BENCH_DEFAULT_TIME_BUDGET", "840"))
        deadline = t_start + args.time_budget
        print(f"# time budget defaulted to {args.time_budget:.0f}s "
              f"(set BENCH_TIME_BUDGET to override)", file=sys.stderr)

    log = _log_path()
    flight = _flight_path()
    summary_path = _summary_path()
    done = set()
    results = []
    # write-ahead: the artifact parses before the first model starts
    summary = {"kind": "bench_summary", "status": "running",
               "models": list(models), "completed": [], "results": [],
               "ts_start": t_start}
    # run provenance (git rev / platform / mesh) rides in the summary
    # so a ledger row ingested from this artifact is bisectable
    try:
        pl = _perf_ledger()
        summary.update(pl.provenance(platform=forced_platform or None))
    except Exception as e:  # noqa: BLE001 — provenance is best-effort
        print(f"# provenance unavailable: {e}", file=sys.stderr)
    _write_summary(summary_path, summary)

    def _finalize_summary(status, reason=None):
        summary["status"] = status
        summary["completed"] = [m for m in models if m in done]
        summary["results"] = results
        if reason is not None:
            summary["reason"] = reason
        if _goodput is not None:
            snap = _goodput.snapshot()
            if snap is not None:
                summary["goodput"] = {
                    "wall_s": snap["wall_s"],
                    "goodput_frac": snap["goodput_frac"],
                    "sum_frac_err": snap["sum_frac_err"],
                    "categories": snap["categories"],
                    "steps": snap["steps"],
                    "post_warmup_compiles": snap["post_warmup_compiles"],
                    "starved_steps": snap["starved_steps"]}
        summary["ts_end"] = time.time()
        _write_summary(summary_path, summary)

    def _on_term(signum, frame):
        # the harness runs bench under `timeout -k`: SIGTERM arrives
        # first, so flush error lines for every unfinished model plus a
        # summary before the follow-up SIGKILL — the artifact stays one
        # parseable line per selected model no matter where we died
        reason = f"killed: signal {signum} before completion"
        lines, partial = _partial_lines(models, done, reason)
        for line in lines:
            print(json.dumps(line), flush=True)
            _emit(log, {"kind": "bench_result", "ts": time.time(),
                        **line})
            results.append(line)
        partial["ts"] = time.time()
        print(json.dumps(partial), flush=True)
        _emit(log, partial)
        _finalize_summary("killed", reason=reason)
        try:
            from paddle_tpu import monitor
            monitor.dump_flight_recorder(flight,
                                         reason=f"signal {signum}")
        except Exception:  # noqa: BLE001 — dying anyway
            pass
        os._exit(128 + signum)

    try:
        import signal
        signal.signal(signal.SIGTERM, _on_term)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass
    monitor_on = False
    try:
        from paddle_tpu import monitor
        monitor_on = monitor.enabled()
        if monitor_on:
            # periodic crash-safe snapshots: even a run killed by the
            # harness timeout leaves step/compile/feed stats behind
            monitor.start_exporter(log)
        # post-mortems for crashes the SIGTERM path can't see (unhandled
        # exceptions); SIGTERM itself stays with _on_term above
        monitor.install_flight_recorder(flight, on_sigterm=False)
    except Exception as e:  # noqa: BLE001 — monitor must never kill bench
        print(f"# monitor unavailable: {e}", file=sys.stderr)

    from paddle_tpu.core.compile_cache import configure_compile_cache
    configure_compile_cache()

    fns = {"bert": bench_bert, "resnet50": bench_resnet50,
           "gpt": bench_gpt, "transformer": bench_transformer,
           "deeplab": bench_deeplab}
    prev_elapsed = None
    failed = False
    for i, m in enumerate(models):
        left = budget_left()
        # stop cleanly between configs: skip the rest once the budget
        # is spent, or when the next config can't plausibly finish in
        # the time remaining (estimated from the previous config)
        if left is not None and (
                left <= 0 or (prev_elapsed is not None
                              and left < 0.8 * prev_elapsed)):
            for skip in models[i:]:
                line = _error_line(
                    skip, f"skipped: time budget exhausted "
                          f"({args.time_budget:.0f}s)")
                print(json.dumps(line), flush=True)
                _emit(log, {"kind": "bench_result", "ts": time.time(),
                            **line})
                results.append(line)
                done.add(skip)
            break
        t0 = time.time()
        try:
            line = fns[m]()
        except Exception as e:  # noqa: BLE001 — the other models still run
            traceback.print_exc()
            failed = True
            line = _error_line(m, f"{type(e).__name__}: {e}")
        prev_elapsed = time.time() - t0
        print(json.dumps(line), flush=True)
        _emit(log, {"kind": "bench_result", "ts": time.time(), **line})
        ex = line.get("extra") or {}
        if ex.get("mesh_shape") and ex.get("mesh_devices"):
            # companion ledger record for BENCH_MESH runs: the scaling
            # facts validate_bench_json.py checks and the
            # metrics_report.py '-- sharding --' section renders
            _emit(log, {"kind": "sharded_bench", "ts": time.time(),
                        "metric": line["metric"],
                        "unit": line.get("unit"),
                        "mesh_shape": ex["mesh_shape"],
                        "mesh_axes": ex.get("mesh_axes"),
                        "mesh_devices": ex["mesh_devices"],
                        "per_chip_throughput": ex.get(
                            "tok_s_per_chip",
                            round(line["value"] / ex["mesh_devices"],
                                  1)),
                        "collective_bytes_per_step": ex.get(
                            "collective_bytes_per_step", 0)})
        results.append(line)
        done.add(m)
        _finalize_summary("running")  # artifact parses mid-run too
        if monitor_on:
            try:
                from paddle_tpu import monitor
                monitor.snapshot_to_jsonl(log)
            except Exception as e:  # noqa: BLE001
                print(f"# snapshot failed: {e}", file=sys.stderr)
    if _goodput is not None:
        _goodput.end_run()
        try:
            # goodput_snapshot JSONL record: tools/goodput_report.py
            # renders the category table + waterfall from the bench log
            _goodput.export_snapshot(log)
        except OSError as e:
            print(f"# goodput export failed: {e}", file=sys.stderr)
    _finalize_summary("complete")
    _ledger_and_gate(summary, log, platform_hint=forced_platform)
    try:
        from paddle_tpu import monitor
        if monitor.flight_records():
            monitor.dump_flight_recorder(flight, reason="bench complete")
    except Exception as e:  # noqa: BLE001 — post-mortem is best-effort
        print(f"# flight recorder dump failed: {e}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
