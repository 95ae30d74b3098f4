"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell from the files BENCHMARK.json names, warms it (set-up),
measures for `--seconds`, checks what the timed path produced against
the plain reference, and prints one JSON line last. It needs the chips
the cell asks for and exits 3, printing no result, where JAX finds
fewer or none; `--rehearsal` (the tests' switch) runs the cell's tiny
sizes on whatever backend there is and says so in its line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (correct, manifest, peaks, serve_window,  # noqa: E402
                       trace_reduce, traffic)

NO_CHIP = 3


class CompileMeter:
    """Seconds jax spent tracing, lowering and compiling (the union of
    the events' intervals: they nest), and what the persistent cache
    did. After chip_smoke.CompileMeter."""

    def __init__(self):
        import jax
        self.spans, self.hits, self.misses = [], 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            end = time.perf_counter()
            self.spans.append((end - duration, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def seconds(self, since=0):
        return trace_reduce.total(trace_reduce.union(self.spans[since:]))


class Tracer:
    """One profiler slice into a fixed directory of the checkout,
    emptied before and after: a run writes little to disk."""

    def __init__(self, workload):
        self.dir = os.path.join(ROOT, ".bench_trace", workload)
        self.path = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.path = found[0] if found else None

    def load(self):
        if self.path is None:
            return None
        trace = trace_reduce.load(self.path)
        shutil.rmtree(self.dir, ignore_errors=True)
        print(json.dumps({"phase": "trace", "devices": len(trace.ops),
                          "ops": [len(d) for d in trace.ops],
                          "modules": [len(d) for d in trace.modules],
                          "module_names": sorted({m.name[:48] for d in
                                                  trace.modules
                                                  for m in d})[:8],
                          "host_spans": len(trace.host)}), flush=True)
        return trace


def phase(name, t_prev, **more):
    now = time.perf_counter()
    print(json.dumps({"phase": name, "s": round(now - t_prev, 3), **more}),
          flush=True)
    return now


def memory_peak_bytes(executables, devices):
    """The peak on the fullest chip: what the process holds there plus
    the temporaries of the largest executable the window drives (XLA's
    memory_analysis; the allocator's own peak does not see a program's
    temporaries on this backend, PERF.md section 6), or the allocator's
    peak where that is higher."""
    import jax
    held = {d: 0 for d in devices}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            if s.device in held:
                held[s.device] += s.data.nbytes
    temp = 0
    for _, compiled in executables:
        mem = compiled.memory_analysis()
        if mem is not None:
            temp = max(temp, int(mem.temp_size_in_bytes))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) or 0
             for d in devices]
    return int(max(max(held.values()) + temp, max(peaks)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    bench = manifest.benchmark_json()
    wl, cfg, mix, limits = manifest.cell(args.workload, args.rehearsal, bench)
    chips = int(wl["chips"])

    if args.rehearsal and chips > 1:
        flag = f"--xla_force_host_platform_device_count={chips}"
        if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = \
                (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return NO_CHIP
    if not args.rehearsal and (devices[0].platform == "cpu"
                               or len(devices) < chips):
        print(f"the cell needs {chips} accelerator chip(s); jax has "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return NO_CHIP
    devices = devices[:chips]
    from paddle_tpu.core.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    meter = CompileMeter()
    t = phase("import", T_PROCESS, compile_cache_dir=cache_dir)

    family = manifest.family(cfg["family"])
    drive = drive_train if family.KIND == "train" else drive_serve
    out = drive(args, family, cfg, mix, chips, limits, meter, t, devices)

    metrics, ctx = out["metrics"], out["ctx"]
    # a share of a peak needs a published peak: a device that is not in
    # the table is an error, and the rehearsal's CPU has no shares
    ctx["peaks"] = None if args.rehearsal else \
        peaks.device_peaks(devices[0].device_kind)
    if args.trace:
        reported = {}
        for m in manifest.metrics_of(wl["name"], "per_layer", bench):
            spec = manifest.metric_file(m["name"])
            value = manifest.reader(spec["reader"])(ctx,
                                                    **spec.get("args", {}))
            if value is not None:
                reported[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reported = {m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
                    for m in manifest.metrics_of(wl["name"], "end_to_end",
                                                 bench)}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": reported, "device": device}
    if args.rehearsal:
        line["rehearsal"] = True
    trace = ctx.get("trace")
    if args.trace and trace is not None:
        device["busy_s"] = trace_reduce.mean_busy_seconds(trace)
        device["window_s"] = trace_reduce.window_seconds(trace)
        line["breakdown"] = {"device_ops": trace_reduce.top_ops(trace),
                             "idle_gaps": trace_reduce.idle_gaps(trace)}
    line["setup_phases_s"] = out["setup_phases"]
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in out["compared"]}
    for name, value, limit in out["compared"]:
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def finish(cell, meter, devices):
    """After the window: memory first, then the program's state goes."""
    t = time.perf_counter()
    peak = memory_peak_bytes(cell.executables(), devices)
    phase("memory_analysis", t, memory_peak_bytes=peak,
          cache_hits=meter.hits, cache_misses=meter.misses)
    cell.free()
    return peak


def drive_train(args, family, cfg, mix, chips, limits, meter, t, devices):
    from benchmark import train_window
    sizes = family.sizes(cfg)
    setup = {}
    cell = family.build(cfg, mix, chips, args.seed)
    t = _mark(setup, "build", t)
    batches = traffic.train_batches(args.seed, mix, cell.rows,
                                    sizes["vocab_size"])
    n_check = int(mix["check_steps"])
    prog = cell.check_steps(batches[:n_check])
    t = _mark(setup, "warm_and_check_steps", t)
    compile_s = meter.seconds()
    setup_s = time.perf_counter() - T_PROCESS
    phase("setup", T_PROCESS, **setup, compile_s=round(compile_s, 3))

    tracer = Tracer(args.workload) if args.trace else None
    win = train_window.run(cell, batches[n_check:], args.seconds,
                           int(mix["steps_in_flight"]), tracer,
                           int(mix["trace_slice_steps"]))
    t = phase("window", t, steps=win["steps"],
              compiles_in_window=round(meter.seconds() - compile_s, 3))
    peak = finish(cell, meter, devices)
    t = time.perf_counter()
    ref = family.reference_readings(cfg, args.seed, batches[:n_check])
    numbers, where = correct.train_numbers(prog, ref)
    if not all(map(math.isfinite, win["losses"])):
        numbers["loss_gap"] = float("inf")
    ok, compared = correct.judge(numbers, limits)
    phase("reference", t, **numbers, **where, loss_program=prog["loss"],
          loss_reference=ref["loss"])
    metrics = {"train_tok_s_chip": win["tokens"] / win["window_s"] / chips,
               "setup_s": setup_s}
    ctx = {"kind": "train", "sizes": sizes, "chips": chips, "window": win,
           "rows": cell.rows, "seq_len": cell.seq_len,
           "setup_s": setup_s, "compile_s": compile_s,
           "trace": tracer.load() if tracer else None}
    return {"metrics": metrics, "ctx": ctx, "correct": ok,
            "compared": compared, "attempted": win["steps"], "failed": 0,
            "memory_peak_bytes": peak, "setup_phases": setup}


def _mark(setup, name, t_prev):
    now = time.perf_counter()
    setup[name] = round(now - t_prev, 3)
    return now


def drive_serve(args, family, cfg, mix, chips, limits, meter, t, devices):
    sizes = family.sizes(cfg)
    setup = {}
    cell = family.build(cfg, mix, chips, args.seed)
    t = _mark(setup, "build", t)
    requests = serve_window.make_requests(mix, args.seed,
                                          sizes["vocab_size"], args.seconds)
    cell.warm()
    t = _mark(setup, "warm", t)
    log = None
    tracer = Tracer(args.workload) if args.trace else None
    if args.trace:
        log = serve_window.StepLog(tracer, int(mix["trace_slice_calls"]),
                                   args.seconds * 0.4)
        if not cell.watch_steps(log):
            log = None
    compile_s = meter.seconds()
    setup_s = time.perf_counter() - T_PROCESS
    phase("setup", T_PROCESS, **setup, compile_s=round(compile_s, 3))

    try:
        t0, sent, t_end = serve_window.run(cell, mix, requests, args.seconds,
                                           log)
    except BaseException:
        cell.stop()
        raise
    finally:
        if log is not None:
            log.close()
    compiles = cell.post_warmup_compiles()
    metrics = {"setup_s": setup_s}
    failed = sum(1 for r in sent if r.failed())
    if mix["arrival"] == "backlog":
        window_s = t_end - t0
        metrics["serve_out_tok_s"] = \
            serve_window.tokens_until(t_end, sent) / window_s
        said = {}
        if 2 * len(sent) > len(requests):
            said["warning"] = "over half of the backlog's trace was sent: " \
                              "raise `requests` in the mix"
            print(f"warning: {said['warning']}", file=sys.stderr)
    else:
        ttft = serve_window.ttft_ms(t0, sent)
        metrics["ttft_p90_ms"] = serve_window.percentile(ttft, 90)
        metrics["gap_p95_ms"] = serve_window.percentile(
            serve_window.gaps_ms(sent), 95)
        window_s = max(r.stamps[-1] for r in sent if r.stamps) - t0
        # whether the queue grew through the window (the knee's test),
        # and how late the generator ran
        said = {**serve_window.ttft_thirds(ttft),
                "late_p95_ms": serve_window.late_ms(t0, sent, 95)}
    t = phase("window", t, sent=len(sent), requests=int(mix["requests"]),
              window_s=window_s, post_warmup_compiles=compiles,
              longest_pause_ms=serve_window.longest_pause_ms(sent), **said)
    picked = serve_window.check_sample(mix, args.seed, requests)
    cell.stop()
    peak = finish(cell, meter, devices)

    t = time.perf_counter()
    numbers = served_numbers(cfg, args.seed, picked)
    numbers.update(compiles_in_window=float(compiles),
                   requests_failed=float(failed))
    ok, compared = correct.judge(numbers, limits)
    phase("reference", t, requests_checked=[r.index for r in picked],
          **numbers)
    ctx = {"kind": "serve", "sizes": sizes, "chips": chips, "t0": t0,
           "sent": sent, "window_s": window_s,
           "max_slots": cell.max_slots, "log": log,
           "setup_s": setup_s, "compile_s": compile_s,
           "trace": tracer.load() if tracer else None}
    return {"metrics": metrics, "ctx": ctx, "correct": ok,
            "compared": compared, "attempted": len(sent), "failed": failed,
            "memory_peak_bytes": peak, "setup_phases": setup}


def served_numbers(cfg, seed, picked, control=None):
    """The reference, once the window has closed, over the sample of
    finished requests: the logits rows that the engine fetched for
    their sampling against the reference's rows at the same positions,
    and the widest gap by which a served token's logit lies below the
    reference's best. `logit_gap_var` is the mean squared gap as a
    share of the row's variance over the vocabulary: roundings add in
    variance, so it counts them (PERF.md section 6); its root and the
    widest single gap come with it and are not judged. With `control`
    (a lower precision) the reference computed in it stands in the
    program's place: its rows, and the tokens it puts first. A sample
    that is empty, or a request whose rows are not one a token, gives
    no numbers, and `judge` then says not correct."""
    if not picked or (control is None and any(
            r.logits is None or len(r.logits) != len(r.tokens)
            for r in picked)):
        return {}
    ref = manifest.reference(cfg["name"])
    params = ref.params(cfg, seed)
    low = ref.params(cfg, seed, control) if control else None
    widest, squares, below = [], [], []
    for r in picked:
        row = r.prompt + r.tokens
        at = slice(len(r.prompt) - 1, len(row) - 1)
        ref_rows = ref.logits(cfg, params, row)[at]
        if control:
            rows = ref.logits(cfg, low, row, control)[at]
            chosen = rows.argmax(axis=-1)
        else:
            rows, chosen = np.stack(r.logits), r.tokens
        gap_max, gap_sq = correct.logit_gaps(ref_rows, rows)
        widest.append(gap_max)
        squares.append(gap_sq)
        below += [float(a.max() - a[int(t)])
                  for a, t in zip(ref_rows, chosen)]
    var = float(np.concatenate(squares).mean())
    return {"logit_gap_var": var, "served_gap_max": max(below),
            "logit_gap_rms": var ** 0.5,
            "logit_gap_max": float(np.concatenate(widest).max()),
            "tokens_checked": float(len(below))}


if __name__ == "__main__":
    sys.exit(main())
