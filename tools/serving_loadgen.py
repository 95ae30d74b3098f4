"""Load generator for the serving engine: throughput + latency JSONL.

Two workloads:

* **encoder** (default): fixed-shape predict requests through the
  dynamic batcher (`kind="serving_loadgen"` records).
* **generation** (--generate): autoregressive decode requests with
  mixed prompt lengths and staggered admission through the
  continuous-batching `GenerationEngine`
  (`kind="generation_loadgen"` records carrying tokens/s, TTFT and
  inter-token latency percentiles). --compare-serial replays the same
  request set through serial per-request `gpt.kv_generate` — the
  throughput floor continuous batching must beat AND the exact-answer
  reference every engine output is verified against (exit 4 on
  mismatch). --shared-prefix-frac makes that fraction of requests open
  with one fixed whole-block prefix: the record gains a "prefix"
  object splitting TTFT hit-vs-miss and snapshotting the paged KV
  pool; --block-size sets the KV block size;
  --temperature applies one sampling temperature to every request
  (engine, HTTP and serial paths alike — parity holds at any value).
  --spec-decode switches to the speculative-decoding A/B
  (`kind="spec_loadgen"`): a spec-on and a spec-off engine run the
  same repetitive cyclic-successor traffic over briefly-trained
  weights, the record carries acceptance rate, effective tokens/step
  and the on/off tokens-per-second speedup, and every spec-on output
  is verified against serial kv_generate (exit 4 on divergence).

Two targets:

* **in-process** (default): builds a tiny CPU model (or loads
  --model-dir), starts a warmed ServingEngine, and drives it directly —
  the CPU smoke bench behind the acceptance criteria (zero post-warmup
  compiles; batched > serial throughput).
* **HTTP** (--url): POSTs /v1/predict (or /v1/generate with
  --generate) at an already-running front end.

Two arrival disciplines:

* **closed loop** (default): --concurrency workers each keep exactly one
  request in flight (classic closed-loop load; throughput is
  concurrency / mean latency).
* **open loop** (--rate R): requests are launched on a fixed-rate
  schedule regardless of completions, the discipline that actually
  exposes queueing collapse (rejections surface as `errors`).

Each run appends one `{"kind": "serving_loadgen", ...}` record to --out
(JSONL, schema enforced by tools/validate_bench_json.py) and prints it;
tools/metrics_report.py renders these records as a serving section.
--compare-serial additionally runs the same request set through a bare
single-request predictor and emits a second record (mode
"serial_baseline") plus a speedup line. --check-compiles asserts the
executor cache-miss counter stayed flat after warmup (exit 3 when it
moved).

--trace (generation, in-process only) arms FLAGS_enable_trace at 100%
sampling, wraps every request in a root span, dumps the kept spans to
--trace-out (JSONL) and ASSERTS the trace trees are complete: every
request must carry queue/prefill/decode/fetch child spans, the
critical-path components must sum to within 10% of the measured e2e,
and the parent/child consistency audit must be clean — exit 6 on any
violation. The record gains a "trace" object and the span dump feeds
tools/trace_report.py.

--chaos is the resilience acceptance run (`kind="chaos_loadgen"`
records): a fault-free baseline pass pins per-request expected outputs
and the fault-free p99, then the same traffic replays with
FLAGS_fault_spec armed (--fault-spec). Every 200 is numerically
verified against the baseline; exit 4 on any wrong answer or engine
worker death, exit 5 when the chaos p99 exceeds --chaos-p99-bound
times the fault-free p99.

Usage:
    python tools/serving_loadgen.py --requests 200 --concurrency 8 \
        --compare-serial --check-compiles --out loadgen.jsonl
    python tools/serving_loadgen.py --url http://127.0.0.1:8000 \
        --rate 50 --duration 10
    python tools/serving_loadgen.py --generate --requests 24 \
        --slots 4 --max-new-tokens 8 --compare-serial --check-compiles
    python tools/serving_loadgen.py --generate --spec-decode \
        --spec-k 8 --requests 64 --slots 4 --vocab 8 --max-seq 128 \
        --max-prompt 8 --max-new-tokens 96 --check-compiles \
        --out spec.jsonl
    python tools/serving_loadgen.py --chaos --requests 100 \
        --fault-spec "transient_fail:p=0.05,step_nan:p=0.01"
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _percentile(sorted_ms, q):
    if not sorted_ms:
        return None
    i = min(len(sorted_ms) - 1, max(0, int(q * len(sorted_ms)) - 1))
    return round(sorted_ms[i], 3)


def summarize(kind_mode, latencies_s, errors, duration_s, config):
    lat = sorted(v * 1e3 for v in latencies_s)
    n = len(lat)
    return {
        "kind": "serving_loadgen",
        "mode": kind_mode,
        "requests": n,
        "errors": errors,
        "duration_s": round(duration_s, 4),
        "throughput_rps": round(n / duration_s, 2) if duration_s else 0.0,
        "latency_ms": {
            "mean": round(sum(lat) / n, 3) if n else None,
            "p50": _percentile(lat, 0.50),
            "p95": _percentile(lat, 0.95),
            "p99": _percentile(lat, 0.99),
            "max": round(lat[-1], 3) if n else None,
        },
        "config": config,
    }


def build_tiny_model(tmpdir, feat=6):
    """Save the classifier the serving tests use: x[b, t, feat] ->
    reduce_sum over t -> fc -> softmax (seq-pad invariant, so bucket
    padding is checkable against unpadded references)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, -1, feat], dtype="float32",
                        append_batch_size=False)
        s = layers.reduce_sum(x, dim=1)
        h = layers.fc(s, size=16, act="relu")
        pred = layers.fc(h, size=4, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(tmpdir, ["x"], [pred], exe,
                                      main_program=main)
    return tmpdir


def make_requests(n, seq_buckets, feat, seed=0):
    """Mixed-shape single-row requests with lengths drawn from the
    bucket ladder's covered range."""
    rng = np.random.RandomState(seed)
    hi = max(seq_buckets)
    return [{"x": rng.randn(1, int(rng.randint(1, hi + 1)),
                            feat).astype(np.float32)}
            for _ in range(n)]


class _EngineTarget:
    def __init__(self, engine):
        self.engine = engine

    def call(self, feed, timeout_ms):
        self.engine.predict(feed, timeout_ms=timeout_ms)


class _HTTPTarget:
    def __init__(self, url):
        self.url = url.rstrip("/")

    def call(self, feed, timeout_ms):
        import urllib.request
        body = json.dumps(
            {"inputs": {k: v.tolist() for k, v in feed.items()},
             "timeout_ms": timeout_ms}).encode()
        req = urllib.request.Request(
            self.url + "/v1/predict", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()


def run_closed(target, requests, concurrency, timeout_ms):
    latencies, errors = [], [0]
    lock = threading.Lock()
    it = iter(requests)

    def worker():
        while True:
            with lock:
                feed = next(it, None)
            if feed is None:
                return
            t0 = time.perf_counter()
            try:
                target.call(feed, timeout_ms)
                dt = time.perf_counter() - t0
                with lock:
                    latencies.append(dt)
            except Exception:  # noqa: BLE001 — rejected/timed-out
                with lock:     # requests are the load signal, not a bug
                    errors[0] += 1

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return latencies, errors[0], time.perf_counter() - t0


def run_open(target, requests, rate, timeout_ms):
    """Fixed-rate arrivals: every 1/rate seconds a new request launches
    on its own thread whether or not earlier ones finished."""
    latencies, errors = [], [0]
    lock = threading.Lock()
    threads = []

    def one(feed):
        t0 = time.perf_counter()
        try:
            target.call(feed, timeout_ms)
            dt = time.perf_counter() - t0
            with lock:
                latencies.append(dt)
        except Exception:  # noqa: BLE001
            with lock:
                errors[0] += 1

    interval = 1.0 / rate
    t_start = time.perf_counter()
    for i, feed in enumerate(requests):
        due = t_start + i * interval
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=one, args=(feed,))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    return latencies, errors[0], time.perf_counter() - t_start


def run_serial_baseline(predictor, requests):
    """Single-request dispatch, no batching — the throughput floor the
    batched engine must beat."""
    latencies = []
    t0 = time.perf_counter()
    for feed in requests:
        t1 = time.perf_counter()
        predictor.run_dict(feed)
        latencies.append(time.perf_counter() - t1)
    return latencies, 0, time.perf_counter() - t0


def _lat_summary(values_s):
    """{"mean", "p50", "p95", "p99", "max"} in ms (None when empty)."""
    lat = sorted(v * 1e3 for v in values_s)
    n = len(lat)
    return {
        "mean": round(sum(lat) / n, 3) if n else None,
        "p50": _percentile(lat, 0.50),
        "p95": _percentile(lat, 0.95),
        "p99": _percentile(lat, 0.99),
        "max": round(lat[-1], 3) if n else None,
    }


def summarize_generation(mode, latencies_s, ttfts_s, inter_s, tokens,
                         errors, duration_s, config):
    """One kind="generation_loadgen" record (schema enforced by
    tools/validate_bench_json.py)."""
    n = len(latencies_s)
    return {
        "kind": "generation_loadgen",
        "mode": mode,
        "requests": n,
        "errors": errors,
        "duration_s": round(duration_s, 4),
        "throughput_rps": round(n / duration_s, 2) if duration_s
        else 0.0,
        "tokens": int(tokens),
        "tokens_per_s": round(tokens / duration_s, 2) if duration_s
        else 0.0,
        "latency_ms": _lat_summary(latencies_s),
        "ttft_ms": _lat_summary(ttfts_s),
        "inter_token_ms": _lat_summary(inter_s),
        "config": config,
    }


def make_gen_requests(n, vocab, max_prompt, max_new_tokens, seed=0,
                      shared_prefix_frac=0.0, shared_prefix_len=0,
                      temperature=0.0):
    """Mixed prompt lengths in [1, max_prompt] — with staggered
    admission this is exactly the traffic that would recompile a
    shape-naive decode path.

    `shared_prefix_frac` of the requests open with one fixed
    `shared_prefix_len`-token prefix (the shared-system-prompt shape of
    real LLM traffic): the prefix-cache workload. Each request carries
    `"shared": bool` so the report can split TTFT by cohort even when
    the engine under test has no cache to report hits from.

    `temperature` rides on every request (engine, HTTP and serial-
    reference paths all honor it): with the per-request seed, sampled
    runs stay reproducible AND --compare-serial stays meaningful at
    temperature > 0 — both paths draw through the same
    models/sampling.py rng discipline."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, size=max(int(shared_prefix_len),
                                            0)).tolist()
    out = []
    for i in range(n):
        shared = bool(prefix) and shared_prefix_frac > 0 \
            and rng.random_sample() < shared_prefix_frac
        if shared:
            tail = rng.randint(0, vocab, size=rng.randint(
                1, max(2, max_prompt - len(prefix) + 1))).tolist()
            prompt = prefix + tail
        else:
            prompt = rng.randint(0, vocab, size=rng.randint(
                1, max_prompt + 1)).tolist()
        out.append({"prompt": prompt,
                    "max_new_tokens": int(max_new_tokens),
                    "seed": int(seed + i), "idx": i, "shared": shared,
                    "temperature": float(temperature)})
    return out


def make_spec_requests(n, vocab, max_prompt, max_new_tokens, seed=0,
                       temperature=0.0):
    """Repetitive generation traffic for the --spec-decode A/B: every
    prompt is a run of the cyclic-successor sequence ((t+1) % vocab
    follows t — the task the spec mode trains its tiny model on), so
    greedy continuations are deterministic and, once the generation
    wraps the vocab cycle, the n-gram drafter's suffix lookup starts
    hitting — the repetition-heavy regime speculative decoding exists
    for. Requests still vary in start token, length and seed so slots
    join/leave the batch staggered."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        s = int(rng.randint(vocab))
        plen = int(rng.randint(2, max_prompt + 1))
        prompt = [(s + j) % vocab for j in range(plen)]
        out.append({"prompt": prompt,
                    "max_new_tokens": int(max_new_tokens),
                    "seed": int(seed + i), "idx": i, "shared": False,
                    "temperature": float(temperature)})
    return out


class _GenStats:
    """Thread-safe TTFT / inter-token / token-count accumulators shared
    by the per-request calls of one run."""

    def __init__(self):
        self.lock = threading.Lock()
        self.ttfts = []
        self.inter = []
        self.tokens = 0
        # prefix-cache probe: TTFT split by whether the engine reported
        # cached prompt tokens, plus per-request outputs keyed by the
        # request's idx for the wrong-answers check vs the serial ref
        self.ttft_hit = []
        self.ttft_miss = []
        self.hits = 0
        self.misses = 0
        self.outputs = {}

    def record(self, t_submit, token_times, n_tokens):
        with self.lock:
            if token_times:
                self.ttfts.append(token_times[0] - t_submit)
                self.inter.extend(b - a for a, b in
                                  zip(token_times, token_times[1:]))
            self.tokens += n_tokens

    def record_prefix(self, t_submit, token_times, cached_tokens,
                      idx=None, tokens=None):
        with self.lock:
            if cached_tokens:
                self.hits += 1
            else:
                self.misses += 1
            if token_times:
                (self.ttft_hit if cached_tokens
                 else self.ttft_miss).append(token_times[0] - t_submit)
            if idx is not None:
                self.outputs[idx] = list(tokens or ())


class _GenEngineTarget:
    """Drives an in-process GenerationEngine; per-token timestamps come
    from the engine's stream_cb. With `traced` each call opens a root
    "request" span (the loadgen stands in for the HTTP front end), so
    the engine's gen.request/queue/prefill/decode spans nest under it
    and the loadgen-measured e2e is the trace's tail-sampling input."""

    def __init__(self, engine, stats, traced=False):
        self.engine = engine
        self.stats = stats
        self.traced = traced

    def call(self, req, timeout_ms):
        from paddle_tpu.serving import GenerationRequest
        times = []
        root = None
        if self.traced:
            from paddle_tpu import trace
            root = trace.start_span("request",
                                    attrs={"idx": req.get("idx")})
        t0 = time.perf_counter()
        try:
            greq = GenerationRequest(
                req["prompt"], req["max_new_tokens"],
                temperature=req.get("temperature", 0.0),
                seed=req["seed"], timeout_ms=timeout_ms,
                spec_decode=req.get("spec_decode"),
                stream_cb=lambda tok: times.append(
                    time.perf_counter()))
            if root is not None:
                from paddle_tpu import trace
                with trace.use_span(root):
                    resp = self.engine.submit(greq)
            else:
                resp = self.engine.submit(greq)
            out = resp.result(
                timeout=(timeout_ms or 30000.0) / 1e3 + 30.0)
        except Exception as e:
            if root is not None:
                from paddle_tpu import trace
                trace.finish_trace(
                    root, error=f"{type(e).__name__}: {e}",
                    e2e_ms=(time.perf_counter() - t0) * 1e3)
            raise
        if root is not None:
            from paddle_tpu import trace
            trace.finish_trace(
                root, e2e_ms=(time.perf_counter() - t0) * 1e3)
        self.stats.record(t0, times, len(out["tokens"]))
        self.stats.record_prefix(t0, times, out.get("cached_tokens", 0),
                                 idx=req.get("idx"),
                                 tokens=out["tokens"])


class _GenHTTPTarget:
    """POSTs /v1/generate; no token stream over plain HTTP, so TTFT
    comes from the engine-reported ttft_ms in the response."""

    def __init__(self, url, stats):
        self.url = url.rstrip("/")
        self.stats = stats

    def call(self, req, timeout_ms):
        import urllib.request
        body = json.dumps({"prompt": req["prompt"],
                           "max_new_tokens": req["max_new_tokens"],
                           "temperature": req.get("temperature", 0.0),
                           "seed": req["seed"],
                           "spec_decode": req.get("spec_decode"),
                           "timeout_ms": timeout_ms}).encode()
        r = urllib.request.Request(
            self.url + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(r, timeout=60) as resp:
            out = json.load(resp)
        with self.stats.lock:
            if out.get("ttft_ms") is not None:
                self.stats.ttfts.append(out["ttft_ms"] / 1e3)
            self.stats.tokens += len(out.get("tokens", ()))


def run_serial_generation(exe, scope, prog, step, reqs):
    """Serial per-request kv_generate over a batch=1 decode graph
    sharing the engine's scope — the no-continuous-batching floor AND
    the exact-answer reference (outputs keyed by request idx)."""
    from paddle_tpu.models import gpt
    stats = _GenStats()
    latencies = []
    outputs = {}
    t0 = time.perf_counter()
    for req in reqs:
        times = []
        t1 = time.perf_counter()
        out = gpt.kv_generate(
            exe, scope, prog, step.token_var, step.logits_var,
            step.cache_names, req["prompt"], req["max_new_tokens"],
            temperature=req.get("temperature", 0.0), seed=req["seed"],
            stream_cb=lambda tok: times.append(time.perf_counter()))
        latencies.append(time.perf_counter() - t1)
        stats.record(t1, times, len(out))
        if "idx" in req:
            outputs[req["idx"]] = list(out)
    return stats, latencies, time.perf_counter() - t0, outputs


_TRACE_PHASES = ("queue", "prefill", "decode", "fetch")


def _check_traces(args, tr_mod):
    """--trace post-run audit: drain the kept-span ring, dump it to
    --trace-out, and verify (a) every ok request trace is COMPLETE
    (queue/prefill/decode/fetch spans all present), (b) the
    critical-path component sum lands within 10% of the measured e2e,
    (c) the parent/child consistency audit is clean. Returns
    (failed, summary-dict for the loadgen record)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_report as trp

    spans = tr_mod.drain_spans()
    out = args.trace_out
    if not out:
        base = args.out or os.path.join(tempfile.gettempdir(),
                                        "serving_loadgen.jsonl")
        out = os.path.splitext(os.path.abspath(base))[0] \
            + ".spans.jsonl"
    try:  # fresh dump per run: trace_report reads whole files
        os.remove(out)
    except OSError:
        pass
    tr_mod.export_jsonl(out, spans)

    by_id, children = trp.build_index(spans)
    roots = [r for r in trp.trace_roots(spans, by_id)
             if r["name"] in trp.REQUEST_ROOTS]
    rows = [trp.analyze_request(r, children) for r in roots]
    checked, violations = trp.check_consistency(spans, children)

    incomplete, crit_bad = [], []
    n_err = 0
    for root, row in zip(roots, rows):
        if row["status"] != "ok":
            n_err += 1  # rejected/timed-out requests legitimately
            continue    # carry partial trees
        names = {s["name"] for s in trp._walk(root, children)}
        missing = [p for p in _TRACE_PHASES if p not in names]
        if missing:
            incomplete.append((row["trace_id"], missing))
            continue
        e2e, crit = row["e2e_ms"], row["critical_path_ms"]
        # The phase spans tile the ENGINE-side request span. A loadgen
        # or HTTP root above it additionally measures the client
        # waiter-thread wakeup delay between engine completion and the
        # caller observing it — time no span can cover — so check the
        # identity against the innermost request-boundary span.
        for s in trp._walk(root, children):
            if s["name"] in trp.REQUEST_ROOTS:
                a = s.get("attrs", {}).get("e2e_ms")
                e2e = float(a) if isinstance(a, (int, float)) \
                    else float(s.get("dur_ms") or e2e)
        # 10% of e2e plus 2ms absolute slack for thread-wakeup jitter
        # on sub-10ms CPU requests
        if abs(e2e - crit) > 0.10 * e2e + 2.0:
            crit_bad.append((row["trace_id"], e2e, crit))

    failed = False
    if not rows:
        print("FAIL: --trace run kept no request traces", file=sys.stderr)
        failed = True
    for tid, missing in incomplete[:10]:
        print(f"FAIL: trace {tid[:8]} incomplete: missing "
              f"{','.join(missing)} span(s)", file=sys.stderr)
    for tid, e2e, crit in crit_bad[:10]:
        print(f"FAIL: trace {tid[:8]} critical path {crit}ms vs e2e "
              f"{e2e}ms (>10% apart)", file=sys.stderr)
    for v in violations[:10]:
        print(f"FAIL: trace consistency: {v}", file=sys.stderr)
    failed = failed or bool(incomplete or crit_bad or violations)

    return failed, {
        "out": out, "spans": len(spans), "requests": len(rows),
        "error_requests": n_err, "incomplete": len(incomplete),
        "crit_path_violations": len(crit_bad),
        "consistency_checked": checked,
        "consistency_violations": len(violations),
    }


def run_generation(args):
    """The --generate workload: continuous-batching engine (or HTTP
    front end) under closed/open-loop generation traffic, optional
    serial kv_generate baseline, optional compile-count gate."""
    prefix_frac = getattr(args, "shared_prefix_frac", 0.0) or 0.0
    prefix_len = getattr(args, "shared_prefix_len", 0) or 0
    block_size = getattr(args, "block_size", 0) or 0
    if prefix_frac > 0 and prefix_len <= 0:
        # auto: largest whole-block prefix that still leaves >= 1
        # uncached prompt token (only FULL blocks are shareable, so the
        # block size itself must fit under max_prompt too)
        if block_size <= 0:
            block_size = min(16, max(args.max_prompt - 1, 1))
        prefix_len = (max(args.max_prompt - 1, 1)
                      // block_size) * block_size
        prefix_len = max(prefix_len, 0)
    temperature = getattr(args, "temperature", 0.0) or 0.0
    reqs = make_gen_requests(args.requests, args.vocab, args.max_prompt,
                             args.max_new_tokens, args.seed,
                             shared_prefix_frac=prefix_frac,
                             shared_prefix_len=prefix_len,
                             temperature=temperature)
    common = {"concurrency": args.concurrency, "rate": args.rate,
              "slots": args.slots, "max_prompt": args.max_prompt,
              "max_new_tokens": args.max_new_tokens,
              "max_seq": args.max_seq, "vocab": args.vocab,
              "temperature": temperature,
              "shared_prefix_frac": prefix_frac,
              "shared_prefix_len": prefix_len}
    if args.trace and args.url:
        print("--trace inspects the in-process span ring; --url is not "
              "supported", file=sys.stderr)
        return 2

    if args.url:
        stats = _GenStats()
        target = _GenHTTPTarget(args.url, stats)
        if args.rate > 0:
            if args.duration > 0:
                reqs = reqs[:max(1, int(args.rate * args.duration))]
            lat, errs, dur = run_open(target, reqs, args.rate,
                                      args.timeout_ms)
            mode = "open"
        else:
            lat, errs, dur = run_closed(target, reqs, args.concurrency,
                                        args.timeout_ms)
            mode = "closed"
        emit(summarize_generation(mode, lat, stats.ttfts, stats.inter,
                                  stats.tokens, errs, dur, common),
             args.out)
        return 0

    import paddle_tpu as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationEngine

    if args.trace:
        from paddle_tpu import trace as _tr
        # 100% head sampling by default: the completeness assertion
        # must see EVERY request's tree, not just the tail-kept ones.
        fluid.set_flags({"FLAGS_enable_trace": True,
                         "FLAGS_trace_sample": args.trace_sample})

    cfg = gpt.gpt_small(vocab_size=args.vocab, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=args.max_seq,
                        dropout=0.0, use_flash=False)
    scope = fluid.Scope()
    engine = GenerationEngine(cfg, scope, max_slots=args.slots,
                              max_seq=args.max_seq,
                              default_timeout_ms=args.timeout_ms,
                              block_size=block_size or None)
    engine.init_scope()   # scratch weights: loadgen measures the
    engine.start()        # serving path, not model quality
    misses_after_warmup = engine.cache_stats()["misses"]
    if args.trace:
        _tr.reset()  # drop any warmup-era spans: the dump must hold
        # exactly the measured run's traces

    stats = _GenStats()
    target = _GenEngineTarget(engine, stats, traced=args.trace)
    if args.rate > 0:
        if args.duration > 0:
            reqs = reqs[:max(1, int(args.rate * args.duration))]
        lat, errs, dur = run_open(target, reqs, args.rate,
                                  args.timeout_ms)
        mode = "open"
    else:
        lat, errs, dur = run_closed(target, reqs, args.concurrency,
                                    args.timeout_ms)
        mode = "closed"
    rec = summarize_generation(mode, lat, stats.ttfts, stats.inter,
                               stats.tokens, errs, dur, common)
    post = engine.post_warmup_compiles()
    rec["cache"] = {"misses_after_warmup": misses_after_warmup,
                    "misses_total": engine.cache_stats()["misses"],
                    "post_warmup_compiles": post}
    total = stats.hits + stats.misses
    rec["prefix"] = {
        "shared_prefix_frac": prefix_frac,
        "shared_prefix_len": prefix_len,
        "hit_requests": stats.hits,
        "miss_requests": stats.misses,
        "hit_rate": round(stats.hits / total, 4) if total else None,
        "ttft_hit_ms": _lat_summary(stats.ttft_hit),
        "ttft_miss_ms": _lat_summary(stats.ttft_miss),
        "kv": engine.kv_block_stats(),
    }
    trace_fail = False
    if args.trace:
        trace_fail, rec["trace"] = _check_traces(args, _tr)
    emit(rec, args.out)

    if args.compare_serial:
        # batch=1 decode graph, default (unprefixed) state names: no
        # collision with the engine's "gen." state, weights shared
        dec_main, dec_start = fluid.Program(), fluid.Program()
        with fluid.program_guard(dec_main, dec_start):
            step1 = gpt.build_decode_step(cfg, batch=1,
                                          max_seq=args.max_seq)
        sstats, slat, sdur, souts = run_serial_generation(
            engine.exe, scope, dec_main, step1, reqs)
        srec = summarize_generation(
            "serial_baseline", slat, sstats.ttfts, sstats.inter,
            sstats.tokens, 0, sdur, common)
        wrong = sum(
            1 for i, toks in souts.items()
            if i in stats.outputs
            and [int(t) for t in stats.outputs[i]]
            != [int(t) for t in toks])
        srec["wrong_answers"] = wrong
        srec["compared_requests"] = sum(
            1 for i in souts if i in stats.outputs)
        emit(srec, args.out)
        if wrong:
            print(f"FAIL: {wrong} engine outputs diverge from the "
                  f"serial reference", file=sys.stderr)
            engine.stop()
            return 4
        if srec["tokens_per_s"]:
            speedup = rec["tokens_per_s"] / srec["tokens_per_s"]
            print(f"# continuous/serial tokens-per-second speedup: "
                  f"{speedup:.2f}x")

    engine.stop()
    if args.check_compiles and post > 0:
        print(f"FAIL: {post} compiles after generation warmup",
              file=sys.stderr)
        return 3
    if trace_fail:
        return 6
    return 0


def run_spec_generation(args):
    """--generate --spec-decode: the speculative-decoding A/B.

    Trains the tiny GPT on the cyclic-successor task first (seconds on
    CPU; greedy continuations become deterministic), then drives the
    SAME repetitive closed-loop traffic (make_spec_requests) through a
    spec-ON and a spec-OFF paged engine sharing the trained weights,
    and finally replays every request through the serial kv_generate
    reference for the exact-answer check. Emits one
    kind="spec_loadgen" record (schema: tools/validate_bench_json.py)
    carrying acceptance rate, effective tokens/step and the on/off
    tokens-per-second speedup. Exit 4 when any spec-on output diverges
    from the serial reference; 3 (--check-compiles) when either engine
    compiled anything post-warmup."""
    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationEngine

    if args.url or args.rate > 0 or args.trace:
        print("--spec-decode is an in-process closed-loop A/B; "
              "--url/--rate/--trace are not supported", file=sys.stderr)
        return 2
    temperature = getattr(args, "temperature", 0.0) or 0.0
    vocab = args.vocab
    spec_k = args.spec_k if args.spec_k > 0 \
        else int(fluid.FLAGS.spec_decode_k)
    spec_ngram = int(fluid.FLAGS.spec_decode_ngram)
    cfg = gpt.gpt_small(vocab_size=vocab, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=args.max_seq,
                        dropout=0.0, use_flash=False)
    scope = fluid.Scope()
    train_main, train_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(train_main, train_start), \
            fluid.scope_guard(scope):
        # train at the FULL decode length: every positional-embedding
        # row a generation can reach must learn the task, or greedy
        # continuations drift off the cycle past the trained horizon
        # (tanking draft acceptance for long requests)
        t_seq = int(args.max_seq)
        loss, _, _ = gpt.build_train(cfg, batch=8, seq_len=t_seq,
                                     lr=5e-3)
        exe = fluid.Executor()
        exe.run(train_start)
        base = np.arange(t_seq) % vocab
        toks = np.stack([(base + i) % vocab
                         for i in range(8)]).astype(np.int64)
        for _ in range(40):
            exe.run(train_main, feed={"tokens": toks},
                    fetch_list=[loss])

    reqs = make_spec_requests(args.requests, vocab, args.max_prompt,
                              args.max_new_tokens, args.seed,
                              temperature=temperature)
    fluid.set_flags({"FLAGS_enable_monitor": True})

    def one_run(spec_on):
        monitor.STAT_RESET()
        eng = GenerationEngine(
            cfg, scope, exe=fluid.Executor(), max_slots=args.slots,
            max_seq=args.max_seq, default_timeout_ms=args.timeout_ms,
            block_size=(getattr(args, "block_size", 0) or None),
            spec_decode=spec_on, spec_k=spec_k)
        eng.start()
        stats = _GenStats()
        target = _GenEngineTarget(eng, stats)
        lat, errs, dur = run_closed(target, reqs, args.concurrency,
                                    args.timeout_ms)
        c = monitor.get_stats_snapshot()["counters"]
        post = eng.post_warmup_compiles()
        eng.stop()
        steps = int(c.get("serving.gen_steps", 0))
        side = {
            "duration_s": round(dur, 4),
            "errors": errs,
            "tokens": int(stats.tokens),
            "tokens_per_s": round(stats.tokens / dur, 2) if dur
            else 0.0,
            "gen_steps": steps,
            # batch-level: generated tokens per decode dispatch (> 1
            # needs either multi-slot occupancy or accepted drafts)
            "tokens_per_step": round(stats.tokens / steps, 3)
            if steps else None,
            "latency_ms": _lat_summary(lat),
            "post_warmup_compiles": post,
        }
        if spec_on:
            prop = int(c.get("serving.gen_spec_draft_proposed", 0))
            acc = int(c.get("serving.gen_spec_draft_accepted", 0))
            side.update({
                "spec_steps": int(c.get("serving.gen_spec_steps", 0)),
                "draft_proposed": prop,
                "draft_accepted": acc,
                "acceptance_rate": round(acc / prop, 4) if prop
                else None,
            })
        return side, stats

    base_side, _ = one_run(False)
    spec_side, spec_stats = one_run(True)

    # exact-answer reference: serial kv_generate over the same trained
    # weights (unprefixed batch=1 graph, no collision with gen. state)
    dec_main, dec_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_start):
        step1 = gpt.build_decode_step(cfg, batch=1,
                                      max_seq=args.max_seq)
    _, _, _, souts = run_serial_generation(
        fluid.Executor(), scope, dec_main, step1, reqs)
    wrong = sum(1 for i, toks in souts.items()
                if i in spec_stats.outputs
                and [int(t) for t in spec_stats.outputs[i]]
                != [int(t) for t in toks])
    compared = sum(1 for i in souts if i in spec_stats.outputs)

    off_tps = base_side["tokens_per_s"]
    rec = {
        "kind": "spec_loadgen",
        "mode": "closed",
        "requests": len(reqs),
        "wrong_answers": wrong,
        "compared_requests": compared,
        "speedup": round(spec_side["tokens_per_s"] / off_tps, 3)
        if off_tps else None,
        "spec": spec_side,
        "baseline": base_side,
        "config": {"concurrency": args.concurrency,
                   "slots": args.slots,
                   "max_prompt": args.max_prompt,
                   "max_new_tokens": args.max_new_tokens,
                   "max_seq": args.max_seq, "vocab": vocab,
                   "temperature": temperature,
                   "spec_k": spec_k, "spec_ngram": spec_ngram},
    }
    emit(rec, args.out)
    if wrong:
        print(f"FAIL: {wrong} spec-on outputs diverge from the serial "
              f"reference", file=sys.stderr)
        return 4
    post = (spec_side["post_warmup_compiles"]
            + base_side["post_warmup_compiles"])
    if args.check_compiles and post > 0:
        print(f"FAIL: {post} compiles after spec A/B warmup",
              file=sys.stderr)
        return 3
    return 0


def _chaos_retryable(e):
    from paddle_tpu.serving import OverloadedError, QueueFullError
    return isinstance(e, (OverloadedError, QueueFullError,
                          ConnectionError))


def run_chaos_closed(engine, requests, expected, concurrency,
                     timeout_ms, retries=0, call=None):
    """Closed-loop pass that also VERIFIES every successful response
    against the fault-free expected outputs: under chaos a request may
    fail (shed, timed out — that is degradation, allowed and counted)
    but a 200 carrying wrong numbers is a correctness bug (counted
    separately, never allowed).

    Accounting is by VERDICT, exactly one per request index: a request
    that sheds on one attempt and answers on a later one (client retry
    here, or router failover behind `call`) counts once, with its final
    outcome — never as both an error and an answer.

    `call(feed, timeout_ms) -> [arrays]` overrides the engine dispatch
    (the router mode routes through Router.predict); `retries` bounds
    client-side re-submissions after a retryable rejection."""
    verdicts = {}          # idx -> ("ok"|"wrong", latency_s) | ("error", None)
    lock = threading.Lock()
    it = iter(list(enumerate(requests)))
    if call is None:
        def call(feed, t):  # noqa: E306
            return engine.predict(feed, timeout_ms=t)

    def worker():
        while True:
            with lock:
                item = next(it, None)
            if item is None:
                return
            idx, feed = item
            t0 = time.perf_counter()
            outs = None
            attempt = 0
            while True:
                try:
                    outs = call(feed, timeout_ms)
                    break
                except Exception as e:  # noqa: BLE001 — shed/timeout
                    if attempt < retries and _chaos_retryable(e):
                        attempt += 1
                        time.sleep(0.01 * attempt)
                        continue
                    break
            if outs is None:
                with lock:
                    verdicts[idx] = ("error", None)
                continue
            dt = time.perf_counter() - t0
            ok = len(outs) == len(expected[idx]) and all(
                np.allclose(o, e, rtol=1e-4, atol=1e-5)
                for o, e in zip(outs, expected[idx]))
            with lock:
                verdicts[idx] = ("ok" if ok else "wrong", dt)

    threads = [threading.Thread(target=worker)
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dur = time.perf_counter() - t0
    latencies = [v[1] for v in verdicts.values() if v[1] is not None]
    errors = sum(1 for v in verdicts.values() if v[0] == "error")
    wrong = sum(1 for v in verdicts.values() if v[0] == "wrong")
    return latencies, errors, wrong, dur


def run_chaos(args):
    """--chaos: the graceful-degradation acceptance run. Baseline pass
    (faults off) for expected outputs + fault-free p99, then the same
    traffic with FLAGS_fault_spec armed. Exit 4 on any wrong answer or
    worker death, 5 when chaos p99 exceeds --chaos-p99-bound x the
    fault-free p99."""
    import paddle_tpu as fluid
    from paddle_tpu.resilience import reset_injector
    from paddle_tpu.serving import EngineConfig, ServingEngine

    if args.url:
        print("--chaos drives an in-process engine; --url is not "
              "supported", file=sys.stderr)
        return 2

    seq_buckets = tuple(int(s) for s in args.seq_buckets.split(","))
    feat = 6
    reqs = make_requests(args.requests, seq_buckets, feat, args.seed)

    fluid.set_flags({"FLAGS_fault_spec": ""})
    reset_injector()
    model_dir = args.model_dir or build_tiny_model(
        tempfile.mkdtemp(prefix="serving_chaos_"), feat)
    cfg = EngineConfig(model_dir,
                       max_batch_size=args.max_batch_size,
                       max_wait_us=args.max_wait_us,
                       queue_capacity=max(64, args.concurrency * 8),
                       default_timeout_ms=args.timeout_ms,
                       seq_buckets=seq_buckets,
                       warmup=True)
    engine = ServingEngine(cfg)
    engine.start()

    # fault-free ground truth, one request at a time (no batching
    # effects), through a predictor clone sharing the weights
    ref = engine.predictor.clone()
    expected = [ref.run_dict(feed) for feed in reqs]

    base_lat, base_errs, base_wrong, base_dur = run_chaos_closed(
        engine, reqs, expected, args.concurrency, args.timeout_ms)
    base_p99 = _percentile(sorted(v * 1e3 for v in base_lat), 0.99)

    fluid.set_flags({"FLAGS_fault_spec": args.fault_spec,
                     "FLAGS_fault_seed": args.seed})
    reset_injector()
    lat, errs, wrong, dur = run_chaos_closed(
        engine, reqs, expected, args.concurrency, args.timeout_ms)
    worker_deaths = sum(1 for w in engine._workers if not w.is_alive())
    fluid.set_flags({"FLAGS_fault_spec": ""})
    reset_injector()
    engine.stop()

    chaos_p99 = _percentile(sorted(v * 1e3 for v in lat), 0.99)
    inflation = (round(chaos_p99 / base_p99, 3)
                 if base_p99 and chaos_p99 else None)
    n = len(lat)
    rec = {
        "kind": "chaos_loadgen",
        "mode": "chaos",
        "requests": n,
        "errors": errs,
        "duration_s": round(dur, 4),
        "throughput_rps": round(n / dur, 2) if dur else 0.0,
        "latency_ms": _lat_summary(lat),
        "fault_spec": args.fault_spec,
        "wrong_answers": wrong + base_wrong,
        "worker_deaths": worker_deaths,
        "baseline_p99_ms": base_p99,
        "chaos_p99_ms": chaos_p99,
        "p99_inflation": inflation,
        "p99_bound": args.chaos_p99_bound,
        "config": {"concurrency": args.concurrency,
                   "max_batch_size": args.max_batch_size,
                   "max_wait_us": args.max_wait_us,
                   "seq_buckets": list(seq_buckets),
                   "baseline_errors": base_errs,
                   "seed": args.seed},
    }
    emit(rec, args.out)

    if rec["wrong_answers"] or worker_deaths:
        print(f"FAIL: {rec['wrong_answers']} wrong answers, "
              f"{worker_deaths} worker deaths under chaos",
              file=sys.stderr)
        return 4
    if inflation is not None and inflation > args.chaos_p99_bound:
        print(f"FAIL: chaos p99 {chaos_p99}ms is {inflation}x the "
              f"fault-free p99 {base_p99}ms (bound "
              f"{args.chaos_p99_bound}x)", file=sys.stderr)
        return 5
    return 0


def run_router(args):
    """--router N: the multi-replica acceptance run
    (`kind="router_loadgen"` records). N warmed in-process replicas go
    behind the serving Router; the run measures closed-loop throughput
    with 1 registered replica then with all N (the ~linear-scaling
    smoke — a deterministic per-batch service time injected via
    `slow_step` makes the ratio machine-independent), and optionally:

    * --preempt-drill: deregister+resume one replica mid-load; any
      client-visible error while another replica is healthy fails the
      run (exit 4).
    * --hot-swap: warm a v2 standby under load, flip, drain v1 —
      zero dropped requests and zero standby post-warmup compiles or
      exit 4.
    * --chaos: hard-kill one replica mid-pass (stop(drain=False), no
      drain) and rely on failover; wrong answers or non-victim worker
      deaths exit 4, p99 over --chaos-p99-bound x the fault-free p99
      exits 5.

    Every response in every pass is verified against fault-free
    expected outputs with exactly-once per-request verdicts. Exit 7
    when the 1->N throughput ratio lands below --scaling-min (> 0)."""
    import paddle_tpu as fluid
    from paddle_tpu.resilience import reset_injector
    from paddle_tpu.serving import (EngineConfig, Replica, Router,
                                    ServingEngine)

    if args.url or args.generate:
        print("--router drives in-process predict replicas; --url and "
              "--generate are not supported", file=sys.stderr)
        return 2
    n_rep = args.router
    seq_buckets = tuple(int(s) for s in args.seq_buckets.split(","))
    feat = 6
    reqs = make_requests(args.requests, seq_buckets, feat, args.seed)
    # closed-loop scaling needs every replica's queue deep enough to
    # fill batches in EACH of the ~3 shape-signature groups the mixed
    # seq lengths land in, even after the load splits N ways
    conc = max(args.concurrency,
               4 * n_rep * args.max_batch_size + n_rep)

    fluid.set_flags({"FLAGS_fault_spec": ""})
    reset_injector()
    model_dir = args.model_dir or build_tiny_model(
        tempfile.mkdtemp(prefix="serving_router_"), feat)
    all_engines = []

    def make_engine(start=True):
        cfg = EngineConfig(model_dir,
                           max_batch_size=args.max_batch_size,
                           max_wait_us=args.max_wait_us,
                           queue_capacity=max(64, conc * 8),
                           default_timeout_ms=args.timeout_ms,
                           seq_buckets=seq_buckets,
                           warmup=True)
        e = ServingEngine(cfg)
        if start:
            e.start()
        all_engines.append(e)
        return e

    engines = [make_engine() for _ in range(n_rep)]
    names = engines[0].output_names()
    # fault-free ground truth: every replica loads the same saved
    # weights, so one clone references them all
    ref = engines[0].predictor.clone()
    expected = [ref.run_dict(feed) for feed in reqs]

    if args.service_ms > 0:
        # deterministic per-batch service time: slow_step with no p=
        # fires on EVERY batch at the "serving" fault site, sleeping
        # inside each engine's infer lock — so service parallelizes
        # across replicas and the 1->N ratio is machine-independent
        fluid.set_flags(
            {"FLAGS_fault_spec":
             f"slow_step:ms={args.service_ms}:site=serving"})
        reset_injector()

    replicas = [Replica(f"r{i}", engine=e, version="v1")
                for i, e in enumerate(engines)]

    def router_call(router):
        def call(feed, t):
            outs = router.predict(feed, timeout_ms=t)
            return [outs[n] for n in names]
        return call

    # -- pass 1: one registered replica (the scaling denominator) ------
    r1 = Router([replicas[0]], start_probe=False)
    lat1, err1, wrong1, dur1 = run_chaos_closed(
        None, reqs, expected, conc, args.timeout_ms,
        retries=2, call=router_call(r1))
    r1.close()
    rps_1 = round(len(lat1) / dur1, 2) if dur1 else 0.0

    # -- pass 2: all N replicas (the main record + chaos baseline) -----
    router = Router(replicas, probe_interval_s=0.2)
    call_n = router_call(router)
    lat_n, err_n, wrong_n, dur_n = run_chaos_closed(
        None, reqs, expected, conc, args.timeout_ms,
        retries=2, call=call_n)
    rps_n = round(len(lat_n) / dur_n, 2) if dur_n else 0.0
    ratio = round(rps_n / rps_1, 3) if rps_1 else None

    wrong_total = wrong1 + wrong_n
    hard_fail = []

    # -- preemption drill ----------------------------------------------
    preempt_rec = None
    if args.preempt_drill and n_rep >= 2:
        res = {}

        def _pload():
            res["r"] = run_chaos_closed(
                None, reqs, expected, conc, args.timeout_ms,
                retries=2, call=call_n)

        th = threading.Thread(target=_pload)
        th.start()
        time.sleep(max(0.05, dur_n * 0.25))
        router.preempt("r1")
        time.sleep(max(0.05, dur_n * 0.25))
        router.resume("r1")
        th.join()
        _, errs_p, wrong_p, _ = res["r"]
        wrong_total += wrong_p
        preempt_rec = {"replica": "r1", "client_errors": errs_p,
                       "wrong_answers": wrong_p, "resumed": True}
        if errs_p or wrong_p:
            hard_fail.append(
                f"preempt drill: {errs_p} client errors / {wrong_p} "
                f"wrong answers while other replicas were healthy")

    # -- hot-swap drill ------------------------------------------------
    hot_rec = None
    if args.hot_swap:
        stop_evt = threading.Event()
        lock = threading.Lock()
        counter, totals, bad = [0], [0], [0]

        def _hs_worker():
            while not stop_evt.is_set():
                with lock:
                    idx = counter[0] % len(reqs)
                    counter[0] += 1
                try:
                    outs = call_n(reqs[idx], args.timeout_ms)
                    ok = len(outs) == len(expected[idx]) and all(
                        np.allclose(o, e, rtol=1e-4, atol=1e-5)
                        for o, e in zip(outs, expected[idx]))
                except Exception:  # noqa: BLE001
                    ok = False
                with lock:
                    totals[0] += 1
                    if not ok:
                        bad[0] += 1

        workers = [threading.Thread(target=_hs_worker)
                   for _ in range(conc)]
        for w in workers:
            w.start()
        # standby warms its full ladder here, WHILE v1 keeps serving
        standby = Replica("r0v2", engine=make_engine(start=False),
                          version="v2")
        swap = router.hot_swap("r0", standby)
        time.sleep(max(0.1, dur_n * 0.25))  # post-flip load on v2
        stop_evt.set()
        for w in workers:
            w.join()
        standby_compiles = standby.post_warmup_compiles()
        hot_rec = {"swapped": bool(swap["swapped"]),
                   "old": swap["old"], "new": swap["new"],
                   "requests": totals[0],
                   "dropped_requests": bad[0],
                   "drained": bool(swap["drained"]),
                   "standby_post_warmup_compiles": standby_compiles}
        if bad[0]:
            hard_fail.append(f"hot-swap drill dropped {bad[0]} of "
                             f"{totals[0]} requests")
        if standby_compiles:
            hard_fail.append(f"standby compiled {standby_compiles} "
                             f"time(s) after warmup")
        if not swap["drained"]:
            hard_fail.append("old replica not drained before stop")

    # -- chaos: hard-kill one replica mid-run --------------------------
    chaos_rec = None
    p99_over = False
    if args.chaos:
        base_p99 = _percentile(sorted(v * 1e3 for v in lat_n), 0.99)
        victim = router.replicas()[-1]
        red0 = router.redispatches

        def _killer():
            time.sleep(max(0.05, dur_n * 0.3))
            victim.engine.stop(drain=False)

        kth = threading.Thread(target=_killer)
        kth.start()
        lat_c, err_c, wrong_c, dur_c = run_chaos_closed(
            None, reqs, expected, conc, args.timeout_ms,
            retries=3, call=call_n)
        kth.join()
        wrong_total += wrong_c
        chaos_p99 = _percentile(sorted(v * 1e3 for v in lat_c), 0.99)
        inflation = (round(chaos_p99 / base_p99, 3)
                     if base_p99 and chaos_p99 else None)
        deaths = sum(1 for r in router.replicas() if r is not victim
                     for w in r.engine._workers if not w.is_alive())
        chaos_rec = {"killed_replica": victim.name,
                     "requests": len(lat_c),
                     "client_errors": err_c,
                     "wrong_answers": wrong_c,
                     "worker_deaths": deaths,
                     "redispatches": router.redispatches - red0,
                     "baseline_p99_ms": base_p99,
                     "chaos_p99_ms": chaos_p99,
                     "p99_inflation": inflation,
                     "p99_bound": args.chaos_p99_bound}
        if wrong_c or deaths:
            hard_fail.append(f"chaos: {wrong_c} wrong answers, "
                             f"{deaths} non-victim worker deaths")
        p99_over = inflation is not None \
            and inflation > args.chaos_p99_bound

    fluid.set_flags({"FLAGS_fault_spec": ""})
    reset_injector()
    router.close()
    for e in all_engines:
        try:
            e.stop(drain=False, timeout=5.0)
        except Exception:  # noqa: BLE001 — chaos victims already down
            pass

    rec = {
        "kind": "router_loadgen",
        "mode": "closed",
        "replicas": n_rep,
        "requests": len(lat_n),
        "errors": err_n,
        "wrong_answers": wrong_total,
        "duration_s": round(dur_n, 4),
        "throughput_rps": rps_n,
        "latency_ms": _lat_summary(lat_n),
        "redispatches": router.redispatches,
        "shed": router.shed,
        "scaling": {"rps_1": rps_1, "rps_n": rps_n, "ratio": ratio,
                    "min_ratio": args.scaling_min,
                    "pass1_errors": err1},
        "config": {"concurrency": conc,
                   "max_batch_size": args.max_batch_size,
                   "max_wait_us": args.max_wait_us,
                   "seq_buckets": list(seq_buckets),
                   "service_ms": args.service_ms,
                   "seed": args.seed},
    }
    if preempt_rec:
        rec["preempt"] = preempt_rec
    if hot_rec:
        rec["hot_swap"] = hot_rec
    if chaos_rec:
        rec["chaos"] = chaos_rec
    emit(rec, args.out)

    if wrong_total or hard_fail:
        for msg in hard_fail or [f"{wrong_total} wrong answers"]:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 4
    if p99_over:
        print(f"FAIL: chaos p99 {chaos_rec['chaos_p99_ms']}ms is "
              f"{chaos_rec['p99_inflation']}x the fault-free p99 "
              f"{chaos_rec['baseline_p99_ms']}ms (bound "
              f"{args.chaos_p99_bound}x)", file=sys.stderr)
        return 5
    if args.scaling_min > 0 and (ratio is None
                                 or ratio < args.scaling_min):
        print(f"FAIL: 1->{n_rep} replica throughput ratio {ratio} "
              f"below --scaling-min {args.scaling_min}",
              file=sys.stderr)
        return 7
    return 0


def run_disagg(args):
    """--router N --disagg: the disaggregated prefill/decode fleet
    acceptance run (`kind="disagg_loadgen"` records).

    Two passes over IDENTICAL shared-prefix generation traffic, each
    against a FRESH fleet of N real subprocess replicas
    (tools/serving_replica.py — separate processes, HTTP wire,
    loaded-from-npz identical weights):

    * baseline: N symmetric (role=unified) workers behind a plain
      Router — every worker re-prefills every prefix it meets.
    * disagg: --disagg-prefill prefill workers + the rest decode
      workers behind Router(disagg=True) — prefixes are prefilled
      once, shipped over /v1/kv/export -> /v1/kv/adopt, and reused via
      the fleet prefix store.

    --service-ms injects a deterministic per-prefill-step delay
    (slow_step at the gen_prefill fault site, armed via FLAGS env in
    the worker processes) so the TTFT comparison is
    machine-independent, exactly like the router scaling run. Gates:
    any wrong answer vs the in-process serial reference exits 4; any
    worker post-warmup compile exits 3 (--check-compiles); disagg
    shared-cohort TTFT p99 not beating baseline exits 5 (active at
    shared-prefix-frac >= 0.6); the one-tree trace audit
    (request -> prefill/fetch/decode spans, trace_report consistency)
    exits 6."""
    import shutil
    import signal as _signal
    import subprocess
    import urllib.request

    import paddle_tpu as fluid
    from paddle_tpu import monitor as _mon
    from paddle_tpu import trace as _tr
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationEngine, Replica, Router

    if args.url or args.chaos:
        print("--disagg races local subprocess replicas; --url and "
              "--chaos are not supported", file=sys.stderr)
        return 2
    # This parent builds an engine and the serial reference in-process,
    # so it holds whatever device jax gave it. The workers run on the
    # parent's platform, stated in their environment and in the record.
    # On a TPU that cannot work: a chip belongs to one process at a
    # time, and the children would fail, hang, or quietly come up on
    # the CPU beside a parent on the chip.
    import jax
    worker_platform = jax.default_backend()
    if worker_platform != "cpu":
        print(f"--disagg spawns subprocess replicas, and this parent "
              f"holds the {worker_platform} backend: its children "
              f"cannot claim the same chip. Run the fleet on the CPU "
              f"(JAX_PLATFORMS=cpu); replicas on chips need one chip a "
              f"process and a parent that stays off jax.",
              file=sys.stderr)
        return 2
    n_rep = args.router
    n_p = max(1, args.disagg_prefill)
    n_d = n_rep - n_p
    if n_d < 1:
        print(f"--disagg needs >= 1 decode worker (--router {n_rep} "
              f"--disagg-prefill {n_p})", file=sys.stderr)
        return 2

    block_size = args.block_size or 8
    prefix_frac = args.shared_prefix_frac \
        if args.shared_prefix_frac > 0 else 0.75
    prefix_len = args.shared_prefix_len or (
        (max(args.max_prompt - 1, 1) // block_size) * block_size)
    if prefix_len < block_size:
        print(f"--disagg needs at least one full shared block "
              f"(prefix_len {prefix_len} < block_size {block_size}; "
              f"raise --max-prompt)", file=sys.stderr)
        return 2
    reqs = make_gen_requests(args.requests, args.vocab,
                             args.max_prompt, args.max_new_tokens,
                             args.seed, shared_prefix_frac=prefix_frac,
                             shared_prefix_len=prefix_len,
                             temperature=args.temperature)

    tmpdir = tempfile.mkdtemp(prefix="serving_disagg_")

    # -- the weights every process shares (npz under training-graph
    # names; each replica loads them, so all fleets decode identically)
    cfg = gpt.gpt_small(vocab_size=args.vocab, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=args.max_seq,
                        dropout=0.0, use_flash=False)
    scope = fluid.Scope()
    seed_engine = GenerationEngine(cfg, scope, max_slots=args.slots,
                                   max_seq=args.max_seq,
                                   block_size=block_size)
    seed_engine.init_scope()  # scratch weights; never start()ed
    weights = {}
    for name in scope.names():
        if name.startswith("gen."):
            continue  # decode state is per-process, not a weight
        v = scope.get(name)
        if v is not None:
            weights[name] = np.asarray(v)
    npz = os.path.join(tmpdir, "weights.npz")
    np.savez(npz, **weights)

    # -- serial exact-answer reference (in-process, batch=1 graph on
    # the same scope — the wrong-answers oracle for BOTH passes)
    dec_main, dec_start = fluid.Program(), fluid.Program()
    with fluid.program_guard(dec_main, dec_start):
        step1 = gpt.build_decode_step(cfg, batch=1,
                                      max_seq=args.max_seq)
    _, _, _, souts = run_serial_generation(
        seed_engine.exe, scope, dec_main, step1, reqs)

    replica_py = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "serving_replica.py")
    worker_env = dict(os.environ)
    worker_env["JAX_PLATFORMS"] = worker_platform
    if args.service_ms > 0:
        # deterministic per-prefill-step service time in EVERY worker
        # of BOTH fleets: prefill cost dominates and is identical
        # across machines, so where prefill *runs* (the thing disagg
        # changes) decides the TTFT comparison
        worker_env["FLAGS_fault_spec"] = \
            f"slow_step:ms={args.service_ms}:site=gen_prefill"

    def spawn_fleet(tag, n):
        procs = []
        for i in range(n):
            name = f"{tag}{i}"
            pf = os.path.join(tmpdir, f"{name}.port")
            log = open(os.path.join(tmpdir, f"{name}.log"), "w")
            cmd = [sys.executable, replica_py, "--weights", npz,
                   "--vocab", str(args.vocab),
                   "--max-seq", str(args.max_seq),
                   "--slots", str(args.slots),
                   "--block-size", str(block_size),
                   "--timeout-ms", str(args.timeout_ms),
                   "--port-file", pf]
            p = subprocess.Popen(cmd, stdout=log,
                                 stderr=subprocess.STDOUT,
                                 env=worker_env)
            procs.append({"proc": p, "port_file": pf, "log": log,
                          "name": name})
        deadline = time.monotonic() + 300.0
        for w in procs:
            while not os.path.exists(w["port_file"]):
                if w["proc"].poll() is not None:
                    w["log"].flush()
                    with open(w["log"].name) as lf:
                        tail = "".join(lf.readlines()[-15:])
                    raise RuntimeError(
                        f"replica {w['name']} died during warmup "
                        f"(rc={w['proc'].returncode}):\n{tail}")
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"replica {w['name']} not ready in 300s")
                time.sleep(0.1)
            with open(w["port_file"]) as f:
                w["url"] = f"http://127.0.0.1:{int(f.read().strip())}"
        return procs

    def worker_compiles(url):
        with urllib.request.urlopen(url + "/healthz",
                                    timeout=5.0) as r:
            body = json.loads(r.read() or b"{}")
        return int(body.get("engines", {}).get("generate", {})
                   .get("post_warmup_compiles") or 0)

    def stop_fleet(procs):
        clean = 0
        for w in procs:
            if w["proc"].poll() is None:
                w["proc"].send_signal(_signal.SIGTERM)
        for w in procs:
            try:
                rc = w["proc"].wait(timeout=30)
            except subprocess.TimeoutExpired:
                w["proc"].kill()
                rc = w["proc"].wait()
            if rc == 0:
                clean += 1
            w["log"].close()
        return clean

    def drive(router, traced):
        """Closed loop: --concurrency threads, each one request in
        flight, straight into Router.generate. Client-side TTFT proxy:
        measured e2e minus the engine-reported decode tail, so router
        + transfer overhead lands in TTFT (where it belongs)."""
        pending = list(reqs)
        results = {}
        errors = [0]
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    if not pending:
                        return
                    req = pending.pop(0)
                payload = {"prompt": req["prompt"],
                           "max_new_tokens": req["max_new_tokens"],
                           "temperature": req.get("temperature", 0.0),
                           "seed": req["seed"],
                           "timeout_ms": args.timeout_ms}
                root = None
                t0 = time.perf_counter()
                try:
                    if traced:
                        root = _tr.start_span(
                            "request", attrs={"idx": req["idx"]})
                        with _tr.use_span(root):
                            out = router.generate(payload)
                    else:
                        out = router.generate(payload)
                except Exception as e:  # noqa: BLE001
                    if root is not None:
                        _tr.finish_trace(
                            root, error=f"{type(e).__name__}: {e}",
                            e2e_ms=(time.perf_counter() - t0) * 1e3)
                    with lock:
                        errors[0] += 1
                    continue
                e2e = time.perf_counter() - t0
                if root is not None:
                    _tr.finish_trace(root, e2e_ms=e2e * 1e3)
                eng_e2e = (out.get("e2e_ms") or 0.0) / 1e3
                eng_ttft = (out.get("ttft_ms") or 0.0) / 1e3
                ttft = max(0.0, e2e - max(0.0, eng_e2e - eng_ttft))
                with lock:
                    results[req["idx"]] = {
                        "e2e": e2e, "ttft": ttft,
                        "tokens": list(out.get("tokens", ())),
                        "shared": bool(req["shared"])}

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker)
                   for _ in range(args.concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return results, errors[0], time.perf_counter() - t0

    def wrong_count(results):
        return sum(1 for i, r in results.items()
                   if i in souts and [int(t) for t in r["tokens"]]
                   != [int(t) for t in souts[i]])

    def pass_summary(results, errors, dur):
        vals = list(results.values())
        lat = [r["e2e"] for r in vals]
        tokens = sum(len(r["tokens"]) for r in vals)
        return {
            "requests": len(vals), "errors": errors,
            "duration_s": round(dur, 4),
            "throughput_rps": round(len(vals) / dur, 2) if dur else 0.0,
            "tokens": tokens,
            "tokens_per_s": round(tokens / dur, 2) if dur else 0.0,
            "latency_ms": _lat_summary(lat),
            "ttft_ms": _lat_summary([r["ttft"] for r in vals]),
            "ttft_shared_ms": _lat_summary(
                [r["ttft"] for r in vals if r["shared"]]),
            "ttft_miss_ms": _lat_summary(
                [r["ttft"] for r in vals if not r["shared"]]),
        }

    fleet = []
    try:
        # ---- pass A: symmetric baseline (N unified workers) ----------
        fleet = spawn_fleet("u", n_rep)
        router_a = Router(
            [Replica(w["name"], url=w["url"], role="unified")
             for w in fleet],
            probe_interval_s=0.2, disagg=False)
        res_a, err_a, dur_a = drive(router_a, traced=False)
        compiles_a = sum(worker_compiles(w["url"]) for w in fleet)
        router_a.close()
        clean_a = stop_fleet(fleet)
        wrong_a = wrong_count(res_a)
        base = pass_summary(res_a, err_a, dur_a)
        base["post_warmup_compiles"] = compiles_a
        base["clean_exits"] = clean_a

        # ---- pass B: disaggregated fleet (fresh processes) -----------
        fluid.set_flags({"FLAGS_enable_trace": True,
                         "FLAGS_trace_sample": 1.0,
                         "FLAGS_enable_monitor": True})
        _mon.STAT_RESET()
        _tr.reset()
        fleet = spawn_fleet("p", n_p) + spawn_fleet("d", n_d)
        reps_b = [Replica(w["name"], url=w["url"],
                          role=("prefill" if w["name"].startswith("p")
                                else "decode"))
                  for w in fleet]
        router_b = Router(reps_b, probe_interval_s=0.2, disagg=True)
        res_b, err_b, dur_b = drive(router_b, traced=True)
        counters = _mon.get_stats_snapshot().get("counters", {})
        store_stats = router_b.prefix_store.stats()
        compiles_b = sum(worker_compiles(w["url"]) for w in fleet)
        router_b.close()
        clean_b = stop_fleet(fleet)
        fleet = []
        wrong_b = wrong_count(res_b)
    finally:
        for w in fleet:
            if w["proc"].poll() is None:
                w["proc"].kill()
        shutil.rmtree(tmpdir, ignore_errors=True)

    # ---- trace audit: one tree per request, router->prefill->fetch->
    # decode spans, trace_report consistency clean --------------------
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_report as trp
    spans = _tr.drain_spans()
    trace_out = args.trace_out
    if not trace_out:
        base_p = args.out or os.path.join(tempfile.gettempdir(),
                                          "disagg_loadgen.jsonl")
        trace_out = os.path.splitext(os.path.abspath(base_p))[0] \
            + ".spans.jsonl"
    try:
        os.remove(trace_out)
    except OSError:
        pass
    _tr.export_jsonl(trace_out, spans)
    by_id, children = trp.build_index(spans)
    roots = [r for r in trp.trace_roots(spans, by_id)
             if r["name"] in trp.REQUEST_ROOTS]
    n_no_decode = 0
    n_with_transfer = 0
    for root in roots:
        if root.get("status") != "ok":
            continue
        names = {s["name"] for s in trp._walk(root, children)}
        if "decode" not in names:
            n_no_decode += 1
        if "prefill" in names and "fetch" in names:
            n_with_transfer += 1
    _, violations = trp.check_consistency(spans, children)
    trace_fail = (not roots) or n_no_decode or violations \
        or n_with_transfer == 0
    if not roots:
        print("FAIL: disagg pass kept no request traces",
              file=sys.stderr)
    if n_no_decode:
        print(f"FAIL: {n_no_decode} request trace(s) missing the "
              f"decode span", file=sys.stderr)
    if n_with_transfer == 0 and roots:
        print("FAIL: no request trace carries the prefill+fetch "
              "transfer spans", file=sys.stderr)
    for v in violations[:10]:
        print(f"FAIL: trace consistency: {v}", file=sys.stderr)

    dis = pass_summary(res_b, err_b, dur_b)
    dis["post_warmup_compiles"] = compiles_b
    dis["clean_exits"] = clean_b
    b99 = base["ttft_shared_ms"]["p99"] \
        if base["ttft_shared_ms"] else None
    d99 = dis["ttft_shared_ms"]["p99"] \
        if dis["ttft_shared_ms"] else None
    ratio = round(d99 / b99, 3) if b99 and d99 is not None else None

    rec = {
        "kind": "disagg_loadgen",
        "mode": "closed",
        "replicas": {"prefill": n_p, "decode": n_d,
                     "baseline_unified": n_rep},
        "worker_platform": worker_platform,
        "requests": dis["requests"],
        "errors": err_a + err_b,
        "wrong_answers": wrong_a + wrong_b,
        "duration_s": dis["duration_s"],
        "throughput_rps": dis["throughput_rps"],
        "tokens": dis["tokens"],
        "tokens_per_s": dis["tokens_per_s"],
        "latency_ms": dis["latency_ms"],
        "ttft_ms": dis["ttft_ms"],
        "ttft_shared_ms": dis["ttft_shared_ms"],
        "ttft_miss_ms": dis["ttft_miss_ms"],
        "ttft_shared_p99_ratio": ratio,
        "post_warmup_compiles": compiles_a + compiles_b,
        "baseline": base,
        "transfer": {
            "requests": int(counters.get(
                "serving.disagg_requests", 0)),
            "prefix_reuse": int(counters.get(
                "serving.disagg_prefix_reuse", 0)),
            "fallbacks": int(counters.get(
                "serving.disagg_fallbacks", 0)),
            "blocks": int(counters.get("serving.kv_xfer_blocks", 0)),
            "bytes": int(counters.get("serving.kv_xfer_bytes", 0)),
            "fleet_store": store_stats,
        },
        "trace": {"out": trace_out, "spans": len(spans),
                  "requests": len(roots),
                  "with_transfer": n_with_transfer,
                  "missing_decode": n_no_decode,
                  "consistency_violations": len(violations)},
        "config": {"concurrency": args.concurrency,
                   "slots": args.slots,
                   "max_prompt": args.max_prompt,
                   "max_new_tokens": args.max_new_tokens,
                   "max_seq": args.max_seq, "vocab": args.vocab,
                   "block_size": block_size,
                   "shared_prefix_frac": prefix_frac,
                   "shared_prefix_len": prefix_len,
                   "service_ms": args.service_ms,
                   "seed": args.seed},
    }
    emit(rec, args.out)

    if rec["wrong_answers"]:
        print(f"FAIL: {rec['wrong_answers']} outputs diverge from the "
              f"serial reference", file=sys.stderr)
        return 4
    if args.check_compiles and rec["post_warmup_compiles"]:
        print(f"FAIL: {rec['post_warmup_compiles']} post-warmup "
              f"compiles across the fleets", file=sys.stderr)
        return 3
    if prefix_frac >= 0.6 and b99 and d99 is not None and d99 > b99:
        print(f"FAIL: disagg shared-cohort TTFT p99 {d99}ms does not "
              f"beat the symmetric baseline {b99}ms", file=sys.stderr)
        return 5
    if trace_fail:
        return 6
    return 0


def emit(rec, out_path):
    print(json.dumps(rec))
    if out_path:
        d = os.path.dirname(os.path.abspath(out_path))
        os.makedirs(d, exist_ok=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(rec) + "\n")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", help="drive a running HTTP front end "
                                  "instead of an in-process engine")
    ap.add_argument("--model-dir", help="saved inference model for the "
                                        "in-process engine (default: "
                                        "build a tiny classifier)")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--duration", type=float, default=0.0,
                    help="open-loop only: cap the run; 0 = run the "
                         "request count")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrivals per second (0 = closed "
                         "loop)")
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--max-wait-us", type=int, default=2000)
    ap.add_argument("--seq-buckets", default="8,16,32",
                    help="comma-separated seq bucket ladder")
    ap.add_argument("--timeout-ms", type=float, default=10000.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the bucket-ladder warmup pass (baseline "
                         "for the compile-count comparison)")
    ap.add_argument("--compare-serial", action="store_true")
    ap.add_argument("--check-compiles", action="store_true",
                    help="exit 3 if the engine executor compiled "
                         "anything after warmup")
    ap.add_argument("--out", help="append JSONL records here")
    ap.add_argument("--generate", action="store_true",
                    help="generation workload through the "
                         "continuous-batching GenerationEngine")
    ap.add_argument("--slots", type=int, default=4,
                    help="generation decode slots (the fixed batch of "
                         "the one compiled decode step)")
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=8,
                    help="prompts are drawn with mixed lengths in "
                         "[1, max-prompt]")
    ap.add_argument("--max-seq", type=int, default=32,
                    help="generation KV-cache length")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="generation sampling temperature, honored by "
                         "the engine, HTTP and serial-reference paths "
                         "alike (0 = greedy); with per-request seeds "
                         "--compare-serial stays exact at any value")
    ap.add_argument("--spec-decode", action="store_true",
                    help="generation only: speculative-decoding A/B — "
                         "spec-on vs spec-off engines over the same "
                         "repetitive traffic plus the serial exact-"
                         "answer reference (kind=spec_loadgen; exit 4 "
                         "on divergence)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens per slot per verify step for "
                         "--spec-decode (0 = FLAGS_spec_decode_k)")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="fraction of generation requests opening with "
                         "one fixed shared prefix (the prefix-cache "
                         "workload); report splits TTFT hit vs miss")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="shared prefix length in tokens (0 = auto: "
                         "largest whole-block prefix < max-prompt)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="KV block size for the paged engine "
                         "(0 = FLAGS_gen_kv_block_size)")
    ap.add_argument("--trace", action="store_true",
                    help="generation only: arm FLAGS_enable_trace, dump "
                         "kept spans to --trace-out and assert complete "
                         "span trees + critical-path consistency "
                         "(exit 6 on violation)")
    ap.add_argument("--trace-out",
                    help="span dump path (default: <out>.spans.jsonl)")
    ap.add_argument("--trace-sample", type=float, default=1.0,
                    help="FLAGS_trace_sample for the --trace run "
                         "(default 1.0 so every tree is auditable)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection acceptance run: baseline "
                         "pass, then the same traffic under "
                         "--fault-spec; exit 4 on wrong answers or "
                         "worker deaths, 5 on p99 over bound")
    ap.add_argument("--fault-spec",
                    default="transient_fail:p=0.05,step_nan:p=0.01",
                    help="FLAGS_fault_spec armed for the chaos pass")
    ap.add_argument("--chaos-p99-bound", type=float, default=50.0,
                    help="max allowed chaos-p99 / fault-free-p99 ratio")
    ap.add_argument("--router", type=int, default=0,
                    help="multi-replica mode: N in-process replicas "
                         "behind the serving Router; records 1->N "
                         "throughput scaling (kind=router_loadgen). "
                         "Combine with --chaos for the replica-kill "
                         "failover run, --hot-swap / --preempt-drill "
                         "for the elasticity drills")
    ap.add_argument("--service-ms", type=float, default=20.0,
                    help="router mode: deterministic per-batch service "
                         "time injected at the serving fault site so "
                         "the scaling ratio is machine-independent "
                         "(0 = none)")
    ap.add_argument("--scaling-min", type=float, default=0.0,
                    help="router mode: minimum required rps_N/rps_1 "
                         "ratio; exit 7 below it (0 = record only)")
    ap.add_argument("--hot-swap", action="store_true",
                    help="router mode: v1->v2 hot-swap drill under "
                         "load (exit 4 on any dropped request or "
                         "standby post-warmup compile)")
    ap.add_argument("--preempt-drill", action="store_true",
                    help="router mode: preempt+resume one replica "
                         "under load; exit 4 on any client-visible "
                         "error")
    ap.add_argument("--disagg", action="store_true",
                    help="router mode: disaggregated prefill/decode "
                         "fleet acceptance run across real subprocess "
                         "replicas — --disagg-prefill prefill workers "
                         "+ rest decode, KV blocks shipped over "
                         "/v1/kv/export->adopt, vs a symmetric "
                         "baseline (kind=disagg_loadgen)")
    ap.add_argument("--disagg-prefill", type=int, default=1,
                    help="disagg mode: prefill workers out of "
                         "--router N (rest are decode workers)")
    args = ap.parse_args(argv)

    if args.router:
        if args.disagg:
            return run_disagg(args)
        return run_router(args)
    if args.chaos:
        return run_chaos(args)
    if args.generate:
        if args.spec_decode:
            return run_spec_generation(args)
        return run_generation(args)

    seq_buckets = tuple(int(s) for s in args.seq_buckets.split(","))
    feat = 6
    reqs = make_requests(args.requests, seq_buckets, feat, args.seed)
    common = {"concurrency": args.concurrency, "rate": args.rate,
              "max_batch_size": args.max_batch_size,
              "max_wait_us": args.max_wait_us,
              "seq_buckets": list(seq_buckets),
              "warmup": not args.no_warmup}

    rc = 0
    if args.url:
        target = _HTTPTarget(args.url)
        if args.rate > 0:
            if args.duration > 0:
                reqs = reqs[:max(1, int(args.rate * args.duration))]
            lat, errs, dur = run_open(target, reqs, args.rate,
                                      args.timeout_ms)
            rec = summarize("open", lat, errs, dur, common)
        else:
            lat, errs, dur = run_closed(target, reqs, args.concurrency,
                                        args.timeout_ms)
            rec = summarize("closed", lat, errs, dur, common)
        emit(rec, args.out)
        return rc

    from paddle_tpu.serving import EngineConfig, ServingEngine

    model_dir = args.model_dir or build_tiny_model(
        tempfile.mkdtemp(prefix="serving_loadgen_"), feat)
    cfg = EngineConfig(model_dir,
                       max_batch_size=args.max_batch_size,
                       max_wait_us=args.max_wait_us,
                       queue_capacity=max(64, args.concurrency * 8),
                       default_timeout_ms=args.timeout_ms,
                       seq_buckets=seq_buckets,
                       warmup=not args.no_warmup)
    engine = ServingEngine(cfg)
    engine.start()
    misses_after_warmup = engine.cache_stats()["misses"]

    target = _EngineTarget(engine)
    if args.rate > 0:
        if args.duration > 0:
            reqs = reqs[:max(1, int(args.rate * args.duration))]
        lat, errs, dur = run_open(target, reqs, args.rate,
                                  args.timeout_ms)
        rec = summarize("open", lat, errs, dur, common)
    else:
        lat, errs, dur = run_closed(target, reqs, args.concurrency,
                                    args.timeout_ms)
        rec = summarize("closed", lat, errs, dur, common)
    stats = engine.cache_stats()
    rec["cache"] = {"misses_after_warmup": misses_after_warmup,
                    "misses_total": stats["misses"],
                    "post_warmup_compiles":
                        stats["misses"] - misses_after_warmup}
    emit(rec, args.out)

    if args.compare_serial:
        ref = engine.predictor.clone()  # shares weights + compile cache
        misses_before_serial = engine.cache_stats()["misses"]
        slat, serrs, sdur = run_serial_baseline(ref, reqs)
        srec = summarize("serial_baseline", slat, serrs, sdur, common)
        # the batcher-off baseline feeds RAW shapes, so every novel
        # (1, seq) pair is a fresh XLA specialization — the recompile
        # pathology the bucket ladder exists to prevent
        srec["cache"] = {"serial_compiles":
                         engine.cache_stats()["misses"]
                         - misses_before_serial}
        emit(srec, args.out)
        if srec["throughput_rps"]:
            speedup = rec["throughput_rps"] / srec["throughput_rps"]
            print(f"# batched/serial speedup: {speedup:.2f}x")

    engine.stop()
    if args.check_compiles and rec["cache"]["post_warmup_compiles"] > 0:
        print(f"FAIL: {rec['cache']['post_warmup_compiles']} compiles "
              f"after warmup (warmup={not args.no_warmup})",
              file=sys.stderr)
        rc = 3
    return rc


if __name__ == "__main__":
    sys.exit(main())
