"""Runtime flag registry + environment bootstrap.

Reference: the 136 gflags in platform/flags.cc:33-449 (DEFINE_* at a
central site, `DECLARE_*` at use sites) exported to Python via
core.globals, and the env bootstrap `read_env_flags` in
python/paddle/fluid/__init__.py:165 which imports `FLAGS_*` environment
variables at package import.

TPU-first differences: most reference flags configure subsystems XLA owns
outright (CUDA allocator fractions, cudnn autotune, NCCL rings), so the
set here is the flags that have a real knob in THIS runtime, plus a small
compatibility tier of reference names that are accepted, stored, and
documented as no-ops (so reference scripts that set them keep running).

Usage:
    from paddle_tpu.core.flags import FLAGS
    if FLAGS.check_nan_inf: ...
    FLAGS.executor_cache_capacity = 16

    # paddle-compatible API (core.globals analogue):
    fluid.get_flags(["FLAGS_check_nan_inf"])
    fluid.set_flags({"FLAGS_check_nan_inf": True})

Environment: `FLAGS_<name>=<value>` is read once at import (bools accept
0/1/true/false). `paddle_tpu.core.flags.reload_from_env()` re-reads.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, List

__all__ = ["FLAGS", "DEFINE_bool", "DEFINE_int32", "DEFINE_int64",
           "DEFINE_double", "DEFINE_string", "get_flags", "set_flags",
           "flag_info", "reload_from_env"]


class _Flag:
    __slots__ = ("name", "default", "value", "ftype", "help", "noop",
                 "traced")

    def __init__(self, name, default, ftype, help_, noop=False,
                 traced=False):
        self.name = name
        self.default = default
        self.value = default
        self.ftype = ftype
        self.help = help_
        self.noop = noop
        # traced flags are baked into jitted executables; their values
        # join the executor cache key (trace_signature)
        self.traced = traced


_REGISTRY: Dict[str, _Flag] = {}
_LOCK = threading.Lock()
# Bumped by every assignment to a flag's value (FLAGS.x = v, set_flags,
# the environment readers). What was resolved under one generation
# (the Executor's bound step: the gates' verdicts, trace_signature())
# holds for as long as the number has not moved: an O(1) test in place
# of re-reading every flag.
_generation = 0


def generation() -> int:
    return _generation


def _define(name, default, ftype, help_, noop=False, traced=False):
    with _LOCK:
        if name in _REGISTRY:
            raise ValueError(f"flag {name!r} already defined")
        _REGISTRY[name] = _Flag(name, default, ftype, help_, noop, traced)
    _load_one_from_env(name)
    return _REGISTRY[name]


def DEFINE_bool(name, default, help_="", traced=False):
    return _define(name, bool(default), bool, help_, traced=traced)


def DEFINE_int32(name, default, help_="", traced=False):
    return _define(name, int(default), int, help_, traced=traced)


DEFINE_int64 = DEFINE_int32


def DEFINE_double(name, default, help_="", traced=False):
    return _define(name, float(default), float, help_, traced=traced)


def DEFINE_string(name, default, help_="", traced=False):
    return _define(name, str(default), str, help_, traced=traced)


def _parse(ftype, raw: str):
    if ftype is bool:
        return raw.strip().lower() in ("1", "true", "yes", "on")
    return ftype(raw)


def _load_one_from_env(name):
    global _generation
    raw = os.environ.get(f"FLAGS_{name}")
    if raw is not None:
        f = _REGISTRY[name]
        try:
            f.value = _parse(f.ftype, raw)
            _generation += 1
        except (ValueError, TypeError):
            # a bad env value must not make the package unimportable
            import warnings
            warnings.warn(
                f"ignoring malformed environment variable FLAGS_{name}="
                f"{raw!r} (expected {f.ftype.__name__}); keeping "
                f"{f.value!r}")


def reload_from_env():
    """Re-read every FLAGS_* environment variable (read_env_flags)."""
    for name in _REGISTRY:
        _load_one_from_env(name)


class _FlagsNamespace:
    """Attribute access: FLAGS.check_nan_inf. Unknown names raise."""

    def __getattr__(self, name):
        try:
            return _REGISTRY[name].value
        except KeyError:
            raise AttributeError(f"unknown flag {name!r}") from None

    def __setattr__(self, name, value):
        f = _REGISTRY.get(name)
        if f is None:
            raise AttributeError(f"unknown flag {name!r}")
        global _generation
        f.value = _parse(f.ftype, value) if isinstance(value, str) \
            else f.ftype(value)
        _generation += 1

    def __dir__(self):
        return sorted(_REGISTRY)


FLAGS = _FlagsNamespace()


def get_flags(names) -> Dict[str, Any]:
    """fluid.get_flags(["FLAGS_x", ...]) -> {name: value}."""
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {n!r}")
        out[n] = _REGISTRY[key].value
    return out


def set_flags(kv: Dict[str, Any]):
    """fluid.set_flags({"FLAGS_x": v, ...})."""
    for n, v in kv.items():
        key = n[6:] if n.startswith("FLAGS_") else n
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag {n!r}")
        setattr(FLAGS, key, v)


def trace_signature() -> tuple:
    """Values of every traced=True flag (baked into jitted executables).
    Executor cache keys include this so set_flags invalidates stale
    compilations instead of being silently ignored. Derived from the
    registry: a new traced flag is covered automatically."""
    return tuple(f.value for _, f in sorted(_REGISTRY.items()) if f.traced)


def flag_handle(name: str) -> _Flag:
    """The mutable _Flag record for `name` (internal). The monitor's
    disabled fast path caches this handle so every STAT_* call costs one
    attribute read instead of a registry lookup."""
    return _REGISTRY[name]


def flag_info() -> List[dict]:
    """All flags with metadata (for docs / debugging)."""
    return [{"name": f.name, "value": f.value, "default": f.default,
             "type": f.ftype.__name__, "help": f.help, "noop": f.noop}
            for f in _REGISTRY.values()]


# ---------------------------------------------------------------------------
# Flag definitions — the live knobs
# ---------------------------------------------------------------------------

DEFINE_bool(
    "check_nan_inf", False,
    "Debug mode: after every lowered op, verify each floating-point "
    "output is finite via an ordered host callback; raises naming the op "
    "and output var. Reference: operator.cc:820-822 / flags.cc:44. "
    "Heavy — debug only.", traced=True)

DEFINE_int32(
    "executor_cache_capacity", 64,
    "Max compiled executables kept per Executor (LRU evicted). Each entry "
    "is one (program fingerprint, feed shapes, fetches) specialization. "
    "Reference analogue: the per-program Prepare cache in executor.py.")

DEFINE_string(
    "prng_impl", "",
    "PRNG implementation for stateful ops (dropout etc.): '' = jax "
    "default (threefry2x32, splittable, slowest), 'rbg' = XLA "
    "RngBitGenerator backed by the TPU hardware RNG (much faster mask "
    "generation, still reproducible per (seed, step, op)), 'unsafe_rbg' "
    "= fastest, weakest folding. Reference analogue: the cuRAND-backed "
    "dropout kernels vs the CPU Philox path.", traced=True)

DEFINE_int32(
    "reader_queue_depth", 2,
    "Default host infeed queue capacity for DataLoader/PyReader when the "
    "user does not pass one (reader double-buffering depth). Reference: "
    "buffered_reader.cc double-buffer + pybind queue capacity.")

DEFINE_int32(
    "flash_attention_block_q", 512,
    "Fallback q-block tile for the Pallas flash-attention kernel when "
    "the op attr is unset AND the autotune cache has no entry for the "
    "shape (FLAGS_flash_autotune). Multiples of 128 only; clamped to "
    "the largest divisor of the (padded) sequence. 512 won a "
    "kernel-only microbench of 2026-08-02 on a v5e at seq "
    "512/1024/2048, where 128 was 2-4x slower "
    "(docs/attention_tuning.md; the end-to-end race is ROADMAP queue 1 "
    "item 7).", traced=True)

DEFINE_int32(
    "flash_attention_block_k", 512,
    "Fallback k-block tile for the Pallas flash-attention kernel when "
    "the op attr is unset and the autotune cache misses. Multiples of "
    "128 only; clamped like block_q. See flash_attention_block_q for "
    "the measured basis.", traced=True)

DEFINE_string(
    "flash_autotune", "cached",
    "Flash-attention tile autotuner mode (ops/pallas/autotune.py): "
    "'off' = flags/attrs only; 'cached' (default) = consult the "
    "process memo + persistent JSON cache but never tune (a miss falls "
    "back to FLAGS_flash_attention_block_{q,k} — CPU/tier-1 runs pay "
    "one dict lookup, no sweep); 'full' = on a cache miss time the "
    "{128,256,512} candidate grid on the real device, memoize and "
    "persist the winner. Interpret/CPU mode never sweeps.", traced=True)

DEFINE_string(
    "flash_autotune_cache", "",
    "Path of the persistent flash-tile cache (JSON). Empty = "
    "flash_autotune.json in the compilation-cache directory "
    "(core/compile_cache.py: JAX_COMPILATION_CACHE_DIR, else "
    "<checkout>/.jax_cache). "
    "Seed it from real chip time with tools/attn_micro.py "
    "--emit-cache.")

DEFINE_bool(
    "pallas_interpret", False,
    "Force Pallas kernels into interpret mode even on TPU (debugging "
    "numerics; very slow). Without it kernels interpret only on the "
    "CPU backend; a backend that is neither CPU nor TPU raises.",
    traced=True)

DEFINE_bool(
    "op_trace_scopes", True,
    "Wrap each lowered op's emission in jax.named_scope("
    "'{op.type}:{block}/{op_idx}') so XPlane device traces, HLO dumps, "
    "and compiled-HLO op_name metadata attribute back to Program ops "
    "(reference: platform/profiler.cc per-op RecordEvent). Scopes are "
    "trace/metadata only — no runtime cost — so the default is on; "
    "turn off to diff HLO text across op reorderings.", traced=True)

DEFINE_string(
    "program_verify", "warn",
    "Static program verification (paddle_tpu/analysis) before the "
    "executor or serving engine spends a compile: 'off' = skip; 'warn' "
    "(default) = verify once per (program fingerprint, feeds, fetches) "
    "and surface findings as one summarized warning; 'error' = raise "
    "ProgramVerificationError on error-severity findings — with "
    "'{op_type}:{block}/{op_idx}' provenance — before any executable "
    "is built or cached. Zero device work either way: shape/dtype "
    "inference runs jax.eval_shape over each op's lowering. Rule "
    "catalog: docs/static_analysis.md; CLI: tools/program_lint.py.")

DEFINE_int32(
    "graph_opt_level", 1,
    "Program-IR optimization before lowering (analysis/passes): 0 = "
    "compile the program as built; 1 (default) = dead-op elimination, "
    "constant folding, and CSE on a verified clone; 2 adds elementwise-"
    "chain fusion (consecutive chains merge into one fused_elementwise "
    "op, falling back to a shared jax.named_scope when a merge gate "
    "fails) and the inplace/donation planner (per-var "
    "jax.jit donation of hazard-free optimizer state). The optimized "
    "program must re-verify clean (error semantics) before it replaces "
    "the original, and it is what the executable cache is keyed on. "
    "Catalog: docs/graph_passes.md.", traced=True)

DEFINE_int64(
    "memory_budget_bytes", 0,
    "HBM budget for the static memory gate (analysis/memory.py). 0 "
    "(default) = auto: use the device's reported bytes_limit "
    "(core.memory.device_memory_stats) when the backend reports one "
    "(CPU backends report nothing). Findings against the auto budget "
    "only warn, whatever FLAGS_memory_gate says, so default flags give "
    "the same verdict on every backend. -1 = never apply a budget. Any "
    "positive value is an explicit budget in bytes, and the only kind "
    "the gate raises on. PTV050 fires when a program's estimated peak "
    "exceeds the budget, PTV051 when one tensor alone does. Docs: "
    "docs/memory_planning.md.")

DEFINE_string(
    "memory_gate", "error",
    "The pre-compile OOM gate (FLAGS_program_verify's sibling for the "
    "memory band, analysis/memory.py): 'off' = skip the static memory "
    "analysis; 'warn' = analyze once per (fingerprint, feed shapes, "
    "fetches, budget) and surface PTV05x findings as one summarized "
    "warning; 'error' (default) = raise ProgramVerificationError on "
    "PTV050/PTV051 against an explicit positive "
    "FLAGS_memory_budget_bytes — in Executor._resolve_step BEFORE the "
    "executable cache records a miss, and in ServingEngine.warmup "
    "before any ladder cell compiles — so a program that cannot fit "
    "is rejected with zero compiles attempted. Against the "
    "auto-detected device limit the findings warn and the program "
    "goes on to XLA, whose own buffer assignment decides. Estimates "
    "with unresolved dynamic dims are documented lower bounds and the "
    "finding says so (Spec.nbytes). Docs: docs/memory_planning.md.")

DEFINE_string(
    "sharding_verify", "warn",
    "The pre-compile sharding gate (analysis/sharding.py — the PTV06x "
    "sibling of FLAGS_program_verify / FLAGS_memory_gate): 'off' = "
    "skip; 'warn' (default) = propagate the SpecLayout through the "
    "program graph once per (fingerprint, mesh, feed shapes, fetches) "
    "and surface PTV060-063 findings as one summarized warning; "
    "'error' = raise ProgramVerificationError on PTV060 layout-"
    "inconsistent ops — in Executor._resolve_step BEFORE the "
    "executable cache records a miss, and in ServingEngine.warmup "
    "before any ladder cell compiles. The gate only engages when a "
    "layout is in scope (the sharded-exec SpecLayout, or "
    "FLAGS_sharded_mesh is set); with no mesh it is a no-op. The same "
    "pass prices the implied collectives into a predicted "
    "collective_bytes_per_step (docs/sharding.md, "
    "docs/static_analysis.md).")

DEFINE_bool(
    "buffer_reuse", True,
    "Enable the buffer-reuse rewrite (analysis/passes/reuse.py) when "
    "FLAGS_graph_opt_level >= 2: transient same-shape/dtype vars with "
    "strictly disjoint liveness intervals collapse onto one shared "
    "buffer (the reference framework's memory_optimize_pass), lowering "
    "the static peak estimate the memory gate enforces. Off = level 2 "
    "keeps fusion+donation but skips the reuse rewrite (the sweep "
    "driver's _reuse_on/_reuse_off A/B pair).", traced=True)

DEFINE_bool(
    "flight_recorder", True,
    "Keep a bounded in-memory ring of per-step flight records (step "
    "index, program, cache hit/miss, timings, stat deltas, NaN "
    "provenance) that monitor.dump_flight_recorder writes as JSONL on "
    "unhandled exception / SIGTERM (monitor.install_flight_recorder) "
    "or on demand. One dict append per step — cheap enough to leave "
    "on; the black box that turns 'the run died' into 'step N died'.")

DEFINE_int32(
    "flight_recorder_capacity", 512,
    "Max records kept in the flight-recorder ring (oldest dropped "
    "first). 512 steps of context is hours of large-model training "
    "and a few KB of host memory.")

DEFINE_string(
    "flight_recorder_path", "",
    "Default path for monitor.dump_flight_recorder / "
    "install_flight_recorder when no explicit path is given. Empty = "
    "flight_recorder.jsonl in the working directory.")

DEFINE_int32(
    "monitor_http_port", 0,
    "When > 0, monitor.serve_prometheus() binds a stdlib HTTP scrape "
    "endpoint on 127.0.0.1:<port> serving prometheus_text() (started "
    "automatically by monitor.start_exporter). 0 = disabled.")

DEFINE_bool(
    "enable_monitor", False,
    "Enable the runtime stats registry (paddle_tpu/monitor.py): "
    "executor compile/step/feed timing, reader queue stats, device "
    "memory gauges. Off = every STAT_* call is a near-zero-cost no-op. "
    "Reference: the always-on STAT registry of platform/monitor.h, made "
    "opt-in here because host callbacks are the expensive resource on "
    "TPU.")

DEFINE_string(
    "monitor_export_path", "",
    "Default JSONL file for monitor snapshots (append mode, one JSON "
    "object per line). Used by monitor.snapshot_to_jsonl / "
    "start_exporter when no explicit path is given; bench.py and "
    "tools/profile_step.py write here when set.")

DEFINE_double(
    "monitor_flush_interval_s", 10.0,
    "Interval of the background JSONL snapshot exporter "
    "(monitor.start_exporter). Crash-safety knob: a run killed by an "
    "external timeout still leaves snapshots this fresh.")

DEFINE_int32(
    "serving_max_batch_size", 8,
    "Default EngineConfig.max_batch_size: the most request rows the "
    "serving engine coalesces into one padded batch (must fit the "
    "largest batch bucket). Serving analogue of the reference "
    "predictor pool size.")

DEFINE_int32(
    "serving_max_wait_us", 2000,
    "Default EngineConfig.max_wait_us: how long (microseconds) a "
    "partially-filled batch may wait for co-batchable requests before "
    "the worker flushes it. The latency/throughput dial of the dynamic "
    "batcher.")

DEFINE_int32(
    "serving_queue_capacity", 256,
    "Default EngineConfig.queue_capacity: max request rows pending in "
    "the dynamic batcher before submissions are rejected with "
    "QueueFullError (backpressure instead of unbounded queueing).")

DEFINE_int32(
    "gen_kv_block_size", 16,
    "Paged KV cache: tokens per physical block. Larger blocks mean "
    "fewer gather indices per step but coarser prefix-cache "
    "granularity (only FULL prompt blocks are content-hash shareable) "
    "and more tail waste per sequence. Also the chunk width of the "
    "chunked-prefill executable.")

DEFINE_int32(
    "gen_kv_pool_blocks", 0,
    "Paged KV cache: physical blocks in the pool (one is reserved as "
    "the scratch block). 0 (default) = derive: from "
    "FLAGS_gen_kv_pool_bytes when set, else full capacity "
    "(max_slots x ceil(max_seq/block_size) + scratch). This — not "
    "max_slots x max_seq — is what bounds peak KV HBM; the static "
    "memory planner prices the pool persistables directly.")

DEFINE_int64(
    "gen_kv_pool_bytes", 0,
    "Paged KV cache: HBM budget for the K/V pools across all layers; "
    "the engine sizes the pool as budget // block_bytes blocks. 0 = "
    "unset (FLAGS_gen_kv_pool_blocks or full capacity applies). The "
    "knob the gen_paged_vs_slab A/B holds fixed while comparing "
    "sustainable slot counts.")

DEFINE_bool(
    "gen_spec_decode", False,
    "Generation engine default for speculative decoding "
    "(serving/spec_decode.py): when True a paged engine builds the "
    "third fixed-shape executable (the [max_slots, k+1] batched verify "
    "step) at start() and drafts with the host-side n-gram / "
    "prompt-lookup drafter every decode iteration. Per-request "
    "GenerationRequest.spec_decode overrides (None = this default). "
    "Host-side program choice only — never part of an executable cache "
    "key; post_warmup_compiles() stays 0 either way.")

DEFINE_int32(
    "spec_decode_k", 4,
    "Speculative decoding: maximum draft tokens proposed per slot per "
    "iteration. The verify executable is compiled at [max_slots, k+1] "
    "(k drafts + the committed token), so changing k changes the ONE "
    "extra warmup compile, not the steady state. Larger k amortizes "
    "more dispatch overhead on repetitive text but wastes verify "
    "compute when acceptance is low.")

DEFINE_int32(
    "spec_decode_ngram", 3,
    "Speculative decoding: longest context suffix the n-gram / "
    "prompt-lookup drafter matches against the slot's prompt + "
    "generated tokens. Matching tries n down to 1 and proposes the "
    "tokens that followed the most recent earlier occurrence; 0 "
    "disables drafting (the verify path then never dispatches).")

DEFINE_bool(
    "spec_decode_adaptive", True,
    "Acceptance-aware adaptive draft length (serving/spec_decode.py "
    "update_spec_k): each slot tracks an EWMA of its measured draft "
    "acceptance rate and shrinks its per-iteration draft budget toward "
    "1 when acceptance stops paying for the verify premium (EWMA < "
    "FLAGS_spec_adapt_low), growing it back toward FLAGS_spec_decode_k "
    "when acceptance recovers (EWMA > FLAGS_spec_adapt_high). Host-side "
    "only: the verify executable stays compiled at [max_slots, k+1] and "
    "accepted outputs are unchanged — only the proposed draft length "
    "moves.")

DEFINE_double(
    "spec_adapt_low", 0.3,
    "Adaptive spec_k shrink threshold: when a slot's acceptance-rate "
    "EWMA drops below this, its draft budget shrinks by 1 (floor 1).")

DEFINE_double(
    "spec_adapt_high", 0.8,
    "Adaptive spec_k grow threshold: when a slot's acceptance-rate "
    "EWMA rises above this, its draft budget grows by 1 (cap "
    "FLAGS_spec_decode_k).")

DEFINE_double(
    "serving_default_timeout_ms", 1000.0,
    "Default EngineConfig.default_timeout_ms: per-request deadline "
    "applied when a submission does not carry its own; a request still "
    "queued past it fails with DeadlineExceededError. 0 = no deadline.")

DEFINE_int32(
    "serving_http_port", 0,
    "Default EngineConfig.http_port for serving.serve(): the port of "
    "the JSON front end (/v1/predict, /healthz, /metrics). 0 binds an "
    "ephemeral port.")

DEFINE_string(
    "profiler_trace_dir", "",
    "When set, fluid.profiler writes chrome-trace/XPlane dumps here by "
    "default. Reference: FLAGS profile_path (flags.cc).")

DEFINE_string(
    "fault_spec", "",
    "Deterministic fault-injection spec (paddle_tpu/resilience/"
    "faults.py): comma-separated kind:param list, e.g. "
    "'step_nan:p=0.01,slow_step:ms=500,transient_fail:p=0.02,"
    "preempt_at:step=40'. Empty (default) = injection disabled, zero "
    "overhead. Grammar and semantics: docs/resilience.md.")

DEFINE_int32(
    "fault_seed", 0,
    "Seed of the fault-injection RNG. Decisions derive from (seed, "
    "site, per-site invocation counter), so a given spec+seed injects "
    "the same faults at the same steps regardless of timing or thread "
    "interleaving.")

DEFINE_int32(
    "retry_max_attempts", 3,
    "Default RetryPolicy attempt budget (paddle_tpu/resilience/"
    "retry.py): total tries, first included. Transient faults "
    "(TransientFault and friends) retry up to this many times with "
    "jittered exponential backoff; poison errors (ValueError, "
    "verification failures) never retry.")

DEFINE_double(
    "retry_base_ms", 10.0,
    "Default RetryPolicy base backoff (milliseconds): attempt n sleeps "
    "~base * 2^(n-1), jittered, capped by FLAGS_retry_max_ms.")

DEFINE_double(
    "retry_max_ms", 1000.0,
    "Default RetryPolicy backoff cap (milliseconds).")

DEFINE_int32(
    "serving_breaker_threshold", 5,
    "Circuit breaker (paddle_tpu/resilience/breaker.py): consecutive "
    "batch-execution failures before the serving/generation breaker "
    "trips CLOSED -> OPEN and submissions shed with OverloadedError "
    "(HTTP 503 + Retry-After). 0 disables the breaker.")

DEFINE_double(
    "serving_breaker_cooldown_ms", 1000.0,
    "How long an OPEN breaker sheds load before admitting half-open "
    "probe traffic. A successful probe closes the breaker; a failed "
    "one re-opens it for another cooldown.")

DEFINE_int32(
    "router_redispatch_budget", 2,
    "Multi-replica router (paddle_tpu/serving/router.py): how many "
    "times one request may be re-dispatched to a different replica "
    "after a retryable failure (replica death, 503 shed, connection "
    "reset) before the error is surfaced to the client. 0 disables "
    "failover.")

DEFINE_double(
    "router_probe_interval_s", 0.5,
    "Router health-probe cadence: every interval the router polls each "
    "replica's health (/healthz for --url replicas, engine.health() "
    "in-process) and updates its routing table. 0 disables active "
    "probing (passive failure accounting still runs).")

DEFINE_int32(
    "router_failure_threshold", 3,
    "Consecutive dispatch failures before the router's per-replica "
    "circuit breaker marks that replica unhealthy and routes around "
    "it. 0 disables the per-replica breaker.")

DEFINE_int32(
    "router_affinity_max", 4096,
    "Session-affinity table capacity: the router keeps at most this "
    "many session->replica pins, evicting the least recently used pin "
    "past the cap, so a long-running router's memory stays bounded "
    "under a stream of short-lived generation sessions.")

DEFINE_double(
    "router_drain_timeout_s", 30.0,
    "Hot-swap / deregister drain deadline: how long the router waits "
    "for a retired replica's in-flight requests to finish before "
    "stopping it anyway.")

DEFINE_bool(
    "router_disagg", False,
    "Disaggregated prefill/decode dispatch (paddle_tpu/serving/"
    "disagg.py): Router.generate() runs two-phase scheduling — pick a "
    "decode replica, and when the fleet prefix store says it does not "
    "already own the prompt's full-block chain, have a prefill-capable "
    "replica export the KV blocks over the wire and the decode replica "
    "adopt them before the decode dispatch. Off (default) = classic "
    "single-phase routing; transfer failures always fall back to the "
    "decode worker re-prefilling locally, so answers never change.")

DEFINE_int32(
    "disagg_fleet_prefix_max", 4096,
    "FleetPrefixStore capacity: at most this many chain-hash entries "
    "(hash -> owning replica names) are kept on the router, LRU-evicted "
    "past the cap. Eviction only forgets WHERE a prefix lives — the "
    "worst case is a redundant re-prefill, never a wrong answer.")

DEFINE_bool(
    "serving_nan_guard", True,
    "Serving engine output hygiene: verify every batch's float outputs "
    "are finite before scattering them to clients; a non-finite batch "
    "is treated as a transient fault (retried via RetryPolicy, then "
    "failed) instead of being served as a wrong answer.")

DEFINE_bool(
    "sharded_exec", False,
    "GSPMD sharded execution (paddle_tpu/parallel/layout.py): when a "
    "CompiledProgram runs data-parallel, attach a SpecLayout table over "
    "the FLAGS_sharded_mesh Mesh — feeds batch-shard on the data axis, "
    "optimizer moments and the weight update ZeRO-shard across replicas "
    "(arxiv 2004.13336), params optionally split on the model axis — "
    "and jit with the derived in/out_shardings. Off = legacy replicated "
    "data-parallel. Traced: flipping it recompiles.", traced=True)

DEFINE_string(
    "sharded_mesh", "",
    "Mesh shape for FLAGS_sharded_exec as 'dp' or 'dp,tp' (e.g. '8' or "
    "'4,2'); axis 0 is the data axis, axis 1 the model axis. Empty = "
    "the parallel.get_mesh() registry mesh (all devices, 1-D data "
    "axis). Traced: a shape change recompiles.", traced=True)

DEFINE_bool(
    "enable_trace", False,
    "Per-request distributed tracing (paddle_tpu/trace.py): spans with "
    "W3C traceparent propagation across the HTTP -> batcher -> engine "
    "-> executor path. Off, every trace entry point returns after one "
    "cached-flag read. Host-side only — never part of a compile cache "
    "key.")

DEFINE_double(
    "trace_sample", 0.05,
    "Head-sampling keep probability for request traces (decided once "
    "per root span). Tail rules OVERRIDE it: errored requests and "
    "requests slower than the rolling latency threshold are always "
    "kept. 1.0 keeps every trace.")

DEFINE_int32(
    "trace_ring_capacity", 8192,
    "Bounded in-process span ring: kept spans past this count evict "
    "oldest-first. Sized for post-mortem dumps, not long-term storage "
    "— export with trace.export_jsonl / export_chrome_tracing.")

DEFINE_double(
    "trace_tail_slow_ms", 0.0,
    "Absolute tail-sampling slow threshold (ms): any request whose "
    "e2e exceeds it is kept regardless of head sampling. 0 (default) "
    "= rolling p95 over the last trace window (keeps ~the slowest 5% "
    "once enough requests have completed).")

DEFINE_bool(
    "enable_goodput", False,
    "Run-level goodput accounting (paddle_tpu/goodput.py): classify "
    "ALL wall-clock of a training/bench run into exclusive categories "
    "(device_compute, compile, input_wait, feed_stage, fetch_sync, "
    "checkpoint_save/restore, retry_backoff, nan_rollback, "
    "preempt_drain, probe_wait, other) with the invariant that the "
    "categories sum to wall-clock. Off (default) = every goodput hook "
    "is one cached-flag read. Stats ride the monitor registry, so "
    "FLAGS_enable_monitor gates the exported goodput.* stats.")

DEFINE_double(
    "goodput_starved_ms", 50.0,
    "Input-starvation threshold: a training step whose reader batch "
    "wait exceeds this many milliseconds counts as input-starved "
    "(goodput.input_starved_steps) and feeds the default "
    "input_starvation burn-rate alert rule that goodput.start_run "
    "appends to FLAGS_alert_rules.")

DEFINE_string(
    "goodput_alert_windows", "15s,60s",
    "Multi-window spec of the default input_starvation burn-rate rule "
    "(short,long — both must breach before the alert fires, the "
    "monitor_alerts.py burn semantics). Only read when "
    "goodput.install_starvation_alert builds the default rule.")

DEFINE_string(
    "alert_rules", "",
    "Declarative SLO alert rules for paddle_tpu/monitor_alerts.py, "
    "semicolon-separated. Grammar per rule: "
    "'name:threshold:STAT OP VALUE[:for=DUR]' over a counter/gauge, "
    "'name:ratio:NUM/DEN OP VALUE[:for=DUR]' over two counters, or "
    "'name:burn:HIST:pQQ OP VALUE:windows=W1,W2' multi-window burn "
    "rate over a histogram percentile (fires only when EVERY window "
    "breaches). OP is one of > >= < <=; durations accept s/m/h "
    "suffixes. Empty (default) disables the evaluator entirely.")

DEFINE_double(
    "alert_eval_interval_s", 5.0,
    "Period of the background alert evaluator thread (seconds). Each "
    "tick snapshots the monitor registry once and evaluates every "
    "FLAGS_alert_rules rule against it; <= 0 disables the background "
    "thread (rules still evaluate via alerts.evaluate_once(), which "
    "tests drive with a fake clock).")

DEFINE_string(
    "alert_bundle_dir", "",
    "Directory for incident bundles: on each pending->firing "
    "transition the alert engine writes exactly one atomic JSON "
    "bundle correlating the rule, the full stats snapshot, breaching-"
    "bucket trace exemplars, the kept-trace ring, and the flight-"
    "recorder ring. Empty (default) = bundles disabled; alerts still "
    "fire and expose via /alertz and ALERTS exposition.")

DEFINE_int32(
    "alert_bundle_max_spans", 512,
    "Cap on kept-trace-ring spans embedded in one incident bundle "
    "(newest kept spans win, after breaching-bucket exemplar traces "
    "are included first). Bounds bundle size on busy servers.")

# ---------------------------------------------------------------------------
# Reference-flag compat surface (App. C parity target:
# platform/flags.cc:33-449 + the read_env_flags whitelist in
# python/paddle/fluid/__init__.py:165). Reference programs call
# fluid.set_flags / export FLAGS_* freely; every name in the inventory
# is accepted here. Flags marked no-op describe CUDA/CPU-runtime
# machinery that XLA/TPU absorbs (allocator strategies, cuDNN
# autotuning, NCCL dirs, eager deletion GC, ...) — they are settable,
# readable, and ignored, with the TPU-native equivalent named in the
# help text where one exists.
# ---------------------------------------------------------------------------

def _compat(name, default, help_=""):
    ftype = type(default)
    _define(name, default,
            ftype if ftype in (bool, int, float) else str,
            help_, noop=True)


for _name, _default, _help in [
    ("cpu_deterministic", False,
     "no-op: single jitted computation is deterministic"),
    ("allocator_strategy", "naive_best_fit",
     "no-op: device memory is XLA buffer assignment; host pool is "
     "native/src/allocator.h"),
    ("fast_check_nan_inf", False,
     "no-op: FLAGS_check_nan_inf covers both modes here"),
    ("collective_get_thread_num", 16, "no-op: XLA collectives"),
    ("communicator_fake_rpc", False, "no-op: test hook of the ref"),
    ("communicator_independent_recv_thread", True,
     "no-op: PS communicator threading (distributed/ps_server.py)"),
    ("communicator_is_sgd_optimizer", True, "no-op"),
    ("communicator_max_merge_var_num", 20, "no-op"),
    ("communicator_merge_sparse_bucket", 2000, "no-op"),
    ("communicator_merge_sparse_grad", True, "no-op"),
    ("communicator_min_send_grad_num_before_recv", 20, "no-op"),
    ("communicator_send_queue_size", 20, "no-op"),
    ("communicator_send_wait_times", 5, "no-op"),
    ("communicator_thread_pool_size", 5, "no-op"),
    ("conv_workspace_size_limit", 512,
     "no-op: XLA picks conv algorithms; no cuDNN workspace"),
    ("cudnn_batchnorm_spatial_persistent", False, "no-op: CUDA-only"),
    ("cudnn_deterministic", False,
     "no-op: XLA TPU executables are deterministic by construction"),
    ("cudnn_exhaustive_search", False, "no-op: CUDA-only"),
    ("cudnn_exhaustive_search_times", -1, "no-op: CUDA-only"),
    ("dist_threadpool_size", 0,
     "no-op: RPC concurrency is distributed/rpc.py thread-per-conn"),
    ("dygraph_debug", False, "no-op: use check_nan_inf / jax debug"),
    ("enable_parallel_graph", False,
     "no-op: multi-device execution is GSPMD, not graph replication"),
    ("fast_eager_deletion_mode", True,
     "no-op: buffer lifetime is XLA's; donation frees inputs"),
    ("fraction_of_cpu_memory_to_use", 1.0, "no-op"),
    ("fraction_of_gpu_memory_to_use", 0.92,
     "no-op: HBM budgeting is core/memory.py assert_hbm_within"),
    ("init_allocated_mem", False, "no-op"),
    ("initial_cpu_memory_in_mb", 500, "no-op"),
    ("inner_op_parallelism", 0, "no-op: XLA schedules ops"),
    ("io_threadpool_size", 100,
     "no-op: reader threads are reader.py + native data_feed.cc"),
    ("local_exe_sub_scope_limit", 256.0,
     "no-op: no per-device scopes (reference: double, MBytes)"),
    ("eager_delete_scope", True, "no-op: Scope GC is Python's"),
    ("enable_cublas_tensor_op_math", False, "no-op: CUDA-only"),
    ("fuse_parameter_groups_size", 3,
     "no-op: gradient fusion is XLA's; GradientMergeOptimizer covers "
     "the accumulation use case"),
    ("fuse_parameter_memory_size", -1, "no-op: same as groups_size"),
    ("gpu_allocator_retry_time", 2000, "no-op"),
    ("initial_gpu_memory_in_mb", 0, "no-op"),
    ("max_body_size", 2147483647,
     "no-op: distributed/rpc.py frames are length-prefixed without a "
     "hard cap"),
    ("print_sub_graph_dir", "",
     "no-op: graph dumps via debugger.draw_block_graphviz"),
    ("reader_queue_speed_test_mode", False,
     "no-op: test hook of the reference reader queue"),
    ("rpc_get_thread_num", 12, "no-op: thread-per-connection server"),
    ("rpc_prefetch_thread_num", 12, "no-op"),
    ("rpc_send_thread_num", 12, "no-op"),
    ("sync_nccl_allreduce", True,
     "no-op: XLA collectives are synchronous in-program ops"),
    ("free_idle_memory", False, "no-op"),
    ("limit_of_tmp_allocation", -1, "no-op"),
    ("memory_optimize_debug", "", "no-op: no memory-reuse pass to log"),
    ("times_excess_than_required_tmp_allocation", 2, "no-op"),
    ("memory_fraction_of_eager_deletion", 1.0, "no-op"),
    ("paddle_num_threads", 1, "no-op: host math is jax CPU"),
    ("pe_profile_fname", "", "no-op: use profiler.py traces"),
    ("reallocate_gpu_memory_in_mb", 0, "no-op"),
    ("rpc_deadline", 180000,
     "no-op: distributed/rpc.py uses socket timeouts"),
    ("rpc_disable_reuse_port", False, "no-op"),
    ("rpc_retry_bind_port", 3, "no-op"),
    ("rpc_retry_times", 3, "no-op"),
    ("rpc_server_profile_path", "./profile_ps", "no-op"),
    ("selected_gpus", "",
     "no-op: device selection is JAX_PLATFORMS / jax.devices()"),
    ("skip_fused_all_reduce_check", False, "no-op"),
    ("use_mkldnn", False, "no-op: CPU fallback is XLA:CPU"),
    ("use_ngraph", False, "no-op"),
    ("worker_update_interval_secs", 900, "no-op: PS heartbeat knob"),
    ("benchmark", False,
     "no-op: bench.py + profiler.py are the benchmark path"),
    ("eager_delete_tensor_gb", 0.0,
     "no-op: XLA buffer assignment frees dead buffers at compile "
     "time; donation covers step state"),
    ("enable_rpc_profiler", False, "no-op"),
    ("multiple_of_cupti_buffer_size", 1, "no-op: CUPTI is CUDA-only"),
    ("init_p2p", True, "no-op: ICI needs no P2P init"),
    ("cuda_dir", "", "no-op: dynload search path, CUDA-only"),
    ("cudnn_dir", "", "no-op"),
    ("nccl_dir", "", "no-op: collectives ride XLA/ICI"),
    ("mklml_dir", "", "no-op"),
    ("cupti_dir", "", "no-op"),
    ("use_pinned_memory", True, "no-op"),
    ("tracer_profile_fname", "", "no-op: dygraph tracing uses "
     "profiler.py"),
]:
    _compat(_name, _default, _help)
