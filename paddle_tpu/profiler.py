"""Profiler: phase annotations + device timeline.

Reference: platform/profiler.h RecordEvent/RecordBlock + CUPTI DeviceTracer
merged into a chrome-trace (tools/timeline.py). TPU equivalent: jax.profiler
traces (XPlane -> TensorBoard/Perfetto) with the same "annotate framework
phases, merge with device timeline" design via TraceAnnotation.
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "record_event", "cuda_profiler", "enable_host_profiler",
           "export_chrome_tracing", "host_phase_stats",
           "parse_hlo_op_map", "extract_op_scope", "summarize_xplane"]

_trace_dir = None


def _default_trace_dir():
    from .core.flags import FLAGS
    return FLAGS.profiler_trace_dir or "/tmp/paddle_tpu_profile"


def start_profiler(state="All", tracer_option=None, output_dir=None):
    global _trace_dir
    _trace_dir = output_dir or _default_trace_dir()
    jax.profiler.start_trace(_trace_dir)


def stop_profiler(sorted_key=None, profile_path=None):
    jax.profiler.stop_trace()


def reset_profiler():
    """Reset host-phase aggregates: the monitor's record_event
    accumulators + event ring, and the native profiler's event buffer
    when the C++ runtime is built. Reference: platform/profiler.cc
    ResetProfiler clears the global event vectors."""
    from .monitor import reset_phases
    reset_phases()
    from .native import profiler_reset
    profiler_reset()


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path=None,
             tracer_option=None):
    start_profiler(state, tracer_option, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def record_event(name):
    """RecordEvent RAII (profiler.h:81) for user code -> `trace.region`
    (the XPlane trace annotation the framework's own regions use, so a
    user's phases sit beside `gen.*` / `executor.*` on the profiler's
    host plane) + native host-phase event (native/src/profiler.cc) +
    monitor phase aggregate (monitor.phase: nested scopes accumulate
    EXCLUSIVE time per phase), so the chrome trace merges framework
    phases with the device timeline like the reference's host+CUPTI
    merge (device_tracer.cc:58) and host_phase_stats() answers "where
    does host step time go" without a trace viewer."""
    from .monitor import phase as _monitor_phase
    from .native import profiler_scope
    from .trace import region
    with region(name), profiler_scope(name), _monitor_phase(name):
        yield


def host_phase_stats():
    """Aggregated record_event phases: {name: {count, total_s,
    exclusive_s}} since the last reset_profiler()."""
    from .monitor import get_phase_stats
    return get_phase_stats()


def enable_host_profiler():
    """Start recording host-phase events in the native profiler."""
    from .native import profiler_enable
    profiler_enable()


def export_chrome_tracing(path: str) -> bool:
    """Dump recorded host events as chrome://tracing JSON (the reference's
    tools/timeline.py output format). Device-side traces live in the
    jax.profiler output dir (TensorBoard/Perfetto). Prefers the native
    profiler's buffer; when the C++ runtime is unavailable the monitor's
    phase-event ring (fed by the same record_event scopes) supplies the
    events, so the merge works in pure-Python deployments too."""
    from .native import profiler_dump
    if profiler_dump(path) >= 0:  # native: -1 = failure, else #events
        return True
    from .monitor import export_chrome_tracing as _monitor_export
    return _monitor_export(path) >= 0


@contextlib.contextmanager
def cuda_profiler(*a, **kw):  # name kept for source compat
    with profiler():
        yield


# The FLAGS_op_trace_scopes annotation emitted by core/lowering._op_scope:
# '{op.type}:{block}/{op_idx}', where op.type may itself contain '::'
# (grad::generic). Appears as one path component of HLO op_name metadata
# and of XPlane name-scope lines; the LAST match in a path is the
# innermost (most specific) op.
import re as _re

_SCOPE_RE = _re.compile(r"((?:[A-Za-z0-9_.]|::)+):(\d+)/(\d+)")


def extract_op_scope(op_name: str):
    """The innermost '{type}:{block}/{idx}' annotation in an HLO op_name
    path, as (op_type, block_idx, op_idx) — or None when the path
    carries no framework scope (e.g. parameter copies, infeed)."""
    m = None
    for m in _SCOPE_RE.finditer(op_name):
        pass
    if m is None:
        return None
    return m.group(1), int(m.group(2)), int(m.group(3))


def parse_hlo_op_map(hlo_text: str):
    """{hlo instruction name -> op_name metadata} from post-optimization
    HLO text (Executor.compiled_hlo). XPlane device/host events carry
    the instruction name (hlo_op stat); joining through this map and
    extract_op_scope attributes each event to the framework op that
    emitted it — source-level annotation carried into fused-HLO
    profiles ("Operator Fusion in XLA", PAPERS.md)."""
    op_map = {}
    pat = _re.compile(
        r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?metadata=\{[^}]*?"
        r"op_name=\"([^\"]+)\"", _re.M)
    for name, op_name in pat.findall(hlo_text):
        op_map[name] = op_name
    return op_map


def summarize_xplane(trace_dir=None, top=25, hlo_text=None):
    """Parse the newest .xplane.pb under trace_dir and aggregate DEVICE
    event durations by kernel name + category (the reference's
    print_profiler table, re-expressed for XPlane). Returns a dict:
    {"total_us", "by_category": {cat: us}, "top_ops": [(name, us)]}.

    Categories: mxu-fusion, dot/conv, pallas/custom-call, rng,
    collective, infeed/host, copy/layout, fusion, other.

    When `hlo_text` (the compiled HLO of the traced step,
    Executor.compiled_hlo) is given, each event is additionally
    attributed to the framework op whose FLAGS_op_trace_scopes
    annotation its op_name metadata carries, and the result gains
    "by_framework_op": {scope: {op_type, block, op, calls, device_us,
    host_us, total_us, min_us, max_us}} with an "(unattributed)" bucket
    for events outside any scope.
    """
    import glob
    import os
    from collections import defaultdict

    trace_dir = trace_dir or _trace_dir or _default_trace_dir()
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(paths[-1], "rb") as f:
        space.ParseFromString(f.read())

    def categorize(name):
        n = name.lower()
        if "fusion" in n and ("dot" in n or "conv" in n):
            return "mxu-fusion"
        if n.startswith(("%dot", "dot", "convolution")) or "gemm" in n:
            return "dot/conv"
        if "custom-call" in n or "tpu_custom_call" in n or "mosaic" in n:
            return "pallas/custom-call"
        if "rng" in n or "threefry" in n:
            return "rng"
        if any(c in n for c in ("all-reduce", "all-gather",
                                "collective", "reduce-scatter",
                                "permute")):
            return "collective"
        if "infeed" in n or "outfeed" in n or "host" in n:
            return "infeed/host"
        if "copy" in n or "transpose" in n or "bitcast" in n:
            return "copy/layout"
        if "fusion" in n:
            return "fusion"
        return "other"

    by_cat = defaultdict(float)
    by_op = defaultdict(float)
    total = 0.0
    # per-framework-op accumulators (hlo_text mode): scope key ->
    # [calls, device_us, host_us, min_us, max_us]
    op_map = parse_hlo_op_map(hlo_text) if hlo_text else None
    by_fw = {}

    # runtime bookkeeping spans on host threads, not ops
    _SKIP = ("end: ", "thunkexecutor", "threadpoollistener")

    def attribute(name, us, device):
        op_name = op_map.get(name) or op_map.get(name.lstrip("%"))
        scope = extract_op_scope(op_name) if op_name else None
        key = f"{scope[0]}:{scope[1]}/{scope[2]}" if scope \
            else "(unattributed)"
        acc = by_fw.get(key)
        if acc is None:
            acc = by_fw[key] = [0, 0.0, 0.0, float("inf"), 0.0]
        acc[0] += 1
        acc[1 if device else 2] += us
        acc[3] = min(acc[3], us)
        acc[4] = max(acc[4], us)

    def accumulate(plane, line, device=True, count=True):
        nonlocal total
        for ev in line.events:
            meta = plane.event_metadata.get(ev.metadata_id)
            name = meta.name if meta else "?"
            low = name.lower()
            if any(s in low for s in _SKIP):
                continue
            us = ev.duration_ps / 1e6
            if count:
                by_op[name] += us
                by_cat[categorize(name)] += us
                total += us
            if op_map is not None:
                attribute(name, us, device)

    # device planes (/device:TPU:N) carry the "XLA Ops" timeline; match
    # it exactly — derived lines ("Framework Ops", name scopes) repeat
    # the same durations and would double-count
    device_planes = [p for p in space.planes
                     if "/device" in p.name.lower()]
    for plane in device_planes:
        for line in plane.lines:
            if line.name.lower() in ("xla ops", "ops"):
                accumulate(plane, line, device=True)
    have_device = total > 0.0
    if not have_device:
        # CPU runs have no device plane: fall back to the XLA client's
        # host execution threads so the tool still works for plumbing
        # tests and host-only profiling. Host spans can nest, so this
        # mode is approximate — fine for relative breakdowns.
        for plane in space.planes:
            for line in plane.lines:
                if "xla" in line.name.lower():
                    accumulate(plane, line, device=False)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    out = {"total_us": total,
           "by_category": dict(sorted(by_cat.items(),
                                      key=lambda kv: -kv[1])),
           "top_ops": top_ops}
    if op_map is not None:
        fw = {}
        for key, (calls, dev_us, host_us, mn, mx) in by_fw.items():
            scope = extract_op_scope(key)
            fw[key] = {
                "op_type": scope[0] if scope else key,
                "block": scope[1] if scope else -1,
                "op": scope[2] if scope else -1,
                "calls": calls,
                "device_us": dev_us,
                "host_us": host_us,
                "total_us": dev_us + host_us,
                "min_us": mn,
                "max_us": mx,
            }
        out["by_framework_op"] = dict(sorted(
            fw.items(), key=lambda kv: -kv[1]["total_us"]))
    return out
