"""Per-request distributed tracing tests (paddle_tpu/trace.py).

Covers the span primitives (context propagation within and across
threads, W3C traceparent parsing), the head+tail sampling rules
(errored and slow requests are ALWAYS kept, the ring is bounded), the
request-completion choke point (`complete_request` finishes the trace
exactly once at the outermost owner), the exporters, the end-to-end
GenerationEngine span tree (queue -> prefill -> decode with a nested
fetch, critical path consistent with measured e2e, zero post-warmup
compiles), HTTP trace continuation, and the tools/trace_report.py +
validate_bench_json.py trace_report surfaces.
"""
import contextlib
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

import paddle_tpu as fluid
from paddle_tpu import trace
from paddle_tpu.models import gpt
from paddle_tpu.serving import GenerationEngine, GenerationRequest, serve

VOCAB, SEQ = 16, 12

_TRACE_FLAGS = ("enable_trace", "trace_sample", "trace_tail_slow_ms",
                "trace_ring_capacity", "enable_monitor")


def _load_tool(name):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@contextlib.contextmanager
def _trace_on(sample=1.0, tail_slow_ms=0.0, ring=8192, monitor=False):
    from paddle_tpu import monitor as mon
    prev = {k: getattr(fluid.FLAGS, k) for k in _TRACE_FLAGS}
    fluid.set_flags({"FLAGS_enable_trace": True,
                     "FLAGS_trace_sample": sample,
                     "FLAGS_trace_tail_slow_ms": tail_slow_ms,
                     "FLAGS_trace_ring_capacity": ring,
                     "FLAGS_enable_monitor": monitor})
    trace.reset()
    if monitor:
        mon.reset_stats()
    try:
        yield
    finally:
        trace.reset()
        if monitor:
            mon.reset_stats()
        fluid.set_flags({f"FLAGS_{k}": v for k, v in prev.items()})


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------

def test_disabled_tracing_is_inert():
    prev = fluid.FLAGS.enable_trace
    fluid.set_flags({"FLAGS_enable_trace": False})
    try:
        assert trace.start_span("op") is None
        assert trace.current_span() is None
        assert trace.current_trace_id() is None
        assert not trace.finish_trace(None)
        trace.complete_request(None)           # must not raise
        trace.end_span(None)
        with trace.use_span(None) as s:
            assert s is None
        with trace.span("op") as s:
            assert s is None
        assert trace.record_span("op", 0.0, 1.0, None) is None
    finally:
        fluid.set_flags({"FLAGS_enable_trace": prev})


def test_traceparent_parse_format_roundtrip():
    with _trace_on():
        root = trace.start_span("op")
        hdr = trace.format_traceparent(root)
        assert hdr == f"00-{root.trace_id}-{root.span_id}-01"
        assert trace.parse_traceparent(hdr) == (root.trace_id,
                                                root.span_id)
        trace.finish_trace(root)
    # malformed headers must be ignored, not propagated
    tid, sid = "0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331"
    assert trace.parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid)
    # case-insensitive per spec
    assert trace.parse_traceparent(
        f"00-{tid.upper()}-{sid}-01") == (tid, sid)
    for bad in (None, "", "garbage",
                f"00-{tid}-{sid}",             # too few fields
                f"00-{tid}-{sid}-01-extra",    # too many fields
                f"ff-{tid}-{sid}-01",          # forbidden version
                f"00-{tid[:-2]}-{sid}-01",     # short trace id
                f"00-{tid}-{sid[:-1]}-01",     # short span id
                f"00-{'z' * 32}-{sid}-01",     # non-hex
                f"00-{'0' * 32}-{sid}-01",     # all-zero trace id
                f"00-{tid}-{'0' * 16}-01"):    # all-zero span id
        assert trace.parse_traceparent(bad) is None, bad


def test_span_tree_context_and_events():
    with _trace_on():
        root = trace.start_span("root", attrs={"k": 1})
        assert root.parent_id is None and trace.is_root(root)
        with trace.use_span(root):
            assert trace.current_span() is root
            assert trace.current_trace_id() == root.trace_id
            with trace.span("child", attrs={"j": 2}) as c:
                assert c.trace_id == root.trace_id
                assert c.parent_id == root.span_id
                c.add_event("tick", n=3)
                with trace.span("grandchild") as g:
                    assert g.parent_id == c.span_id
        assert c.dur_ms is not None and c.status == "ok"
        assert c.events[0]["name"] == "tick" and c.events[0]["n"] == 3
        # error inside span() marks status and re-raises
        with pytest.raises(ValueError):
            with trace.use_span(root):
                with trace.span("boom"):
                    raise ValueError("nope")
        assert trace.finish_trace(root)        # sample=1.0 -> head keep
        spans = trace.drain_spans()
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"root", "child", "grandchild", "boom"}
        assert by_name["boom"]["status"] == "error"
        assert by_name["root"]["attrs"]["keep"] == "head"
        assert all(s["dur_ms"] is not None for s in spans)


def test_thread_handoff_propagation():
    """Contextvars do not cross threads; the hand-off contract is to
    pass the Span object and re-enter it with use_span()."""
    with _trace_on():
        root = trace.start_span("root")
        seen = {}

        def worker():
            # fresh thread: no ambient span leaks in
            seen["ambient"] = trace.current_span()
            with trace.use_span(root):
                child = trace.start_span("worker_op")
                trace.end_span(child)
                seen["child"] = child

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert seen["ambient"] is None
        assert seen["child"].trace_id == root.trace_id
        assert seen["child"].parent_id == root.span_id
        trace.finish_trace(root)


def test_record_span_retroactive():
    with _trace_on():
        root = trace.start_span("root")
        sp = trace.record_span("sub", 100.0, 100.25, root,
                               attrs={"bytes": 8})
        assert sp.parent_id == root.span_id and sp.t_start == 100.0
        assert abs(sp.dur_ms - 250.0) < 1e-6
        assert sp.attrs["bytes"] == 8
        trace.finish_trace(root)


# ---------------------------------------------------------------------------
# Head + tail sampling
# ---------------------------------------------------------------------------

def test_tail_keep_rules_fixed_threshold():
    with _trace_on(sample=0.0, tail_slow_ms=5.0):
        # fast + ok + head coin lost -> dropped
        r = trace.start_span("req")
        assert not trace.finish_trace(r, e2e_ms=1.0)
        # slower than FLAGS_trace_tail_slow_ms -> kept
        r = trace.start_span("req")
        assert trace.finish_trace(r, e2e_ms=50.0)
        assert r.attrs["keep"] == "slow"
        # errored -> always kept, regardless of latency
        r = trace.start_span("req")
        assert trace.finish_trace(r, error="boom", e2e_ms=0.1)
        assert r.attrs["keep"] == "error" and r.status == "error"
        assert r.attrs["error"] == "boom"
        kept = trace.drain_spans()
        assert [s["attrs"]["keep"] for s in kept] == ["slow", "error"]
    with _trace_on(sample=1.0, tail_slow_ms=5.0):
        r = trace.start_span("req")
        assert trace.finish_trace(r, e2e_ms=0.1)
        assert r.attrs["keep"] == "head"


def test_tail_rolling_p95_threshold():
    """With FLAGS_trace_tail_slow_ms=0 the slow rule self-calibrates to
    a rolling p95 — undefined until enough requests have finished."""
    with _trace_on(sample=0.0, tail_slow_ms=0.0):
        assert trace.slow_threshold_ms() is None
        for _ in range(30):
            r = trace.start_span("req")
            assert not trace.finish_trace(r, e2e_ms=10.0)
        thresh = trace.slow_threshold_ms()
        assert thresh is not None and abs(thresh - 10.0) < 1e-6
        r = trace.start_span("req")
        assert trace.finish_trace(r, e2e_ms=100.0)   # 10x the p95
        assert r.attrs["keep"] == "slow"
        # record_latency=False traces don't drag the window (the
        # batch-span exemption)
        r = trace.start_span("batch")
        assert not trace.finish_trace(r, e2e_ms=0.01,
                                      record_latency=False)
        assert abs(trace.slow_threshold_ms() - 10.0) < 1e-6


def test_ring_capacity_bound_and_drain():
    with _trace_on(sample=1.0, ring=6):
        ids = []
        for _ in range(10):
            r = trace.start_span("req")
            ids.append(r.trace_id)
            trace.finish_trace(r)
        ring = trace.ring_spans()
        assert len(ring) == 6
        # oldest evicted first
        assert [s["trace_id"] for s in ring] == ids[4:]
        assert trace.drain_spans() == ring
        assert trace.ring_spans() == []


def test_complete_request_root_vs_child():
    """complete_request runs the tail decision exactly once, at the
    outermost owner: child spans are just ended, the root finishes the
    trace."""
    with _trace_on(sample=1.0):
        root = trace.start_span("outer")
        child = trace.start_span("gen.request", parent=root)
        trace.complete_request(child)          # not root -> end only
        assert child.dur_ms is not None
        assert trace.is_root(root)             # trace still in flight
        assert trace.ring_spans() == []
        trace.complete_request(root, e2e_ms=3.0)
        assert not trace.is_root(root)
        spans = trace.drain_spans()
        assert {s["name"] for s in spans} == {"outer", "gen.request"}
        assert spans[0]["attrs"]["e2e_ms"] == 3.0


def test_trace_stats_counters():
    with _trace_on(sample=0.0, tail_slow_ms=5.0, monitor=True):
        from paddle_tpu import monitor
        r = trace.start_span("req")
        trace.start_span("child", parent=r)
        trace.finish_trace(r, e2e_ms=50.0)     # slow -> both spans kept
        r = trace.start_span("req")
        trace.finish_trace(r, e2e_ms=0.1)      # dropped
        c = monitor.get_stats_snapshot()["counters"]
        assert c["trace.spans_started"] == 3
        assert c["trace.spans_kept"] == 2
        assert c["trace.spans_dropped"] == 1
        g = monitor.get_stats_snapshot()["gauges"]
        assert g["trace.ring_spans"] == 2.0


def test_exporters_jsonl_and_chrome(tmp_path):
    with _trace_on():
        root = trace.start_span("req")
        with trace.use_span(root):
            with trace.span("work"):
                pass
        trace.finish_trace(root)
        jl = str(tmp_path / "spans.jsonl")
        n = trace.export_jsonl(jl, trace.ring_spans())
        assert n == 2
        recs = [json.loads(x) for x in open(jl)]
        assert all(r["kind"] == "span" for r in recs)
        ct = str(tmp_path / "trace.json")
        n = trace.export_chrome_tracing(ct, include_phases=False)
        assert n == 2
        doc = json.load(open(ct))
        ev = doc["traceEvents"][0]
        assert ev["ph"] == "X"
        assert ev["args"]["trace_id"] == root.trace_id


# ---------------------------------------------------------------------------
# trace_report + validate_bench_json surfaces
# ---------------------------------------------------------------------------

def _sp(trace_id, span_id, parent_id, name, t0, dur_ms, status="ok",
        attrs=None):
    return {"kind": "span", "trace_id": trace_id, "span_id": span_id,
            "parent_id": parent_id, "name": name, "t_start": t0,
            "dur_ms": dur_ms, "status": status, "attrs": attrs or {},
            "events": [], "links": [], "tid": 1}


def test_trace_report_build_and_consistency():
    trp = _load_tool("trace_report")
    t1, t2 = "a" * 32, "b" * 32
    spans = [
        _sp(t1, "r1", None, "request", 100.0, 10.0,
            attrs={"e2e_ms": 10.0, "keep": "head"}),
        _sp(t1, "q1", "r1", "queue", 100.0, 2.0),
        _sp(t1, "p1", "r1", "prefill", 100.002, 3.0),
        _sp(t1, "d1", "r1", "decode", 100.005, 5.0),
        _sp(t1, "f1", "d1", "fetch", 100.005, 1.0),
        # second trace: a child LONGER than its parent -> inconsistency
        _sp(t2, "r2", None, "request", 200.0, 5.0,
            attrs={"e2e_ms": 5.0, "keep": "slow"}),
        _sp(t2, "q2", "r2", "queue", 200.0, 50.0),
    ]
    by_id, children = trp.build_index(spans)
    roots = trp.trace_roots(spans, by_id)
    assert {r["span_id"] for r in roots} == {"r1", "r2"}
    row = trp.analyze_request(spans[0], children)
    assert row["e2e_ms"] == 10.0
    assert abs(row["critical_path_ms"] - 10.0) < 1e-6
    assert row["queue_ms"] == 2.0 and row["fetch_ms"] == 1.0
    assert row["n_spans"] == 5
    checked, violations = trp.check_consistency(spans, children)
    assert checked == 5 and len(violations) == 1
    assert "queue" in violations[0] and "request" in violations[0]

    report = trp.build_report(spans, top=5, source="unit")
    assert report["kind"] == "trace_report"
    assert report["n_traces"] == 2 and report["n_requests"] == 2
    assert report["keep"] == {"head": 1, "slow": 1}
    assert abs(report["breakdown_ms"]["queue"]["mean_ms"] - 26.0) < 1e-6
    assert report["consistency"]["violations"] == 1
    # slowest sorted by e2e descending
    assert [r["trace_id"] for r in report["slowest"]] == [t1, t2]
    text = trp.render(report)
    assert "critical" in text and "queue" in text

    v = _load_tool("validate_bench_json")
    assert v.validate_trace_report(report) == []
    bad = json.loads(json.dumps(report))
    bad["n_spans"] = -1
    del bad["breakdown_ms"]["decode"]
    bad["consistency"]["checked"] = "x"
    errs = v.validate_trace_report(bad)
    assert any("n_spans" in e for e in errs)
    assert any("breakdown_ms.decode" in e for e in errs)
    assert any("consistency.checked" in e for e in errs)


# ---------------------------------------------------------------------------
# End to end: GenerationEngine span tree + HTTP continuation
# ---------------------------------------------------------------------------

def _fresh_engine(max_slots=2, max_seq=SEQ, **kw):
    cfg = gpt.gpt_small(vocab_size=VOCAB, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq_len=max_seq,
                        dropout=0.0, use_flash=False)
    eng = GenerationEngine(cfg, fluid.Scope(), exe=fluid.Executor(),
                           max_slots=max_slots, max_seq=max_seq, **kw)
    eng.init_scope()
    return eng


def test_engine_end_to_end_span_tree():
    """The acceptance shape: a traced request produces a complete
    queue -> prefill -> decode(+fetch) tree whose critical path agrees
    with the measured e2e, with zero post-warmup compiles. Reuses the
    same engine to check the error tail rule: a rejected request is
    kept even at sample=0 (one engine build — this is a 1-core box)."""
    trp = _load_tool("trace_report")
    from paddle_tpu.serving import QueueFullError
    with _trace_on(sample=1.0):
        eng = _fresh_engine()
        eng.start()
        try:
            t0 = time.perf_counter()
            root = trace.start_span("request")
            with trace.use_span(root):
                resp = eng.submit(GenerationRequest([0, 1, 2], 5))
            out = resp.result(timeout=60.0)
            e2e_ms = (time.perf_counter() - t0) * 1e3
            trace.finish_trace(root, e2e_ms=e2e_ms)
            assert out["finish_reason"] == "length"
            assert eng.post_warmup_compiles() == 0, eng.cache_stats()
            spans = trace.drain_spans()
            # rejected request at sample=0: errors are ALWAYS kept
            fluid.set_flags({"FLAGS_trace_sample": 0.0,
                             "FLAGS_trace_tail_slow_ms": 1e9})
            eng.queue_capacity = 0
            with pytest.raises(QueueFullError):
                eng.submit(GenerationRequest([0, 1], 2))
        finally:
            eng.stop()
        err_spans = trace.drain_spans()
        assert err_spans, "errored request was not kept"
        err_root = next(s for s in err_spans
                        if s["name"] == "gen.request")
        assert err_root["status"] == "error"
        assert err_root["attrs"]["keep"] == "error"
        assert "QueueFullError" in err_root["attrs"]["error"]
        by_id, children = trp.build_index(spans)
        roots = [r for r in trp.trace_roots(spans, by_id)
                 if r["name"] in trp.REQUEST_ROOTS]
        assert len(roots) == 1
        rd = roots[0]
        names = {s["name"] for s in trp._walk(rd, children)}
        assert {"gen.request", "queue", "prefill",
                "decode", "fetch"} <= names
        row = trp.analyze_request(rd, children)
        crit = row["critical_path_ms"]
        # queue + prefill + decode must account for the request (the
        # fetch child is nested inside decode, not double-counted)
        assert abs(e2e_ms - crit) <= 0.10 * e2e_ms + 5.0, (e2e_ms, row)
        checked, violations = trp.check_consistency(spans, children)
        assert checked > 0 and violations == [], violations
        # gen.request carries the engine's own e2e/token metadata
        gen = next(s for s in spans if s["name"] == "gen.request")
        assert gen["parent_id"] == rd["span_id"]
        assert gen["attrs"]["tokens"] == 5
        assert gen["attrs"]["finish_reason"] == "length"


def _post(url, obj, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, dict(r.headers), json.loads(r.read().decode())


def test_http_traceparent_continuation_and_request_id():
    with _trace_on(sample=1.0):
        eng = _fresh_engine()
        srv = serve(gen_engine=eng, port=0)   # starts the engine too
        try:
            url = srv.url + "/v1/generate"
            body = {"prompt": [0, 1, 2], "max_new_tokens": 3}
            # caller sends a valid traceparent -> the server continues
            # that trace and echoes it back
            tid, sid = "c" * 32, "d" * 16
            code, hdrs, _ = _post(url, body, headers={
                "traceparent": f"00-{tid}-{sid}-01"})
            assert code == 200
            assert hdrs["X-Request-Id"] == tid
            got = trace.parse_traceparent(hdrs["traceparent"])
            assert got is not None and got[0] == tid
            # no (or malformed) traceparent -> a fresh trace id
            code, hdrs2, _ = _post(url, body,
                                   headers={"traceparent": "garbage"})
            assert code == 200
            rid = hdrs2["X-Request-Id"]
            assert rid != tid and len(rid) == 32
            int(rid, 16)
        finally:
            srv.close()
            eng.stop()
        # the handler finishes the trace just after writing the reply;
        # give that thread a beat before inspecting the ring
        deadline = time.time() + 5.0
        spans = trace.ring_spans()
        while time.time() < deadline and len(
                {s["trace_id"] for s in spans}) < 2:
            time.sleep(0.02)
            spans = trace.ring_spans()
        mine = [s for s in spans if s["trace_id"] == tid]
        assert mine, "continued trace never reached the ring"
        http_root = next(s for s in mine if s["name"] == "http.request")
        assert http_root["parent_id"] == sid       # remote parent
        assert http_root["attrs"]["http.status"] == 200
        names = {s["name"] for s in mine}
        assert {"gen.request", "queue", "prefill", "decode"} <= names


def test_loadgen_trace_mode_end_to_end(tmp_path, capsys):
    """`serving_loadgen --generate --trace`: exit 0, a span dump on
    disk, a trace audit record with zero violations, and a
    trace_report over the dump that validates against the schema."""
    loadgen = _load_tool("serving_loadgen")
    trp = _load_tool("trace_report")
    v = _load_tool("validate_bench_json")
    out = str(tmp_path / "gen.jsonl")
    spans_out = str(tmp_path / "gen.spans.jsonl")
    with _trace_on():   # loadgen arms the flags itself; restore after
        rc = loadgen.main(["--generate", "--slots", "2",
                           "--requests", "6", "--max-new-tokens", "4",
                           "--check-compiles", "--trace",
                           "--trace-out", spans_out, "--out", out])
    capsys.readouterr()
    assert rc == 0
    rec = next(json.loads(ln) for ln in open(out) if ln.strip())
    tr = rec["trace"]
    assert tr["requests"] == 6
    assert tr["incomplete"] == 0
    assert tr["crit_path_violations"] == 0
    assert tr["consistency_violations"] == 0
    assert tr["spans"] > 0 and os.path.exists(spans_out)
    spans = trp.load_spans([spans_out])
    assert len(spans) == tr["spans"]
    report = trp.build_report(spans, source=spans_out)
    assert report["n_requests"] == 6
    assert v.validate_trace_report(report) == []
    rep_out = str(tmp_path / "report.jsonl")
    assert trp.main([spans_out, "--out", rep_out, "--strict"]) == 0
    capsys.readouterr()
    assert v.validate_file(rep_out) == []


# ---------------------------------------------------------------------------
# Regions of the program's own host code and the iteration record
# ---------------------------------------------------------------------------

def _small_step():
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, 4], dtype="float32",
                        append_batch_size=False)
        out = layers.fc(x, size=3)
    return main, startup, out


def test_region_seconds_go_to_the_open_iteration_exclusively():
    """A region adds its own seconds, nested regions taken out, to the
    record open on this thread: the values add up to what the outermost
    region covers. With no record open it adds nothing anywhere."""
    trace.reset()
    with trace.region("gen.sample"):
        pass
    assert trace.iteration_records() == []
    rec = trace.begin_iteration(slots=4, block_size=8, kv_blocks_total=9)
    with trace.region("gen.iteration"):
        with trace.region("gen.decode.step"):
            with trace.region("executor.dispatch"):
                time.sleep(0.02)
            time.sleep(0.01)
        with trace.region("gen.sample"):
            time.sleep(0.01)
        with trace.region("gen.sample"):
            pass
    rec.decode_rows = 2
    trace.end_iteration(rec)
    got, = trace.iteration_records()
    host = got["host_s"]
    assert set(host) == {"gen.iteration", "gen.decode.step",
                         "executor.dispatch", "gen.sample"}
    assert host["executor.dispatch"] >= 0.02
    assert host["gen.decode.step"] >= 0.01
    assert host["gen.sample"] >= 0.01
    # durations that included their nested regions would add up to
    # more than twice the turn
    assert 0.04 <= sum(host.values()) <= got["t_end"] - got["t_start"]
    assert (got["slots"], got["block_size"], got["kv_blocks_total"],
            got["decode_rows"]) == (4, 8, 9, 2)
    assert "_open" not in got
    # a region entered after the record closed leaves it alone
    with trace.region("gen.admit"):
        pass
    assert set(trace.iteration_records()[0]["host_s"]) == set(host)
    trace.reset()


def test_iteration_ring_is_bounded_and_drops_turns_that_only_waited():
    # the benchmark's counters read a whole 40 s window from the ring
    # after it closed: it has to hold one at turns of 2.5 ms
    assert trace.ITERATION_RING >= 16384
    trace.reset()
    rec = trace.begin_iteration(1, 0, 0)
    trace.end_iteration(rec, keep=False)
    assert trace.iteration_records() == []
    for i in range(trace.ITERATION_RING + 5):
        rec = trace.begin_iteration(1, 0, 0)
        rec.tokens_emitted = i
        trace.end_iteration(rec)
    recs = trace.iteration_records()
    assert len(recs) == trace.ITERATION_RING
    assert recs[0]["tokens_emitted"] == 5
    assert recs[-1]["tokens_emitted"] == trace.ITERATION_RING + 4
    trace.reset()
    assert trace.iteration_records() == []


def test_executor_regions_are_children_of_the_current_span():
    """Under FLAGS_enable_trace the Executor's regions close children
    of whatever span is current, as they run and nested as they run:
    executor.feed (and, on a miss, executor.compile) inside
    executor.resolve. `profiler.record_event` is the same primitive."""
    import numpy as np
    from paddle_tpu import profiler
    main, startup, out = _small_step()
    scope, exe = fluid.Scope(), fluid.Executor()
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        # tracing off: the regions run and record no span
        exe.run(main, feed=feed, fetch_list=[out])
        with _trace_on(sample=1.0):
            root = trace.start_span("step")
            with trace.use_span(root):
                exe.run(main, feed=feed, fetch_list=[out])
                with profiler.record_event("user.phase"):
                    pass
                assert trace.current_span() is root
            trace.finish_trace(root)
            spans = trace.drain_spans()
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"step", "executor.resolve", "executor.feed",
                            "executor.dispatch", "executor.fetch",
                            "user.phase"}
    rid = by_name["step"]["span_id"]
    for name in ("executor.resolve", "executor.dispatch",
                 "executor.fetch", "user.phase"):
        assert by_name[name]["parent_id"] == rid, name
    assert by_name["executor.feed"]["parent_id"] == \
        by_name["executor.resolve"]["span_id"]
    assert all(s["dur_ms"] is not None and s["status"] == "ok"
               for s in spans)
    # a new feed shape misses the cache: the region that says so
    with fluid.scope_guard(scope), _trace_on(sample=1.0):
        root = trace.start_span("step")
        with trace.use_span(root):
            exe.run(main, feed={"x": np.ones((3, 4), np.float32)},
                    fetch_list=[out])
        trace.finish_trace(root)
        spans = {s["name"]: s for s in trace.drain_spans()}
    assert spans["executor.compile"]["parent_id"] == \
        spans["executor.resolve"]["span_id"]


def test_iteration_record_counts_prefill_tiles_beside_requests():
    """`prefill_tiles` is the rows a turn's prefill step fed, `prefill_
    rows` the requests it advanced: a prompt of five pages takes four
    of a four-row step's tiles and the next step's first, where the
    short prompt behind it gets its one. The monitor's
    `serving.gen_prefill_tiles` is the records' sum."""
    from paddle_tpu import monitor
    prev = fluid.FLAGS.enable_monitor
    fluid.set_flags({"FLAGS_enable_monitor": True})
    monitor.reset_stats()
    eng = _fresh_engine(max_slots=4, max_seq=40, block_size=4)
    try:
        # queued before the worker starts: one admission, in this order
        long_ = eng.submit(GenerationRequest(
            [i % VOCAB for i in range(18)], 2, timeout_ms=6e5))
        short = eng.submit(GenerationRequest([3, 2, 1], 2, timeout_ms=6e5))
        t0 = time.perf_counter()
        eng.start()
        try:
            assert len(long_.result(timeout=60.0)["tokens"]) == 2
            assert len(short.result(timeout=60.0)["tokens"]) == 2
        finally:
            eng.stop()
        counters = monitor.get_stats_snapshot()["counters"]
    finally:
        monitor.reset_stats()
        fluid.set_flags({"FLAGS_enable_monitor": prev})
    recs = [r for r in trace.iteration_records()
            if r["t_start"] >= t0 and r["prefill_rows"]]
    assert [(r["prefill_rows"], r["prefill_tiles"], r["prefill_tokens"])
            for r in recs] == [(1, 4, 16), (2, 2, 1 + 2)]
    assert long_.timings["prefill_steps"] == 2
    assert short.timings["prefill_steps"] == 1
    assert counters["serving.gen_prefill_tiles"] == 6
    assert counters["serving.gen_chunked_prefills"] == 3
    assert all(r["prefill_tiles"] == 0 for r in trace.iteration_records()
               if r["t_start"] >= t0 and not r["prefill_rows"])


def _host_events(path):
    """(name, start_ns, end_ns) of every event on the trace's host
    planes."""
    import jax
    out = []
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def test_regions_land_on_the_profilers_host_plane_nested(tmp_path):
    """Any jax.profiler trace shows the engine's and the Executor's
    regions on the host plane, on the device trace's clock, nested as
    the code nests them, with no flag set."""
    import glob

    import jax
    eng = _fresh_engine()
    eng.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = eng.submit(GenerationRequest([0, 1, 2, 3], 4)) \
            .result(timeout=60.0)
    finally:
        # the worker first: a region still open when the session stops
        # is not in the trace
        eng.stop()
        jax.profiler.stop_trace()
    assert len(out["tokens"]) == 4
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = _host_events(path)
    by_name = {}
    for name, t0, t1 in events:
        by_name.setdefault(name, []).append((t0, t1))
    for name in ("gen.iteration", "gen.admit", "gen.prefill.stage",
                 "gen.prefill.step", "gen.decode.stage",
                 "gen.decode.step", "gen.sample", "executor.resolve",
                 "executor.feed", "executor.dispatch", "executor.fetch"):
        assert name in by_name, (name, sorted(by_name)[:40])
    assert "executor.compile" not in by_name   # nothing recompiled

    def inside(child, parent):
        return all(any(p0 <= c0 and c1 <= p1
                       for p0, p1 in by_name[parent])
                   for c0, c1 in by_name[child])

    assert inside("gen.sample", "gen.iteration")
    assert inside("gen.decode.step", "gen.iteration")
    assert inside("executor.feed", "executor.resolve")
    # every Executor region of the window ran inside a step of the loop
    steps = by_name["gen.decode.step"] + by_name["gen.prefill.step"]
    for name in ("executor.resolve", "executor.dispatch",
                 "executor.fetch"):
        assert all(any(p0 <= c0 and c1 <= p1 for p0, p1 in steps)
                   for c0, c1 in by_name[name]), name
