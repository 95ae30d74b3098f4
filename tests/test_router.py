"""Router-tier tests: least-loaded dispatch, health-gated failover,
session affinity, preemption-aware membership, zero-downtime hot-swap,
the RouterHTTP front end (shed with Retry-After), two-tier trace
propagation, the /healthz worst-state-wins aggregation table, and the
router loadgen record against its validator + report section.

Unit-level routing tests drive the Router against stub engines (no
model, no warmup) so they pin the dispatch policy itself; the hot-swap,
drain, trace, and loadgen tests run real warmed engines on the same
tiny seq-pad-invariant model tests/test_serving.py uses.
"""
import contextlib
import json
import os
import signal
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import trace
from paddle_tpu import layers
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
from paddle_tpu.serving import (EngineConfig, OverloadedError,
                                QueueFullError, Replica, Router,
                                RouterHTTP, ServingEngine,
                                ServingHTTPServer, serve)
from paddle_tpu.serving.http import _STATE_RANK

FEAT = 6


@pytest.fixture(scope="module")
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("router_model"))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data("x", shape=[-1, -1, FEAT], dtype="float32",
                        append_batch_size=False)
        s = layers.reduce_sum(x, dim=1)
        h = layers.fc(s, size=16, act="relu")
        pred = layers.fc(h, size=4, act="softmax")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=main)
    return d


def _engine(model_dir, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("seq_buckets", (4, 8))
    kw.setdefault("max_wait_us", 1000)
    kw.setdefault("queue_capacity", 64)
    kw.setdefault("default_timeout_ms", 10000)
    return ServingEngine(EngineConfig(model_dir, **kw))


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, json.loads(r.read() or b"{}"), dict(
                r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _load_tool(name):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# Stub backends: pin the routing policy without models or warmup
# ---------------------------------------------------------------------------

class _StubEngine:
    """Duck-typed ServingEngine: load/health/predict/output_names plus
    the lifecycle hooks Replica touches."""

    def __init__(self, tag, load=0):
        self.tag = float(tag)
        self.load_value = load
        self.calls = 0
        self.fail = None

    def start(self):
        pass

    def stop(self, drain=True, timeout=30.0):
        pass

    def cache_stats(self):
        return {"misses": 0}

    def load(self):
        return self.load_value

    def health(self):
        return {"state": "ready", "retry_after_s": 0.0}

    def output_names(self):
        return ["y"]

    def predict(self, feed, timeout_ms=None):
        self.calls += 1
        if self.fail is not None:
            raise self.fail
        return [np.full((1, 1), self.tag, np.float32)]


class _StubGenResult:
    def __init__(self, payload):
        self._payload = payload

    def result(self, timeout=None):
        return self._payload


class _StubGenEngine:
    def __init__(self, tag, load=0):
        self.tag = tag
        self.load_value = load
        self.calls = 0

    def start(self):
        pass

    def stop(self, drain=True, timeout=30.0):
        pass

    def load(self):
        return self.load_value

    def health(self):
        return {"state": "ready", "retry_after_s": 0.0}

    def post_warmup_compiles(self):
        return 0

    def submit(self, greq):
        self.calls += 1
        return _StubGenResult({"text": f"from-{self.tag}",
                               "tokens": [1, 2, 3]})


_FEED = {"x": np.zeros((1, 4, FEAT), np.float32)}
_GEN = {"prompt": [1, 2, 3], "max_new_tokens": 4}


@contextlib.contextmanager
def _router(*reps, **kw):
    kw.setdefault("start_probe", False)
    rt = Router(list(reps), **kw)
    try:
        yield rt
    finally:
        rt.close()


# ---------------------------------------------------------------------------
# Dispatch policy
# ---------------------------------------------------------------------------

def test_least_loaded_dispatch():
    stubs = [_StubEngine(tag=i, load=l)
             for i, l in enumerate((5, 0, 3))]
    reps = [Replica(f"r{i}", engine=s) for i, s in enumerate(stubs)]
    with _router(*reps) as rt:
        out = rt.predict(_FEED)
        assert out["y"][0, 0] == 1.0
        assert [s.calls for s in stubs] == [0, 1, 0]
        # load moves, dispatch follows
        stubs[1].load_value = 9
        out = rt.predict(_FEED)
        assert out["y"][0, 0] == 2.0
        assert rt.requests == 2 and rt.redispatches == 0


def test_failover_redispatches_to_healthy_replica():
    bad = _StubEngine(tag=0, load=0)
    bad.fail = QueueFullError("replica queue full")
    good = _StubEngine(tag=7, load=5)
    with _router(Replica("bad", engine=bad),
                 Replica("good", engine=good)) as rt:
        out = rt.predict(_FEED)
        # least-loaded picked the failing replica first, then failed
        # over without surfacing an error to the caller
        assert bad.calls == 1 and good.calls == 1
        assert out["y"][0, 0] == 7.0
        assert rt.redispatches == 1


def test_shed_with_retry_after_when_all_replicas_out():
    s = _StubEngine(tag=0, load=0)
    s.fail = OverloadedError("full", retry_after_s=3.0)
    with _router(Replica("r0", engine=s), redispatch_budget=2) as rt:
        with pytest.raises(OverloadedError) as ei:
            rt.predict(_FEED)
        # the one replica was tried once, then the empty healthy set
        # shed the request with the fleet's max backoff
        assert s.calls == 1
        assert rt.shed == 1
        assert ei.value.retry_after_s >= 1.0


def test_nonretryable_error_propagates_without_failover():
    a = _StubEngine(tag=0, load=0)
    a.fail = ValueError("bad feed")
    b = _StubEngine(tag=1, load=5)
    with _router(Replica("a", engine=a), Replica("b", engine=b)) as rt:
        with pytest.raises(ValueError):
            rt.predict(_FEED)
        assert b.calls == 0 and rt.redispatches == 0
        # the replica is not at fault for a malformed request: its
        # breaker stays closed and it remains routable
        assert [r.name for r in rt.healthy_replicas()] == ["a", "b"]


def test_breaker_opens_after_repeated_failures():
    bad = _StubEngine(tag=0, load=0)
    bad.fail = QueueFullError("full")
    good = _StubEngine(tag=1, load=50)
    with _router(Replica("bad", engine=bad, failure_threshold=2),
                 Replica("good", engine=good)) as rt:
        for _ in range(3):
            rt.predict(_FEED)
        # after 2 strikes the breaker opens: "bad" leaves the healthy
        # set and stops being tried at all despite its lower load
        assert [r.name for r in rt.healthy_replicas()] == ["good"]
        calls_before = bad.calls
        rt.predict(_FEED)
        assert bad.calls == calls_before


def test_half_open_probe_recovers_replica_after_cooldown():
    """A tripped breaker must recover through the half-open probe even
    while read-only paths (healthz, gauge sweeps, healthy_replicas)
    keep checking routability: those checks must not consume the
    HALF_OPEN probe slot, or the replica stays excluded forever."""
    bad = _StubEngine(tag=3, load=0)
    bad.fail = QueueFullError("full")
    rep = Replica("r", engine=bad, failure_threshold=1)
    rep.breaker.cooldown_ms = 60.0
    with _router(rep) as rt:
        with pytest.raises(OverloadedError):
            rt.predict(_FEED)         # one strike trips the breaker
        assert rt.healthy_replicas() == []
        time.sleep(0.08)              # cooldown elapsed -> HALF_OPEN
        for _ in range(5):            # read-only paths, repeatedly
            rt.healthz()
            rt.probe_once()
            assert [r.name for r in rt.healthy_replicas()] == ["r"]
        bad.fail = None
        out = rt.predict(_FEED)       # the real probe closes it
        assert out["y"][0, 0] == 3.0
        from paddle_tpu.resilience.breaker import CLOSED
        assert rep.breaker.state == CLOSED
        assert [r.name for r in rt.healthy_replicas()] == ["r"]


def test_nonretryable_in_half_open_releases_probe_slot():
    bad = _StubEngine(tag=0, load=0)
    bad.fail = QueueFullError("full")
    rep = Replica("r", engine=bad, failure_threshold=1)
    rep.breaker.cooldown_ms = 40.0
    with _router(rep) as rt:
        with pytest.raises(OverloadedError):
            rt.predict(_FEED)         # OPEN
        time.sleep(0.06)              # HALF_OPEN
        bad.fail = ValueError("bad feed")
        with pytest.raises(ValueError):
            rt.predict(_FEED)         # probe claimed, then released
        # the replica is not at fault and must stay routable
        assert [r.name for r in rt.healthy_replicas()] == ["r"]
        bad.fail = None
        assert rt.predict(_FEED)["y"][0, 0] == 0.0


def test_healthz_polls_do_not_inflate_shed_counter():
    with _router(Replica("r", engine=_StubEngine(tag=0))) as rt:
        rt.preempt("r")
        for _ in range(3):
            code, _body, ra = rt.healthz()
            assert code == 503 and ra >= 1.0
        # no client request was shed: only actual sheds may count
        assert rt.shed == 0


def test_session_affinity_pins_and_repins():
    g0, g1 = _StubGenEngine("g0", load=0), _StubGenEngine("g1", load=5)
    with _router(Replica("r0", gen_engine=g0),
                 Replica("r1", gen_engine=g1)) as rt:
        out = rt.generate(_GEN, session="s1")
        assert out["text"] == "from-g0"
        # affinity holds even when the pinned replica gets busier
        g0.load_value = 50
        assert rt.generate(_GEN, session="s1")["text"] == "from-g0"
        # a fresh session follows load, not the old pin
        assert rt.generate(_GEN, session="s2")["text"] == "from-g1"
        # pin breaks with the replica and re-pins on a healthy one
        rt.preempt("r0")
        assert rt.generate(_GEN, session="s1")["text"] == "from-g1"


def test_affinity_map_is_lru_bounded():
    g = _StubGenEngine("g", load=0)
    with _router(Replica("r", gen_engine=g), affinity_max=4) as rt:
        for i in range(10):
            rt.generate(_GEN, session=f"s{i}")
        with rt._lock:
            assert list(rt._affinity) == ["s6", "s7", "s8", "s9"]
        # touching a survivor refreshes it; a new session evicts the
        # least recently used pin, not the refreshed one
        rt.generate(_GEN, session="s6")
        rt.generate(_GEN, session="new")
        with rt._lock:
            assert "s6" in rt._affinity
            assert "s7" not in rt._affinity
            assert len(rt._affinity) == 4


def test_probe_once_gates_unhealthy_replica():
    a, b = _StubEngine(tag=0, load=0), _StubEngine(tag=1, load=5)
    with _router(Replica("a", engine=a), Replica("b", engine=b)) as rt:
        a.health = lambda: {"state": "open", "retry_after_s": 2.0}
        rt.probe_once()
        assert [r.name for r in rt.healthy_replicas()] == ["b"]
        out = rt.predict(_FEED)
        assert out["y"][0, 0] == 1.0 and a.calls == 0
        # recovery: the next sweep re-admits it (backoff expired is
        # simulated by clearing it — probe_once set it from Retry-After)
        a.health = lambda: {"state": "ready", "retry_after_s": 0.0}
        rt.probe_once()
        rep_a = [r for r in rt.replicas() if r.name == "a"][0]
        rep_a.backoff_until = 0.0
        assert len(rt.healthy_replicas()) == 2


# ---------------------------------------------------------------------------
# Preemption-aware membership
# ---------------------------------------------------------------------------

def test_preempt_and_resume_membership():
    a, b = _StubEngine(tag=0, load=0), _StubEngine(tag=1, load=5)
    with _router(Replica("a", engine=a), Replica("b", engine=b)) as rt:
        rt.preempt("a")
        assert [r.name for r in rt.healthy_replicas()] == ["b"]
        out = rt.predict(_FEED)       # no client-visible error
        assert out["y"][0, 0] == 1.0
        rt.resume("a")
        assert len(rt.healthy_replicas()) == 2
        assert rt.predict(_FEED)["y"][0, 0] == 0.0


def test_install_sigterm_chains_previous_handler():
    calls = []

    def prev_handler(signum, frame):
        calls.append(signum)

    old = signal.signal(signal.SIGTERM, prev_handler)
    try:
        with _router(Replica("a", engine=_StubEngine(tag=0))) as rt:
            rt.install_sigterm("a")
            handler = signal.getsignal(signal.SIGTERM)
            assert handler is not prev_handler
            handler(signal.SIGTERM, None)
            # SIGTERM deregistered the replica AND chained through to
            # the previously installed handler (trainer_guard pattern)
            assert calls == [signal.SIGTERM]
            assert rt.replicas()[0].registered is False
        # close() restored the previous handler
        assert signal.getsignal(signal.SIGTERM) is prev_handler
    finally:
        signal.signal(signal.SIGTERM, old)


# ---------------------------------------------------------------------------
# RouterHTTP front end
# ---------------------------------------------------------------------------

def test_router_http_serves_and_sheds(model_dir):
    eng = _engine(model_dir)
    rep = Replica("r0", engine=eng, version="v1")
    rep.start()
    rt = Router([rep], start_probe=False)
    srv = RouterHTTP(rt, port=0)
    try:
        url = srv.url
        code, body, _ = _get(url + "/healthz")
        assert code == 200 and body["state"] == "ok"
        assert body["replicas"]["r0"]["version"] == "v1"

        xb = np.random.RandomState(0).randn(1, 5, FEAT) \
            .astype(np.float32)
        ref = create_paddle_predictor(AnalysisConfig(model_dir))
        want, = ref.run_dict({"x": xb})
        code, body, _ = _post(url + "/v1/predict",
                              {"inputs": {"x": xb.tolist()}})
        assert code == 200, body
        name = eng.output_names()[0]
        np.testing.assert_allclose(np.asarray(body["outputs"][name]),
                                   np.asarray(want), rtol=1e-4,
                                   atol=1e-5)

        code, body, _ = _post(url + "/v1/predict", {"inputs": {}})
        assert code == 400

        # deregister the only replica: the router sheds with a 503 and
        # a Retry-After, both on the route and on /healthz
        rt.preempt("r0")
        code, body, hdrs = _post(url + "/v1/predict",
                                 {"inputs": {"x": xb.tolist()}})
        assert code == 503 and body["retryable"] is True
        assert int(hdrs["Retry-After"]) >= 1
        code, body, hdrs = _get(url + "/healthz")
        assert code == 503 and body["state"] == "open"
        assert int(hdrs["Retry-After"]) >= 1
        assert rt.shed >= 1
    finally:
        srv.close()
        rt.close(stop_replicas=True)


# ---------------------------------------------------------------------------
# Hot swap
# ---------------------------------------------------------------------------

def test_hot_swap_flips_table_and_drains_old(model_dir):
    old_eng = _engine(model_dir)
    rep = Replica("r0", engine=old_eng, version="v1")
    rep.start()
    rt = Router([rep], start_probe=False, drain_timeout_s=10.0)
    standby = Replica("r0v2", engine=_engine(model_dir), version="v2")
    try:
        xb = np.random.RandomState(1).randn(2, 5, FEAT) \
            .astype(np.float32)
        want = rt.predict({"x": xb})
        res = rt.hot_swap("r0", standby)
        assert res["swapped"] and res["drained"]
        assert res["old"] == "r0" and res["new"] == "r0v2"
        assert res["standby_post_warmup_compiles"] == 0
        assert [r.name for r in rt.replicas()] == ["r0v2"]
        # the old replica was drained and fully stopped
        assert not old_eng.ready
        # traffic keeps flowing and the answers don't change
        got = rt.predict({"x": xb})
        name = next(iter(want))
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5)
    finally:
        rt.close(stop_replicas=True)


def test_hot_swap_rejects_duplicate_before_start_allows_same_name():
    a = _StubEngine(tag=0, load=0)
    b = _StubEngine(tag=1, load=9)
    with _router(Replica("r0", engine=a), Replica("r1", engine=b)) as rt:
        # a collision with a live replica is rejected BEFORE the
        # standby is warmed, so no engine is started just to be thrown
        # away
        class _TrackStart(_StubEngine):
            started = False

            def start(self):
                self.started = True

        dup_eng = _TrackStart(tag=2)
        with pytest.raises(ValueError):
            rt.hot_swap("r0", Replica("r1", engine=dup_eng))
        assert dup_eng.started is False
        assert sorted(r.name for r in rt.replicas()) == ["r0", "r1"]
        # swapping under the SAME name (restart with new weights) works
        res = rt.hot_swap("r0", Replica(
            "r0", engine=_StubEngine(tag=5), version="v2"))
        assert res["swapped"] and res["old"] == "r0" \
            and res["new"] == "r0"
        reps = {r.name: r for r in rt.replicas()}
        assert set(reps) == {"r0", "r1"}
        assert reps["r0"].version == "v2"
        assert rt.predict(_FEED)["y"][0, 0] == 5.0


def test_hot_swap_compile_gate_stops_standby_and_keeps_table():
    class _CompilingGen(_StubGenEngine):
        def __init__(self):
            super().__init__("c")
            self.stopped = False

        def post_warmup_compiles(self):
            return 1

        def stop(self, drain=True, timeout=30.0):
            self.stopped = True

    g = _StubGenEngine("g0", load=0)
    comp = _CompilingGen()
    with _router(Replica("g0", gen_engine=g)) as rt:
        with pytest.raises(RuntimeError, match="post-warmup compiles"):
            rt.hot_swap("g0", Replica("g1", gen_engine=comp))
        # the aborted standby was stopped, and the old replica still
        # serves
        assert comp.stopped is True
        assert [r.name for r in rt.replicas()] == ["g0"]
        assert rt.generate(_GEN)["text"] == "from-g0"


# ---------------------------------------------------------------------------
# /healthz worst-state-wins aggregation (replica server)
# ---------------------------------------------------------------------------

class _StubHealth:
    ready = True

    def __init__(self):
        self.h = {"state": "ready", "retry_after_s": 0.0}

    def health(self):
        return self.h


def test_healthz_worst_state_wins_full_table():
    """Every (predict_state, generate_state) pair resolves to the
    higher-ranked state; ok/degraded answer 200, the rest 503; the
    Retry-After header appears only for worst == "open" and carries the
    MAX of the engines' retry_after_s."""
    a, b = _StubHealth(), _StubHealth()
    srv = ServingHTTPServer(engine=a, gen_engine=b, port=0)
    try:
        url = f"http://127.0.0.1:{srv.port}"
        for s1 in _STATE_RANK:
            for s2 in _STATE_RANK:
                a.h = {"state": s1,
                       "retry_after_s": 2.0 if s1 == "open" else 0.0}
                b.h = {"state": s2,
                       "retry_after_s": 5.0 if s2 == "open" else 0.0}
                worst = max((s1, s2), key=lambda s: _STATE_RANK[s])
                code, body, hdrs = _get(url + "/healthz")
                ctx = f"pair ({s1}, {s2})"
                if worst in ("ready", "degraded"):
                    assert code == 200, ctx
                else:
                    assert code == 503, ctx
                expect = "ok" if worst == "ready" else worst
                assert body["state"] == expect, ctx
                if worst == "open":
                    # max of the per-engine retry_after_s values
                    want_ra = 5 if s2 == "open" else 2
                    assert int(hdrs["Retry-After"]) == want_ra, ctx
                else:
                    assert "Retry-After" not in hdrs, ctx
    finally:
        srv.close(drain=False)


# ---------------------------------------------------------------------------
# Drain-before-close (replica server)
# ---------------------------------------------------------------------------

def test_http_close_drains_inflight_request(model_dir):
    from paddle_tpu.resilience import reset_injector
    eng = _engine(model_dir)
    srv = serve(eng, port=0)
    prev_spec = fluid.FLAGS.fault_spec
    fluid.set_flags({"FLAGS_fault_spec": "slow_step:ms=300:site=serving"})
    reset_injector()
    result = {}
    xb = np.random.RandomState(2).randn(1, 5, FEAT).astype(np.float32)

    def worker():
        result["resp"] = _post(srv.url + "/v1/predict",
                               {"inputs": {"x": xb.tolist()}})

    t = threading.Thread(target=worker)
    try:
        t.start()
        time.sleep(0.15)          # request is inside the engine now
        srv.close(drain=True, timeout=5.0)
        t.join(timeout=10.0)
        assert not t.is_alive()
        code, body, _ = result["resp"]
        # the in-flight request completed with a real answer instead of
        # a connection reset
        assert code == 200, body
        # and the listening socket is really gone afterwards
        with pytest.raises(Exception):
            urllib.request.urlopen(srv.url + "/healthz", timeout=2)
    finally:
        fluid.set_flags({"FLAGS_fault_spec": prev_spec})
        reset_injector()
        eng.stop()


# ---------------------------------------------------------------------------
# Two-tier tracing: router span parents the replica's request span
# ---------------------------------------------------------------------------

_TRACE_FLAGS = ("enable_trace", "trace_sample", "trace_tail_slow_ms",
                "trace_ring_capacity")


@contextlib.contextmanager
def _trace_on():
    prev = {k: getattr(fluid.FLAGS, k) for k in _TRACE_FLAGS}
    fluid.set_flags({"FLAGS_enable_trace": True,
                     "FLAGS_trace_sample": 1.0,
                     "FLAGS_trace_tail_slow_ms": 0.0,
                     "FLAGS_trace_ring_capacity": 8192})
    trace.reset()
    try:
        yield
    finally:
        trace.reset()
        fluid.set_flags({f"FLAGS_{k}": v for k, v in prev.items()})


def test_traceparent_crosses_router_to_replica_hop(model_dir):
    """One request through RouterHTTP -> url Replica -> replica server
    produces ONE trace: router http.request (root) -> router.dispatch
    -> replica http.request, and the tree passes the trace_report
    consistency audit."""
    tr_tool = _load_tool("trace_report")
    eng = _engine(model_dir)
    replica_srv = serve(eng, port=0)
    rt = srv = None
    with _trace_on():
        try:
            rep = Replica("r0", url=replica_srv.url)
            rt = Router([rep], start_probe=False)
            srv = RouterHTTP(rt, port=0)
            xb = np.random.RandomState(3).randn(1, 5, FEAT) \
                .astype(np.float32)
            code, body, hdrs = _post(srv.url + "/v1/predict",
                                     {"inputs": {"x": xb.tolist()}})
            assert code == 200, body
            # the router's handler closes its root span after it has
            # written the response: the client can be back here first
            spans, deadline = [], time.monotonic() + 10.0
            while time.monotonic() < deadline:
                spans += trace.drain_spans()
                if any(s["name"] == "http.request"
                       and s["parent_id"] is None for s in spans):
                    break
                time.sleep(0.01)
        finally:
            if srv is not None:
                srv.close()
            if rt is not None:
                rt.close()
            replica_srv.close(drain=False)
            eng.stop()
    roots = [s for s in spans
             if s["name"] == "http.request" and s["parent_id"] is None]
    assert len(roots) == 1
    assert roots[0]["attrs"].get("tier") == "router"
    # every request-path span shares the router root's trace (batch
    # spans live in their own linked trace, by design)
    spans = [s for s in spans
             if s["trace_id"] == roots[0]["trace_id"]]
    disp = [s for s in spans if s["name"] == "router.dispatch"]
    assert len(disp) == 1
    assert disp[0]["parent_id"] == roots[0]["span_id"]
    assert disp[0]["attrs"]["replica"] == "r0"
    rep_http = [s for s in spans
                if s["name"] == "http.request"
                and s["parent_id"] is not None]
    assert len(rep_http) == 1
    # the replica's request span parents under the router's dispatch
    # span: one tree covers both tiers
    assert rep_http[0]["parent_id"] == disp[0]["span_id"]
    rep_report = tr_tool.build_report(spans)
    assert rep_report["consistency"]["violations"] == 0
    assert rep_report["n_requests"] >= 1


# ---------------------------------------------------------------------------
# Loadgen record -> validator -> report section
# ---------------------------------------------------------------------------

def test_router_loadgen_schema_validator_and_report(model_dir, tmp_path,
                                                    capsys):
    loadgen = _load_tool("serving_loadgen")
    v = _load_tool("validate_bench_json")
    metrics_report = _load_tool("metrics_report")
    out = str(tmp_path / "router.jsonl")
    rc = loadgen.main(["--model-dir", model_dir, "--router", "2",
                       "--requests", "24", "--max-batch-size", "2",
                       "--seq-buckets", "4,8", "--service-ms", "5",
                       "--out", out])
    assert rc == 0
    recs = [json.loads(ln) for ln in open(out) if ln.strip()]
    rec = next(r for r in recs if r.get("kind") == "router_loadgen")
    assert rec["replicas"] == 2
    assert rec["wrong_answers"] == 0
    assert rec["scaling"]["rps_1"] > 0 and rec["scaling"]["rps_n"] > 0
    assert v.validate_router_loadgen(rec) == []
    assert v.validate_file(out) == []
    # a corrupted record must fail the zero-wrong-answers gate
    bad = dict(rec, wrong_answers=1)
    assert any("wrong_answers" in e
               for e in v.validate_router_loadgen(bad))
    assert metrics_report.report(out) == 0
    text = capsys.readouterr().out
    assert "-- router " in text
    assert "scaling 1->N" in text
