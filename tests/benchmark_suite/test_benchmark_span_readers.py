"""The readers of the program's own spans, iteration records and
request timings, on hand-built traces, records and responses with
known answers; and None where a program has nothing for them to read."""
import types

import pytest

from benchmark import manifest, serve_window, trace_reduce as tr
from benchmark.readers import (idle_under_spans, iteration_record,
                               request_timing, span_ms)

MS = 1e-3


def op(start, dur):
    return tr.Op("fusion", "", "", start * MS, dur * MS)


def span(name, start, dur):
    return tr.Span(name, start * MS, dur * MS)


# busy [0,10) [14,20) [23,30) [36,40) [40.01,41): gaps of 4, 3 and 6 ms
# and one of 10 us, which the reduction leaves out
OPS = [op(0, 10), op(14, 6), op(23, 7), op(36, 4), op(40.01, 0.99)]
HOST = [
    span("bench.decode_step", 8, 13),            # the benchmark's own
    span("gen.iteration", 1, 28),
    span("gen.decode.step", 9, 8),
    span("executor.fetch", 10, 6),               # covers the gap at 12
    span("np.asarray(jax.Array)", 10.5, 5),      # jax's: no candidate
    span("gen.sample", 19, 3),                   # covers the gap at 21.5
    span("$ threading.py:1 wait", 30, 6),        # the gap at 33: nobody's
    span("gen.decode.step", 41, 1),
]
TRACE = tr.Trace([OPS], [[]], sorted(HOST, key=lambda s: s.start))


def log(calls, first=0, last=None):
    out = serve_window.StepLog(None, 0, 0.0)
    out.calls = calls
    out.slice = (first, len(calls) if last is None else last)
    return out


def call(kind, t0):
    return (kind, None, None, 0.0, t0, t0 + 0.1)


def ctx(**more):
    return {"trace": TRACE, "log": log([call("prefill", 1.0),
                                        call("decode", 1.1),
                                        call("decode", 1.4)]), **more}


def test_idle_goes_to_the_innermost_span_of_the_program():
    idle = idle_under_spans.by_span(TRACE)
    # the gap at [20,23) is split where gen.sample ends, at 22
    assert idle == {"executor.fetch": pytest.approx(4 * MS),
                    "gen.sample": pytest.approx(2 * MS),
                    "gen.iteration": pytest.approx(1 * MS),
                    "unattributed": pytest.approx(6 * MS)}
    # the reduction itself hands the first gap to jax's own span, and
    # the second whole to the span over its middle
    whole = dict(tr.idle_gaps(TRACE))
    assert whole["np.asarray_jax.Array_"] == pytest.approx(4 * MS)
    assert whole["gen.sample"] == pytest.approx(3 * MS)


def test_a_gap_is_split_where_the_host_crosses_into_the_next_region():
    """The 5 ms after a prefill step: the fetch's tail, the engine's
    staging, then the Executor's resolve and dispatch until the device
    starts. Its middle lies at executor.fetch's very end."""
    ops = [op(0, 100), op(105, 100)]
    host = [span("gen.iteration", -5, 220),
            span("gen.prefill.step", -4, 106.6),
            span("executor.fetch", 1, 101.5),       # tail: 2.5 ms
            span("gen.decode.stage", 102.7, 0.1),
            span("gen.decode.step", 102.9, 110),
            span("executor.resolve", 103.3, 1.2),
            span("executor.dispatch", 104.5, 3.0)]
    idle = idle_under_spans.by_span(tr.Trace([ops], [[]], host))
    assert idle == {"executor.fetch": pytest.approx(2.5 * MS),
                    "gen.prefill.step": pytest.approx(0.1 * MS),
                    "gen.iteration": pytest.approx(0.2 * MS),
                    "gen.decode.stage": pytest.approx(0.1 * MS),
                    "gen.decode.step": pytest.approx(0.4 * MS),
                    "executor.resolve": pytest.approx(1.2 * MS),
                    "executor.dispatch": pytest.approx(0.5 * MS)}
    c = {"trace": tr.Trace([ops], [[]], host)}
    assert idle_under_spans.read(c, spans=r"^executor\.") == \
        pytest.approx(4.2)
    assert idle_under_spans.read(c, spans=r"^gen\.") == pytest.approx(0.8)
    assert idle_under_spans.read(c) == 0.0


@pytest.mark.parametrize("args,want", [
    ({"spans": r"^gen\."}, 3.0 / 2),          # ms an iteration, 2 decodes
    ({"spans": r"^executor\."}, 4.0 / 2),
    ({}, 100.0 * 6 / 13),                     # share under no span
])
def test_idle_under_spans_per_iteration_and_the_unattributed_share(
        args, want):
    assert idle_under_spans.read(ctx(), **args) == pytest.approx(want)


def test_the_three_idle_readings_add_up_to_the_idle_time():
    c = ctx()
    total = sum(idle_under_spans.by_span(TRACE).values())
    engine = idle_under_spans.read(c, spans=r"^gen\.")
    executor = idle_under_spans.read(c, spans=r"^executor\.")
    share = idle_under_spans.read(c)
    assert (engine + executor) * 2 * MS + share / 100 * total == \
        pytest.approx(total)
    # what the device plane says: window less busy, less the short gap
    busy = tr.busy_seconds(OPS)
    assert total == pytest.approx(41 * MS - busy - 0.01 * MS)


def test_iterations_come_from_the_log_else_from_the_spans():
    assert idle_under_spans.iterations(ctx()) == 2
    # a run that logged no call: the two gen.decode.step spans
    assert idle_under_spans.iterations({"trace": TRACE}) == 2
    only_prefill = ctx(log=log([call("prefill", 1.0)]))
    assert idle_under_spans.iterations(only_prefill) == 2
    assert idle_under_spans.read({"trace": TRACE}, spans=r"^gen\.") == \
        pytest.approx(1.5)


@pytest.mark.parametrize("trace", [
    None,
    tr.Trace([], [], HOST),                                   # no device
    tr.Trace([OPS], [[]], [span("bench.decode_step", 8, 13),  # the parent
                           span("np.asarray(jax.Array)", 10.5, 5)]),
])
def test_idle_under_spans_finds_nothing_to_read(trace):
    for args in ({"spans": r"^gen\."}, {"spans": r"^executor\."}, {}):
        assert idle_under_spans.read({"trace": trace}, **args) is None


def test_span_ms_is_the_mean_duration_of_the_named_span():
    assert span_ms.read(ctx(), span=r"^gen\.sample$") == pytest.approx(3.0)
    assert span_ms.read(ctx(), span=r"^gen\.decode\.step$") == \
        pytest.approx((8 + 1) / 2)
    assert span_ms.read(ctx(), span=r"^executor\.feed$") is None
    assert span_ms.read({"trace": None}, span=r"^gen\.sample$") is None


def record(t_start, t_end, **fields):
    base = {"t_start": t_start, "t_end": t_end, "prefill_rows": 0,
            "prefill_tokens": 0, "decode_rows": 4, "tokens_emitted": 4,
            "queue_depth": 0, "active_slots": 4, "kv_blocks_held": 0,
            "kv_tokens_resident": 0, "kv_blocks_total": 64, "slots": 4,
            "block_size": 16, "host_s": {}}
    return {**base, **fields}


RECORDS = [
    record(0.5, 0.9, prefill_rows=4, prefill_tokens=64),     # warm-up
    record(1.0, 1.2, prefill_rows=2, prefill_tokens=24,
           kv_blocks_held=10, kv_tokens_resident=80),
    record(1.2, 1.5, kv_blocks_held=10, kv_tokens_resident=120),
    record(1.5, 1.8, prefill_rows=1, prefill_tokens=8,
           kv_blocks_held=0, kv_tokens_resident=0),          # holds none
    record(1.8, 2.6, prefill_rows=4, prefill_tokens=64),     # past the end
]


@pytest.fixture
def ring(monkeypatch):
    from paddle_tpu import trace
    monkeypatch.setattr(trace, "iteration_records", lambda: list(RECORDS),
                        raising=False)


def window(**more):
    return {"t0": 1.0, "window_s": 1.0, "log": None, "sent": [], **more}


def test_iteration_record_reads_the_windows_records(ring):
    w = window()
    assert [r["t_start"] for r in iteration_record.records(w)] == \
        [1.0, 1.2, 1.5]
    # (24 + 8) tokens in two prefill steps with room for 4 x 16 each
    assert iteration_record.read(w, what="prefill_fill") == \
        pytest.approx(100.0 * 32 / 128)
    # mean of 80/160 and 120/160; the record that holds no block is out
    assert iteration_record.read(w, what="kv_fill") == \
        pytest.approx(100.0 * (0.5 + 0.75) / 2)
    with pytest.raises(ValueError):
        iteration_record.read(w, what="other")


def test_the_ttft_counters_stop_where_the_traced_slice_begins(ring):
    sliced = window(log=log([call("decode", 1.1), call("decode", 1.5),
                             call("decode", 1.7)], first=1))
    assert iteration_record.slice_start(sliced) == 1.5
    assert [r["t_start"] for r in
            iteration_record.records(sliced, before_slice=True)] == \
        [1.0, 1.2]
    assert iteration_record.read(sliced, what="prefill_fill",
                                 before_slice=True) == \
        pytest.approx(100.0 * 24 / 64)
    # a run that traced nothing reads the whole window
    assert iteration_record.read(window(), what="prefill_fill",
                                 before_slice=True) == pytest.approx(25.0)


def test_iteration_record_finds_nothing_to_read(ring, monkeypatch):
    late = window(t0=5.0)
    assert iteration_record.read(late, what="prefill_fill") is None
    assert iteration_record.read(late, what="kv_fill") is None
    # a program that keeps no records (the parent of the PR that added
    # them): nothing, and no error
    from paddle_tpu import trace
    monkeypatch.delattr(trace, "iteration_records")
    assert iteration_record.read(window(), what="kv_fill") is None


def sent(first_token, timings):
    r = serve_window.Request(0, 0.0, [1], 1)
    r.stamps = [] if first_token is None else [first_token]
    r.response = types.SimpleNamespace(timings=timings)
    return r


def test_request_timing_percentile_and_the_never_admitted():
    reqs = [sent(1.1 + i * 0.01, {"queue_ms": float(i)}) for i in range(10)]
    w = window(sent=reqs)
    assert request_timing.read(w, key="queue_ms", q=90) == 9.0
    assert request_timing.read(w, key="queue_ms", q=50) == 5.0
    # never admitted: no queue_ms, beyond every percentile it reaches
    w["sent"] = reqs + [sent(None, {})]
    assert request_timing.read(w, key="queue_ms", q=100) == float("inf")
    assert request_timing.read(w, key="queue_ms", q=50) == 5.0
    # with a traced slice: only requests whose first token preceded it
    w["log"] = log([call("decode", 1.0), call("decode", 1.145)], first=1)
    assert request_timing.read(w, key="queue_ms", q=100) == 4.0


def test_request_timing_finds_nothing_to_read():
    assert request_timing.read(window(), key="queue_ms") is None
    old = sent(1.1, None)
    old.response = types.SimpleNamespace()       # a response without timings
    assert request_timing.read(window(sent=[old]), key="queue_ms") is None


def test_the_new_metrics_name_their_readers():
    want = {"idle_ms_per_iter.engine.batch": "idle_under_spans",
            "idle_ms_per_iter.engine.gap": "idle_under_spans",
            "idle_ms_per_iter.executor.batch": "idle_under_spans",
            "idle_ms_per_iter.executor.gap": "idle_under_spans",
            "idle_unattributed_share.batch": "idle_under_spans",
            "idle_unattributed_share.gap": "idle_under_spans",
            "sample_ms_per_iter.batch": "span_ms",
            "executor_feed_ms_per_step.train": "span_ms",
            "queue_wait_ms_p90.ttft": "request_timing",
            "prefill_fill_share.ttft": "iteration_record",
            "kv_fill_share.batch": "iteration_record"}
    listed = {m["name"]: m for m in manifest.benchmark_json()["per_layer"]}
    for name, reader in want.items():
        assert manifest.metric_file(name)["reader"] == reader
        assert "workloads" not in listed[name]
    # the two counters of the open-loop cell keep to what precedes the
    # traced slice; the backlog cell's reads its whole window
    assert manifest.metric_file("prefill_fill_share.ttft")["args"][
        "before_slice"] is True
    assert "before_slice" not in manifest.metric_file(
        "kv_fill_share.batch")["args"]
