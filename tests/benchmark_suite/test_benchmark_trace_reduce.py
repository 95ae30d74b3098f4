"""The reduction on a hand-built trace with known answers, and the
flops and bytes functions against closed forms."""
import pytest

from benchmark import manifest, trace_reduce as tr, workmodel

MS = 1e-3


def op(name, start, dur, tf_op="", category=""):
    return tr.Op(name, tf_op, category, start * MS, dur * MS)


OPS = [
    op("fusion.1", 0, 4, "jit(step)/mul:0/5/dot_general", "convolution"),
    op("fusion.2", 3, 2, "jit(step)/paged_attention:0/7/select_n", "fusion"),
    op("all-reduce.1", 6, 4, "jit(step)/grad::generic:0/9/psum",
       "all-reduce"),
    op("fusion.3", 8, 3, "jit(step)/paged_attention:0/7/gather", "fusion"),
    op("copy.4", 20, 1),
]
MODULES = [tr.Span("jit_step(1)", 0, 11 * MS), tr.Span("jit_step(2)", 20 * MS, MS)]
HOST = [tr.Span("np.asarray(jax.Array)", 10 * MS, 12 * MS),
        tr.Span("outer", 0, 30 * MS)]
TRACE = tr.Trace([OPS], [MODULES], HOST)


def test_busy_union_window_and_gaps():
    # [0,5) u [6,11) u [20,21) = 11 ms busy in a 21 ms window
    assert tr.busy_seconds(OPS) == pytest.approx(11 * MS)
    assert tr.mean_busy_seconds(TRACE) == pytest.approx(11 * MS)
    assert tr.window_seconds(TRACE) == pytest.approx(21 * MS)
    assert tr.module_gaps(MODULES) == [pytest.approx(9 * MS)]
    gaps = dict(tr.idle_gaps(TRACE))
    assert gaps["np.asarray_jax.Array_"] == pytest.approx(9 * MS)
    assert gaps["outer"] == pytest.approx(1 * MS)


def test_scope_category_and_exposed_collective_time():
    assert tr.scope_seconds(OPS, "paged_attention:") == pytest.approx(5 * MS)
    assert tr.category_seconds(OPS, "convolution") == pytest.approx(4 * MS)
    # the all-reduce runs [6,10); fusion.3 covers [8,10): 2 ms exposed
    assert tr.exposed_collective_seconds(OPS) == pytest.approx(2 * MS)
    inside = tr.ops_within(OPS, [MODULES[1]])
    assert [o.name for o in inside] == ["copy.4"]
    assert tr.top_ops(TRACE, 2) == [
        ["fusion.1__mul:0/5", pytest.approx(4 * MS)],
        ["all-reduce.1__grad::generic:0/9", pytest.approx(4 * MS)]]
    assert tr.instruction_name(
        "%fusion.2300 = (f32[16384,768]{1,0}) fusion(%p)") == "fusion.2300"


def test_flops_and_bytes_closed_forms():
    bert = manifest.reference("bert_base_nodropout").sizes(
        manifest.config("bert_base_nodropout"))
    gpt = manifest.reference("gpt2_medium").sizes(
        manifest.config("gpt2_medium"))
    # BERT-base: 12 x (4 x 768^2 + 2 x 768 x 3072) + 30522 x 768
    assert workmodel.matmul_params(bert) == 84_934_656 + 23_440_896
    assert workmodel.train_flops_per_token(bert, 512) == \
        6 * 108_375_552 + 12 * 12 * 512 * 768
    # GPT-2-medium: 24 x 12 x 1024^2 + 50257 x 1024
    assert workmodel.matmul_params(gpt) == 301_989_888 + 51_463_168
    # one decode token at position 100 (sees 101 keys), through the head
    assert workmodel.forward_flops(gpt, 1, 101, 1) == \
        2 * 353_453_056 + 4 * 24 * 1024 * 101
    # a prefill chunk of 16 from 32: no head, 16*32 + 16*17/2 pairs
    assert workmodel.forward_flops(gpt, 16, 16 * 32 + 136, 0) == \
        2 * 16 * 301_989_888 + 4 * 24 * 1024 * 648
    # KV read: 2 x 1024 x 4 B x 24 layers = 196,608 B a token held
    assert workmodel.kv_read_bytes(gpt, 1000) == 196_608_000
