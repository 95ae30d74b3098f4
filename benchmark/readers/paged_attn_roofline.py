"""paged_attention's share of its roofline in the decode steps of the
traced slice. It is bound by memory: the bytes the algorithm needs are
the keys and values of every token held by the rows that decode, in
every layer (whatever implements it), over the published HBM
bandwidth, over the measured device time under the op's scope."""
from benchmark import trace_reduce, workmodel
from benchmark.readers import _serve_calls


def read(ctx, tf_op="paged_attention:"):
    if ctx.get("peaks") is None:
        return None
    by_kind = _serve_calls.modules_by_kind(ctx)
    if not by_kind or "decode" not in by_kind:
        return None
    ops = trace_reduce.ops_within(ctx["trace"].ops[0], by_kind["decode"])
    seconds = trace_reduce.scope_seconds(ops, tf_op)
    if seconds <= 0:
        return None
    held = 0.0
    for kind, start, nvalid, *_ in _serve_calls.calls(ctx, "slice"):
        if kind == "decode":
            rows = nvalid > 0
            held += float((start[rows] + nvalid[rows]).sum())
    bw = ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * workmodel.kv_read_bytes(ctx["sizes"], held) / bw / seconds
