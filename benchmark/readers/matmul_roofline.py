"""The matrix products' share of their roofline in the training step:
the model's matmul flops (forward + backward) of the traced steps over
the published peak, over the device time of the ops that the
profiler's `hlo_category` gives as matrix products."""
from benchmark import trace_reduce, workmodel


def read(ctx, category="convolution"):
    if ctx.get("peaks") is None:
        return None
    trace, sl = ctx.get("trace"), ctx["window"]["slice"]
    if trace is None or not trace.ops or not sl:
        return None
    seconds = sum(trace_reduce.category_seconds(d, category)
                  for d in trace.ops) / len(trace.ops)
    if seconds <= 0:
        return None
    flops = sl["steps"] * workmodel.matmul_flops_train_step(
        ctx["sizes"], ctx["rows"], ctx["seq_len"]) / ctx["chips"]
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / peak / seconds
