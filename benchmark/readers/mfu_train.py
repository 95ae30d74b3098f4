"""The whole training step's share of the chips' peak: model flops
(forward + backward, recomputation not counted) of the steps of the
traced slice, over the slice's length on the device's clock (first
step's start to last step's end, the gaps between them included), over
chips x the published bf16 peak."""
from benchmark import trace_reduce, workmodel


def read(ctx):
    if ctx.get("peaks") is None:
        return None
    trace, sl = ctx.get("trace"), ctx["window"]["slice"]
    if not sl or trace is None or not trace.modules:
        return None
    # every step of the slice, and nothing else, has to be in the trace
    if len(trace_reduce.step_modules(trace.modules[0])) != sl["steps"]:
        return None
    seconds = trace_reduce.window_seconds(trace)
    if seconds <= 0:
        return None
    flops = sl["steps"] * workmodel.matmul_flops_train_step(
        ctx["sizes"], ctx["rows"], ctx["seq_len"])
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / seconds / (ctx["chips"] * peak)
