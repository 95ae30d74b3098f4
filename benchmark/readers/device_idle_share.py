"""1 - (union of `XLA Ops` intervals / traced window), mean over the
chips, in %."""
from benchmark import trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.ops:
        return None
    window = trace_reduce.window_seconds(trace)
    if window <= 0:
        return None
    return 100.0 * (1.0 - trace_reduce.mean_busy_seconds(trace) / window)
