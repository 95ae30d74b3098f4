"""The gap between consecutive executable runs on the device (device
trace, `XLA Modules`), a percentile in ms: what the host puts between
two steps."""
import numpy as np

from benchmark import trace_reduce


def read(ctx, q=50):
    trace = ctx.get("trace")
    if trace is None or not trace.modules or len(trace.modules[0]) < 2:
        return None
    gaps = trace_reduce.module_gaps(
        trace_reduce.step_modules(trace.modules[0]))
    return float(np.percentile(gaps, q)) * 1e3
