"""Collective ops' time during which no compute op runs on that
device, per step, mean over the chips (device trace)."""
from benchmark import trace_reduce


def read(ctx):
    trace, sl = ctx.get("trace"), ctx["window"]["slice"]
    if trace is None or len(trace.ops) < 2 or not sl:
        return None
    exposed = sum(trace_reduce.exposed_collective_seconds(d)
                  for d in trace.ops) / len(trace.ops)
    return exposed * 1e3 / sl["steps"]
