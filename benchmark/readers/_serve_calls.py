"""Shared by the serving readers: the executable runs the traced run
logged, whole window or traced slice, and their place in the trace."""
from __future__ import annotations

from benchmark import trace_reduce, workmodel


def calls(ctx, which="window"):
    """(kind, start, nvalid, host_s, t0, t1) of every executable run
    inside the window (or the traced slice); [] where the program has
    no step call to watch any more."""
    log = ctx.get("log")
    if log is None:
        return []
    if which == "slice":
        if log.slice is None:
            return []
        return log.calls[log.slice[0]:log.slice[1]]
    t_end = ctx["t0"] + ctx["window_s"]
    return [c for c in log.calls if c[5] <= t_end]


def modules_by_kind(ctx):
    """{kind: [module Span, ...]} of the first device in the traced
    slice: the i-th executable run of the trace is the i-th call the
    log recorded while tracing. None where the two do not line up."""
    trace, sl = ctx.get("trace"), calls(ctx, "slice")
    if trace is None or not trace.modules or not sl:
        return None
    mods = trace_reduce.step_modules(trace.modules[0])
    if len(mods) != len(sl):
        return None
    out = {}
    for m, c in zip(mods, sl):
        out.setdefault(c[0], []).append(m)
    return out


def forward_flops(ctx, cs):
    """Forward flops of the tokens these calls really advanced."""
    flops = 0.0
    for kind, start, nvalid, *_ in cs:
        rows = nvalid > 0
        n = nvalid[rows].astype(float)
        s = start[rows].astype(float)
        context = float((n * s + n * (n + 1) / 2).sum())
        head = float(rows.sum()) if kind == "decode" else 0.0
        flops += workmodel.forward_flops(ctx["sizes"], float(n.sum()),
                                         context, head)
    return flops
