"""Device time under a framework scope (`tf_op` pattern) or of ops
whose HLO text matches a pattern, per step of the traced slice. With
`kind`, only the ops inside that executable's runs count, per run of
it."""
import re

from benchmark import trace_reduce
from benchmark.readers import _serve_calls


def read(ctx, tf_op=None, text=None, kind=None):
    trace = ctx.get("trace")
    if trace is None or not trace.ops:
        return None
    ops = trace.ops[0]
    if kind is not None:
        by_kind = _serve_calls.modules_by_kind(ctx)
        if not by_kind or kind not in by_kind:
            return None
        ops = trace_reduce.ops_within(ops, by_kind[kind])
        steps = len(by_kind[kind])
    else:
        steps = len(trace_reduce.step_modules(trace.modules[0]))
    if not steps:
        return None
    if tf_op is not None:
        seconds = trace_reduce.scope_seconds(ops, tf_op)
    else:
        rx = re.compile(text)
        seconds = sum(o.dur for o in ops if rx.search(o.text))
    return seconds * 1e3 / steps if seconds > 0 else None
