"""Mean duration, in ms, of the program's spans of one name in the
traced slice (host plane of the profiler's trace; `span` is a regular
expression). None where the trace holds none."""
import re


def read(ctx, span):
    trace = ctx.get("trace")
    if trace is None:
        return None
    rx = re.compile(span)
    durs = [s.dur for s in trace.host if rx.search(s.name)]
    return 1e3 * sum(durs) / len(durs) if durs else None
