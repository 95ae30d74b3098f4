"""The device's idle time by the PROGRAM'S OWN spans: the regions the
program annotates itself (`paddle_tpu.trace.region`: names that start
`gen.` or `executor.`). Every instant of a gap between busy intervals
of the first device goes to the innermost (shortest) such span that
covers it; jax's own spans (`np.asarray(jax.Array)` inside
`executor.fetch`) are no candidates, so idle time names the layer the
host was in. `trace_reduce.idle_gaps` hands a whole gap to the span
over its middle; here a gap is split where the host crosses from one
region into the next, because the middle of the 5 ms after a prefill
step lies within a fifth of a millisecond of `executor.fetch`'s end and
the whole-gap rule flips between the Executor and the engine from run
to run (PERF.md section 6, PR 25).

With `spans` (a regular expression): the idle seconds under spans of
that name over the iterations of the slice, in ms. Without: the share
of the idle seconds that no span of the program covers, in %. Gaps
under `trace_reduce.SHORT_GAP` are left out, as the reduction leaves
them. None where the trace holds no span of the program.
"""
import re

from benchmark import trace_reduce
from benchmark.readers import _serve_calls

PROGRAM = re.compile(r"^(gen|executor)\.")


def by_span(trace):
    """{span name, or "unattributed" where no span of the program
    covers it: idle seconds} of the first device."""
    busy = trace_reduce.union((o.start, o.start + o.dur)
                              for o in trace.ops[0])
    own = [(s.start, s.start + s.dur, s.name) for s in trace.host
           if PROGRAM.match(s.name)]
    acc = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 - e0 < trace_reduce.SHORT_GAP:
            continue
        over = [s for s in own if s[0] < s1 and s[1] > e0]
        cuts = sorted({e0, s1} | {t for s in over for t in s[:2]
                                  if e0 < t < s1})
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in over if s[0] <= a and b <= s[1]]
            name = min(cover, key=lambda s: s[1] - s[0])[2] if cover \
                else "unattributed"
            acc[name] = acc.get(name, 0.0) + b - a
    return acc


def iterations(ctx):
    """Iterations of the traced slice: its decode calls where the run
    logged them, else its `gen.decode.step` spans (the one that is open
    when the slice stops is not in the trace)."""
    logged = [c for c in _serve_calls.calls(ctx, "slice")
              if c[0] == "decode"]
    return len(logged) or sum(s.name == "gen.decode.step"
                              for s in ctx["trace"].host)


def read(ctx, spans=None):
    trace = ctx.get("trace")
    if trace is None or not trace.ops or \
            not any(PROGRAM.match(s.name) for s in trace.host):
        return None
    idle = by_span(trace)
    if spans is None:
        total = sum(idle.values())
        return 100.0 * idle.get("unattributed", 0.0) / total \
            if total else None
    n = iterations(ctx)
    if not n:
        return None
    rx = re.compile(spans)
    return 1e3 * sum(v for k, v in idle.items() if rx.search(k)) / n
