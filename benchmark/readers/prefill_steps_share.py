"""Iterations that ran a chunk-prefill step over iterations. An
iteration is one prefill step, one decode step, or a prefill step with
the decode step that follows it."""
from benchmark.readers import _serve_calls


def read(ctx):
    kinds = [c[0] for c in _serve_calls.calls(ctx)]
    if not kinds:
        return None
    iterations = sum(1 for i, k in enumerate(kinds)
                     if k == "prefill" or i == 0 or kinds[i - 1] != "prefill")
    return 100.0 * kinds.count("prefill") / iterations
