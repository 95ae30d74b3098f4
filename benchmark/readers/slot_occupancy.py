"""Slots decoding in an iteration over the slots there are, mean over
the window's decode steps (the engine's own step feeds)."""
from benchmark.readers import _serve_calls


def read(ctx):
    dec = [c for c in _serve_calls.calls(ctx) if c[0] == "decode"]
    if not dec:
        return None
    return 100.0 * sum(float((c[2] > 0).sum()) for c in dec) \
        / (len(dec) * ctx["max_slots"])
