"""What the engine's own iteration records say of the window
(`paddle_tpu.trace.iteration_records()`: one record for every turn of
the serving loop that ran a step, kept in a ring of the process, so
they outlive the engine that the run frees before its readers run).

`prefill_fill`: valid prompt tokens that the window's prefill steps
advanced over the tokens they had room for (slots x block_size a
step), in %. `kv_fill`: tokens resident in the live slots over the
tokens their blocks hold, mean over the window's iterations, in %.

`before_slice` keeps to what precedes the traced slice: when the slice
ends, `stop_trace` holds the engine's thread for seconds while an open
loop keeps sending (PERF.md section 7). None where the program keeps
no records, or none lies in the window.
"""
from benchmark.readers import _serve_calls


def slice_start(ctx):
    """When the traced slice's first call began, on the host's clock;
    None for a run that logged none."""
    first = _serve_calls.calls(ctx, "slice")[:1]
    return first[0][4] if first else None


def records(ctx, before_slice=False):
    from paddle_tpu import trace
    ring = getattr(trace, "iteration_records", None)
    if ring is None:
        return []
    t0, t1 = ctx["t0"], ctx["t0"] + ctx["window_s"]
    cut = slice_start(ctx) if before_slice else None
    if cut is not None:
        t1 = min(t1, cut)
    return [r for r in ring() if r["t_start"] >= t0 and r["t_end"] <= t1]


def read(ctx, what, before_slice=False):
    recs = records(ctx, before_slice)
    if what == "prefill_fill":
        ran = [r for r in recs if r["prefill_rows"] > 0]
        room = sum(r["slots"] * r["block_size"] for r in ran)
        return 100.0 * sum(r["prefill_tokens"] for r in ran) / room \
            if room else None
    if what == "kv_fill":
        held = [(r["kv_tokens_resident"],
                 r["kv_blocks_held"] * r["block_size"]) for r in recs]
        shares = [tokens / room for tokens, room in held if room > 0]
        return 100.0 * sum(shares) / len(shares) if shares else None
    raise ValueError(f"iteration_record: no reading {what!r}")
