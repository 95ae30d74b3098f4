"""Forward flops of the tokens the engine really advanced, as a share
of the chip's published peak, on the device's clock. `kind` None:
every prompt and output token of the traced slice over the slice's
length, first executable's start to last executable's end with the
gaps between them (the whole step's share). `kind` decode / prefill:
that executable's tokens over its own device time."""
from benchmark import trace_reduce
from benchmark.readers import _serve_calls


def read(ctx, kind=None):
    if ctx.get("peaks") is None:
        return None
    sl = _serve_calls.calls(ctx, "slice")
    # the i-th run in the trace is the i-th call logged, or nothing is read
    by_kind = _serve_calls.modules_by_kind(ctx)
    if not sl or not by_kind:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"]
    if kind is None:
        seconds = trace_reduce.window_seconds(ctx["trace"])
    elif kind in by_kind:
        seconds = sum(m.dur for m in by_kind[kind])
        sl = [c for c in sl if c[0] == kind]
    else:
        return None
    if seconds <= 0:
        return None
    return 100.0 * _serve_calls.forward_flops(ctx, sl) / seconds / peak
