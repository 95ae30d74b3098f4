"""The `hybrid_serve` family's shares of a peak, over the traced slice,
on the device's clock, with `workmodel_hybrid`'s counts.

`what="mfu"`: forward flops of the tokens the slice's calls really
advanced over the slice's length, first executable's start to last
one's end, as a share of the chip's peak (defined as `mfu.batch` is).
`what="moe"` / `"ssm"`: that layer kind's share of its roofline in the
slice's decode steps: max(flops / peak, bytes / bandwidth) over the
device time of the layer's ops; `"moe_ms"` / `"ssm_ms"`: that device
time a decode step, in ms. A layer's ops are those under the op's
`tf_op` scope and, for the experts, XLA's own grouped-product kernels
(`ragged-dot-*`), which the TPU compiler emits under no framework scope
and which nothing else in the program uses.

The selections that fell on held experts and the held experts hit are
the engine's own counts of those very decode steps (its iteration
records' `moe_selected_held`, `moe_experts_hit`); a prefill step
fetches no such count and takes the expectation. None where the trace,
the step log or those record fields are missing.
"""
import re

from benchmark import trace_reduce, workmodel_hybrid as wm
from benchmark.readers import _serve_calls

SCOPES = {"moe": ("latent_moe:", r"^ragged-dot"),
          "ssm": ("mamba2_mixer:", None)}


def layer_seconds(ops, layer):
    scope, names = SCOPES[layer]
    scope = re.compile(scope)
    names = re.compile(names) if names else None
    return sum(o.dur for o in ops if scope.search(o.tf_op)
               or (names is not None and names.search(o.name)))


def records_of(calls):
    """For each call, the iteration record of the turn that ran it (the
    one whose stretch holds the call), or None."""
    from paddle_tpu import trace
    ring = getattr(trace, "iteration_records", None)
    recs = ring() if ring else []
    out = []
    for c in calls:
        t0, t1 = c[4], c[5]
        out.append(next((r for r in recs
                         if r["t_start"] <= t0 and t1 <= r["t_end"]), None))
    return out


def read(ctx, what):
    if ctx.get("peaks") is None:
        return None
    sl = _serve_calls.calls(ctx, "slice")
    by_kind = _serve_calls.modules_by_kind(ctx)
    if not sl or not by_kind:
        return None
    recs = records_of(sl)
    if any(r is None or "moe_selected_held" not in r
           for c, r in zip(sl, recs) if c[0] == "decode"):
        return None
    sz, peaks = ctx["sizes"], ctx["peaks"]
    peak, bw = peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    if what == "mfu":
        seconds = trace_reduce.window_seconds(ctx["trace"])
        flops = 0.0
        for (kind, start, nvalid, *_), r in zip(sl, recs):
            rows = nvalid > 0
            n = nvalid[rows].astype(float)
            s = start[rows].astype(float)
            decode = kind == "decode"
            flops += wm.forward_flops(
                sz, float(n.sum()),
                float((n * s + n * (n + 1) / 2).sum()),
                float(rows.sum()) if decode else 0.0,
                r["moe_selected_held"] if decode
                else wm.expected_held(sz, float(n.sum())))
        return 100.0 * flops / seconds / peak if seconds > 0 else None
    if "decode" not in by_kind:
        return None
    ops = trace_reduce.ops_within(ctx["trace"].ops[0], by_kind["decode"])
    layer = what[:3]
    seconds = layer_seconds(ops, layer)
    if seconds <= 0:
        return None
    if what.endswith("_ms"):
        return seconds * 1e3 / len(by_kind["decode"])
    least = 0.0
    for (kind, _, nvalid, *_), r in zip(sl, recs):
        if kind != "decode":
            continue
        rows = float((nvalid > 0).sum())
        if layer == "moe":
            flops = wm.moe_flops(sz, rows, r["moe_selected_held"])
            nbytes = wm.moe_bytes(sz, r["moe_experts_hit"])
        else:
            flops, nbytes = wm.ssm_flops(sz, rows), wm.ssm_bytes(sz, rows)
        least += max(flops / peak, nbytes / bw)
    return 100.0 * least / seconds
