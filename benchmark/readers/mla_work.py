"""The `mla_serve` family's shares of a peak, over the traced slice, on
the device's clock, with `workmodel_mla`'s counts.

`what="mfu"`: forward flops of the tokens the slice's calls really
advanced over the slice's length, first executable's start to last
one's end, as a share of the chip's peak (defined as `mfu.batch` is).
`"attn"`: the latent attention's share of its roofline in the slice's
decode steps: max(bytes of the pages held / bandwidth, flops / peak)
over the device time under the `paged_attention:` scope (the latent
kernel and the row's write). `"moe"`: the expert layers' share of
theirs (bytes of the experts hit, the routers and the shared experts);
`"moe_ms"`: their device time a decode step, in ms. The expert layers'
ops are those under the `gated_moe:` scope and XLA's own grouped-product
kernels (`ragged-dot-*`), which the TPU compiler emits under no
framework scope and which nothing else in the program uses.

The selections on held experts, the held experts hit and the block size
are the engine's own counts of those very steps (its iteration
records); a prefill step fetches no expert count and takes the
expectation. None where the trace, the step log or those record fields
are missing.
"""
import re

from benchmark import trace_reduce, workmodel_mla as wm
from benchmark.readers import _serve_calls
from benchmark.readers.hybrid_work import records_of

MOE_SCOPE, MOE_NAMES = re.compile("gated_moe:"), re.compile(r"^ragged-dot")
ATTN_SCOPE = "paged_attention:"


def read(ctx, what):
    if what not in ("mfu", "attn", "moe", "moe_ms"):
        raise ValueError(f"mla_work: no reading {what!r}")
    if ctx.get("peaks") is None:
        return None
    sl = _serve_calls.calls(ctx, "slice")
    by_kind = _serve_calls.modules_by_kind(ctx)
    if not sl or not by_kind:
        return None
    recs = records_of(sl)
    if any(r is None or "kv_bytes_read" not in r
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
    if what == "attn":
        seconds = trace_reduce.scope_seconds(ops, ATTN_SCOPE)
    else:
        seconds = sum(o.dur for o in ops if MOE_SCOPE.search(o.tf_op)
                      or MOE_NAMES.search(o.name))
    if seconds <= 0:
        return None
    if what == "moe_ms":
        return seconds * 1e3 / len(by_kind["decode"])
    least = 0.0
    for (kind, start, nvalid, *_), r in zip(sl, recs):
        if kind != "decode":
            continue
        rows = nvalid > 0
        held = (start[rows] + nvalid[rows]).astype(float)
        if what == "attn":
            bs = r["block_size"]
            nbytes = wm.attn_bytes(sz, float((-(-held // bs)).sum()), bs)
            flops = wm.attn_flops(sz, float(held.sum()))
        else:
            flops = wm.moe_flops(sz, float(rows.sum()),
                                 r["moe_selected_held"])
            nbytes = wm.moe_bytes(sz, r["moe_experts_hit"])
        least += max(flops / peak, nbytes / bw)
    return 100.0 * least / seconds
