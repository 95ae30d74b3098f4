"""The `kda_serve` family's shares of a peak and of its memory, with
`workmodel_kda`'s counts.

`what="mfu"`: forward flops of the tokens the traced slice's calls
really advanced over the slice's length, first executable's start to
last one's end, as a share of the chip's peak (defined as `mfu.batch`
is). `"kda"`: the linear-attention layers' share of their roofline in
the slice's decode steps: max(the live rows' state and windows read and
written once + the mixers' weights over the bandwidth, flops over the
peak) over the device time under the `kda_mixer:` scope, whatever
implements the mixer; `"kda_ms"`: that device time a decode step, in
ms. The rows a step fed are the step log's; the selections on held
experts the engine's own counts of those very steps (its iteration
records), and a prefill step, which fetches no expert count, takes the
expectation. `"state_bytes_share"`: recurrent state of the live slots
over that plus the latent rows of the tokens resident, mean over the
window's iteration records, in %.

None where the trace, the step log, the `kda_mixer:` scope or the
record fields are missing (a program from before them).
"""
from benchmark import trace_reduce, workmodel_kda as wm
from benchmark.readers import _serve_calls, iteration_record
from benchmark.readers.hybrid_work import records_of

KDA_SCOPE = "kda_mixer:"


def state_bytes_share(ctx):
    per_token = wm.kv_token_bytes(ctx["sizes"])
    shares = [r["state_bytes"]
              / (r["state_bytes"] + r["kv_tokens_resident"] * per_token)
              for r in iteration_record.records(ctx)
              if r.get("state_bytes", 0) > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None


def read(ctx, what):
    if what == "state_bytes_share":
        return state_bytes_share(ctx)
    if what not in ("mfu", "kda", "kda_ms"):
        raise ValueError(f"kda_work: no reading {what!r}")
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
    seconds = trace_reduce.scope_seconds(ops, KDA_SCOPE)
    if seconds <= 0:
        return None
    if what == "kda_ms":
        return seconds * 1e3 / len(by_kind["decode"])
    least = 0.0
    for kind, _, nvalid, *_ in sl:
        if kind == "decode":
            rows = float((nvalid > 0).sum())
            least += max(wm.kda_flops(sz, rows) / peak,
                         wm.kda_bytes(sz, rows) / bw)
    return 100.0 * least / seconds
