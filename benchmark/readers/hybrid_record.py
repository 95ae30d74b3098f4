"""What the engine's iteration records say of a model with routed
experts and recurrent state, over the window (`iteration_record`'s
records). `held_share`: selections that fell on experts held here over
selections made, in %. `load_max_over_mean`: tokens on the busiest held
expert over the mean over the held experts, summed over the expert
layers and the window's decode steps. `state_bytes_share`: recurrent
state of the live slots over that plus the KV of the tokens resident,
mean over the records, in %. None where the records lack the fields
(a program from before them) or nothing was counted."""
from benchmark import workmodel_hybrid as wm
from benchmark.readers import iteration_record


def read(ctx, what):
    recs = [r for r in iteration_record.records(ctx)
            if "moe_selected" in r]
    if what == "state_bytes_share":
        per_token = wm.kv_token_bytes(ctx["sizes"])
        shares = [r["state_bytes"]
                  / (r["state_bytes"] + r["kv_tokens_resident"] * per_token)
                  for r in recs if r["state_bytes"] > 0]
        return 100.0 * sum(shares) / len(shares) if shares else None
    made = sum(r["moe_selected"] for r in recs)
    held = sum(r["moe_selected_held"] for r in recs)
    if what == "held_share":
        return 100.0 * held / made if made else None
    if what == "load_max_over_mean":
        busiest = sum(r["moe_load_max"] for r in recs)
        return busiest * ctx["sizes"]["experts_held"] / held if held else None
    raise ValueError(f"hybrid_record: no reading {what!r}")
