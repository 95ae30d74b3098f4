"""The readings that the limits of a `hybrid_serve` cell's `correct`
are set from (PERF.md records them): `readings.py` for a family whose
weights are made a layer at a time and whose controls are its own
(`ssm_bfloat16`, `weights_int8`: the reference's `CONTROLS`). One
process, one engine; every seed gets the cell's own window, its sample
of finished requests compared with the reference, and where asked the
controls over the same sample.

    python3 benchmark/readings_hybrid.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2] [--seconds 40]

Not part of a benchmark run; the driver never calls it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    from benchmark import correct, manifest, run, serve_window
    from paddle_tpu.core.compile_cache import configure_compile_cache
    configure_compile_cache()
    wl, cfg, mix, limits = manifest.cell(args.workload, args.rehearsal)
    family = manifest.family(cfg["family"])
    controls = manifest.reference(cfg["name"]).CONTROLS
    vocab = family.sizes(cfg)["vocab_size"]

    def say(**kw):
        print(json.dumps(kw), flush=True)

    def verdict(numbers):
        ok, rows = correct.judge(numbers, limits)
        return {"judged_correct": ok,
                "over_limit": [n for n, v, lim in rows
                               if v is None or not v <= lim]}

    cell = family.build(cfg, mix, int(wl["chips"]), seeds[0])
    cell.warm()
    try:
        for seed in seeds:
            t = time.perf_counter()
            cell.set_weights(seed)
            requests = serve_window.make_requests(mix, seed, vocab,
                                                  args.seconds)
            _, sent, _ = serve_window.run(cell, mix, requests, args.seconds)
            picked = serve_window.check_sample(mix, seed, requests)
            compiles = cell.post_warmup_compiles()
            failed = sum(r.failed() for r in sent)
            cell.stop()
            numbers = run.served_numbers(cfg, seed, picked)
            numbers.update(compiles_in_window=float(compiles),
                           requests_failed=float(failed))
            say(seed=seed, who="program", **numbers, **verdict(numbers),
                checked=[r.index for r in picked], sent=len(sent),
                s=round(time.perf_counter() - t, 1))
            if seed in control_seeds:
                for name in controls:
                    low = run.served_numbers(cfg, seed, picked,
                                             control=name)
                    low.update(compiles_in_window=0.0, requests_failed=0.0)
                    say(seed=seed, who=f"control_{name}", **low,
                        **verdict(low))
            del requests, sent, picked
            cell.warm()
    finally:
        cell.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
