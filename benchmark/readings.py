"""The readings that the limits of `correct` are set from (PERF.md
records them): the program on a dozen seeds, the control in the next
precision below, and the faults, all at the cell's own size, in ONE
process so that set-up is paid once.

    python3 benchmark/readings.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 35]

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
    ap.add_argument("--seconds", type=float, default=35.0)
    ap.add_argument("--sweep-rates", default="",
                    help="serving, open loop: offer the trace at these "
                         "rates instead, one window each, to find the knee")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    from benchmark import manifest
    from benchmark import reference_layers as rl
    from paddle_tpu.core.compile_cache import configure_compile_cache
    configure_compile_cache()
    wl, cfg, mix, limits = manifest.cell(args.workload, args.rehearsal)
    family = manifest.family(cfg["family"])
    chips = int(wl["chips"])

    def say(**kw):
        print(json.dumps(kw), flush=True)

    def verdict(numbers):
        """What `judge` says of these numbers under the cell's limits,
        and the numbers over theirs."""
        ok, rows = correct.judge(numbers, limits)
        return {"judged_correct": ok,
                "over_limit": [n for n, v, lim in rows
                               if v is None or not v <= lim]}

    from benchmark import correct
    if family.KIND == "train":
        from benchmark import traffic
        sizes = family.sizes(cfg)
        n_check = int(mix["check_steps"])
        for seed in seeds:
            t = time.perf_counter()
            cell = family.build(cfg, mix, chips, seed)
            batches = traffic.train_batches(seed, mix, cell.rows,
                                            sizes["vocab_size"])[:n_check]
            prog = cell.check_steps(batches)
            rows = cell.rows
            cell.free()
            del cell
            ref = family.reference_readings(cfg, seed, batches)
            numbers, where = correct.train_numbers(prog, ref)
            say(seed=seed, who="program", **numbers, **where,
                **verdict(numbers), s=round(time.perf_counter() - t, 1))
            if seed in control_seeds:
                low = family.reference_readings(cfg, seed, batches,
                                                prec=rl.INT8)
                numbers = correct.train_numbers(low, ref)[0]
                say(seed=seed, who="control_int8", **numbers,
                    **verdict(numbers))
                half = family.reference_readings(cfg, seed, batches,
                                                 rows_used=rows // 2)
                numbers = correct.train_numbers(half, ref)[0]
                say(seed=seed, who="fault_half_batch", **numbers,
                    **verdict(numbers))
                if chips > 1:
                    one = family.reference_readings(
                        cfg, seed, batches, rows_used=rows // chips)
                    numbers = correct.train_numbers(one, ref)[0]
                    say(seed=seed, who="fault_no_exchange", **numbers,
                        **verdict(numbers))
        return 0

    from benchmark import run, serve_window, weights
    from paddle_tpu import trace
    sizes = family.sizes(cfg)
    cell = family.build(cfg, mix, chips, seeds[0])
    cell.warm()
    try:
        rates = [float(r) for r in args.sweep_rates.split(",") if r]
        for k, rate in enumerate(rates):
            # a seed of its own for every rate: the same token ids twice
            # would be served from the prefix cache
            swept = {**mix, "rate_per_s": rate,
                     "requests": max(int(mix["requests"]),
                                     int(2 * rate * args.seconds))}
            requests = serve_window.make_requests(
                swept, seeds[0] + k, sizes["vocab_size"], args.seconds)
            t0, sent, _ = serve_window.run(cell, swept, requests,
                                           args.seconds)
            ttft = serve_window.ttft_ms(t0, sent)
            t_last = max(r.stamps[-1] for r in sent if r.stamps)
            live = [r["active_slots"] for r in trace.iteration_records()
                    if t0 <= r["t_end"] <= t0 + args.seconds]
            say(rate=rate, sent=len(sent),
                ttft_p50_ms=serve_window.percentile(ttft, 50),
                ttft_p90_ms=serve_window.percentile(ttft, 90),
                **serve_window.ttft_thirds(ttft),
                gap_p95_ms=serve_window.percentile(
                    serve_window.gaps_ms(sent), 95),
                active_slots_mean=sum(live) / max(1, len(live)),
                iterations=len(live),
                late_p95_ms=serve_window.late_ms(t0, sent, 95),
                drained_after_s=t_last - t0 - args.seconds,
                failed=sum(r.failed() for r in sent))
            while cell.load():
                time.sleep(0.05)
        if args.sweep_rates:
            return 0
        for seed in seeds:
            t = time.perf_counter()
            for name, arr in weights.make_weights(seed, sizes).items():
                cell.scope.set(name, arr)
            requests = serve_window.make_requests(
                mix, seed, sizes["vocab_size"], args.seconds)
            _, sent, _ = serve_window.run(cell, mix, requests, args.seconds)
            # wait for the sample as a run does for its answers, then
            # empty the engine for the next seed
            want = min(int(mix["check_requests"]),
                       len(serve_window.check_candidates(mix, seed,
                                                         requests)))
            until = time.perf_counter() + 90.0
            while len(serve_window.check_sample(mix, seed, requests)) < want \
                    and time.perf_counter() < until:
                time.sleep(0.05)
            picked = serve_window.check_sample(mix, seed, requests)
            compiles = cell.post_warmup_compiles()
            failed = sum(r.failed() for r in sent)
            cell.stop()
            numbers = run.served_numbers(cfg, seed, picked)
            numbers.update(
                compiles_in_window=float(compiles),
                requests_failed=float(failed))
            say(seed=seed, who="program", **numbers, **verdict(numbers),
                finished=sum(r.finished() for r in sent), sent=len(sent),
                s=round(time.perf_counter() - t, 1))
            if seed in control_seeds:
                for name, prec in (("bfloat16", rl.BFLOAT16),
                                   ("int8", rl.INT8)):
                    low = run.served_numbers(cfg, seed, picked, control=prec)
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
