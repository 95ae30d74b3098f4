"""Profile one benchmark training step on the attached device and print
a device-time breakdown.

Usage (on TPU; also runs on CPU for plumbing checks):
    python tools/profile_step.py [bert|resnet50]

Uses bench.py's model builders, so the profiled program is EXACTLY the
benchmarked one (same BENCH_BATCH/BENCH_SEQ/BENCH_AMP/BENCH_FLASH env
config). Captures a jax.profiler trace around a handful of steps
(enqueued async, one `block_until_ready` at the end) and
aggregates the XPlane device events by category via
fluid.profiler.summarize_xplane: the per-op cost discipline of the
reference's operators/benchmark/op_tester.cc applied to the whole step.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402


def main():
    args = [a for a in sys.argv[1:] if a != "--cpu"]
    if "--cpu" in sys.argv:
        import jax
        jax.config.update("jax_platforms", "cpu")
    model = args[0] if args else "bert"
    import bench
    import paddle_tpu as fluid
    from paddle_tpu import monitor, profiler

    # a profile run IS a metrics run: turn the monitor on (unless the
    # user explicitly set the flag) so the same command yields both the
    # device trace and a JSONL stats snapshot next to it
    if "FLAGS_enable_monitor" not in os.environ:
        fluid.set_flags({"FLAGS_enable_monitor": True})

    build = bench.build_resnet50_bench if model == "resnet50" \
        else bench.build_bert_bench
    exe, prog, scope, feed, loss, _ = build()
    trace_dir = "/tmp/paddle_tpu_profile_step"
    with fluid.scope_guard(scope):
        _profile(exe, prog, feed, loss, trace_dir, profiler)
    if monitor.enabled():
        log = monitor.snapshot_to_jsonl(
            os.path.join(trace_dir, "monitor.jsonl"))
        print(f"# monitor snapshot: {log} "
              f"(report: python tools/metrics_report.py {log})")


def _profile(exe, prog, feed, loss, trace_dir, profiler, steps=5):
    # warm up + compile outside the trace
    exe.run(prog, feed=feed, fetch_list=[loss])
    x, = exe.run(prog, feed=feed, fetch_list=[loss], return_numpy=False)
    np.asarray(x)
    profiler.start_profiler(output_dir=trace_dir)
    for _ in range(steps):
        x, = exe.run(prog, feed=feed, fetch_list=[loss],
                     return_numpy=False)
    np.asarray(x)  # drain before stopping the trace
    profiler.stop_profiler()
    summary = profiler.summarize_xplane(trace_dir)
    summary["per_step_us"] = summary["total_us"] / steps
    print(json.dumps({
        "per_step_us": round(summary["per_step_us"], 1),
        "by_category_us": {k: round(v, 1)
                           for k, v in summary["by_category"].items()},
        "top_ops_us": [(n, round(v, 1))
                       for n, v in summary["top_ops"][:15]],
    }, indent=1))


if __name__ == "__main__":
    main()
