"""StableHLO bf16 audit of a bench path's whole training step.

Lowers the EXACT benched step (tiny shapes — dtypes are shape-
independent) on CPU and reports every dot_general / convolution with
its operand dtypes. An f32 dot on the MXU runs at 1/4-1/8 the bf16
rate, so "ALL dots bf16" is the strongest off-chip evidence the AMP
rewrite holds end-to-end (fwd + vjp + optimizer).

    python tools/hlo_audit.py [bert|resnet50|gpt|transformer|deeplab|all]

Reference analogue for the audit discipline:
paddle/fluid/operators/benchmark/op_tester.cc (measure the op you
ship, not a proxy).
"""
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def audit(model):
    import bench
    import paddle_tpu as fluid

    os.environ["BENCH_FLASH"] = "0"  # audit the composed XLA path
    exe, prog, scope, feed, loss, _ = bench._CPU_TINY_BUILDS[model]()
    with fluid.scope_guard(scope):
        txt = exe.lowered_stablehlo(prog, feed=feed, fetch_list=[loss])

    # capture the TYPE SIGNATURE tuple `: (tensor<..>, tensor<..>)`,
    # not the call operands (SSA names carry no dtypes)
    dots = re.findall(
        r"stablehlo\.dot_general\s+[^\n]*?:\s*\(([^)]*)\)\s*->\s*"
        r"tensor<[0-9x]*(\w+)>", txt)
    convs = re.findall(
        r"stablehlo\.convolution\([^\n]*?:\s*\(([^)]*)\)\s*->\s*"
        r"tensor<[0-9x]*(\w+)>", txt)

    def operand_dtypes(sig):
        return re.findall(r"tensor<[0-9x]*(\w+)>", sig)

    n_dot = len(dots)
    bf_dot = sum(1 for sig, _ in dots
                 if all(d == "bf16" for d in operand_dtypes(sig)[:2]))
    n_conv = len(convs)
    bf_conv = sum(1 for sig, _ in convs
                  if all(d == "bf16" for d in operand_dtypes(sig)[:2]))
    print(f"{model}: dot_general {bf_dot}/{n_dot} bf16-operand, "
          f"convolution {bf_conv}/{n_conv} bf16-operand", flush=True)
    f32_dots = [sig for sig, _ in dots
                if not all(d == "bf16" for d in operand_dtypes(sig)[:2])]
    for sig in f32_dots[:5]:
        print(f"  non-bf16 dot: {sig[:110]}")
    return n_dot, bf_dot, n_conv, bf_conv


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    import bench
    models = list(bench._CPU_TINY_BUILDS) if which == "all" else [which]
    for m in models:
        audit(m)


if __name__ == "__main__":
    main()
