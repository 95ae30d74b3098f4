"""Per-op micro-benchmark harness — the reference's
operators/benchmark/op_tester.cc re-expressed for the TPU registry.

    python tools/op_bench.py matmul --shape 4096x4096 --dtype bfloat16
    python tools/op_bench.py softmax --shape 8192x32768
    python tools/op_bench.py flash_attention --shape 384x512x64

Times the op's registered lowering under jit with the async-chain +
single-sync methodology bench.py uses (the window ends in
`jax.block_until_ready`), and prints ms/op plus achieved GB/s and
TFLOP/s where derivable from the shapes.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("op", help="registered op type (e.g. matmul, softmax)")
    ap.add_argument("--shape", default="1024x1024",
                    help="AxBxC input shape (matmul: A x B @ B x C)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--attrs", default="",
                    help="comma k=v attrs (ints/floats/bools parsed)")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import jax
    import jax.numpy as jnp

    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.core.registry import REGISTRY

    dims = [int(d) for d in args.shape.lower().split("x")]
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(0)

    attrs = {}
    for kv in filter(None, args.attrs.split(",")):
        k, v = kv.split("=")
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                pass
        attrs[k] = {"true": True, "false": False}.get(str(v).lower(), v)

    def arr(shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    opdef = REGISTRY.get(args.op)
    flops = None
    if args.op in ("matmul", "mul", "matmul_v2"):
        a, b, c = dims[0], dims[1], dims[2] if len(dims) > 2 else dims[1]
        ins = {"X": [arr((a, b))], "Y": [arr((b, c))]}
        flops = 2 * a * b * c
    elif args.op == "flash_attention":
        bh, t, d = dims
        ins = {"Q": [arr((bh, t, d))], "K": [arr((bh, t, d))],
               "V": [arr((bh, t, d))]}
        flops = 4 * bh * t * t * d
    else:
        ins = {"X": [arr(tuple(dims))]}

    class Ctx:
        is_test = True
        mesh = None
        rng = jax.random.PRNGKey(0)

    def fn(ins):
        return opdef.lower(Ctx(), ins, attrs)

    jitted = jax.jit(fn)
    out = jax.block_until_ready(jitted(ins))  # compile + drain

    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = jitted(ins)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / args.steps

    in_bytes = sum(v.size * v.dtype.itemsize
                   for vs in ins.values() for v in vs)
    out_bytes = sum(v.size * v.dtype.itemsize
                    for v in jax.tree.leaves(out)
                    if hasattr(v, "itemsize") or hasattr(v, "dtype"))
    line = f"{args.op} {args.shape} {args.dtype}: {dt * 1e3:.3f} ms"
    line += f", {(in_bytes + out_bytes) / dt / 1e9:.1f} GB/s"
    if flops:
        line += f", {flops / dt / 1e12:.1f} TFLOP/s"
    print(line)


if __name__ == "__main__":
    main()
