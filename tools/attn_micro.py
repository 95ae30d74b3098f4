"""Flash (Pallas) vs composed-XLA attention A/B at bench shapes.

Sweeps seq 512/1024/2048 (fwd and fwd+bwd) and, at each seq, the flash
block-tile grid — the kernel-level half of what settles
`models/transformer.py`'s `use_flash` default with a number (the
end-to-end half is ROADMAP queue 1 item 7). Run on the chip:

    python tools/attn_micro.py [--seqs 512,1024,2048] [--bh 384]

Reference analogue for measure-then-dispatch:
paddle/fluid/operators/jit/benchmark.cc.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.ops.pallas.flash_attention import (  # noqa: E402
    flash_attention, reference_attention)


def timed(f, *args, n=20):
    g = jax.jit(f)
    jax.block_until_ready(g(*args))  # compile outside the window
    t0 = time.perf_counter()
    for _ in range(n):
        o = g(*args)
    jax.block_until_ready(o)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="512,1024,2048")
    ap.add_argument("--bh", type=int, default=32 * 12,
                    help="batch*heads (BERT-base bench default)")
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--blocks", default="128,256,512",
                    help="flash block tiles to sweep (q=k)")
    ap.add_argument("--iters", type=int, default=20,
                    help="timed iterations per variant")
    ap.add_argument("--emit-cache", default="",
                    help="write each seq's winning flash tile into the "
                         "autotune JSON cache at this path (seeds "
                         "FLAGS_flash_autotune=cached processes; see "
                         "ops/pallas/autotune.py)")
    args = ap.parse_args()

    from paddle_tpu.ops.pallas import autotune

    d = args.d
    k0 = jax.random.PRNGKey(0)
    cache_entries = {}
    for t in [int(s) for s in args.seqs.split(",")]:
        # hold tokens ~constant so long-seq rows fit HBM
        bh = args.bh if t <= 512 else max(8, args.bh * 512 // t)
        q = jax.random.normal(k0, (bh, t, d), jnp.bfloat16)
        k = jax.random.normal(k0, (bh, t, d), jnp.bfloat16)
        v = jax.random.normal(k0, (bh, t, d), jnp.bfloat16)

        def loss_ref(q, k, v):
            return jnp.sum(reference_attention(q, k, v)
                           .astype(jnp.float32))

        rows = []
        fwd = timed(loss_ref, q, k, v, n=args.iters)
        g = jax.grad(loss_ref, argnums=(0, 1, 2))
        bwd = timed(lambda q, k, v: sum(
            jnp.sum(x.astype(jnp.float32)) for x in g(q, k, v)), q, k, v,
            n=args.iters)
        rows.append(("xla", None, fwd, bwd))

        for blk in [int(b) for b in args.blocks.split(",")]:
            if blk > t or t % blk:
                continue

            def loss_flash(q, k, v, _blk=blk):
                return jnp.sum(
                    flash_attention(q, k, v, block_q=_blk, block_k=_blk)
                    .astype(jnp.float32))

            fwd = timed(loss_flash, q, k, v, n=args.iters)
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))
            bwd = timed(lambda q, k, v: sum(
                jnp.sum(x.astype(jnp.float32)) for x in gf(q, k, v)),
                q, k, v, n=args.iters)
            rows.append(("flash", blk, fwd, bwd))

        best = min(rows, key=lambda r: r[3])
        for name, blk, fwd, bwd in rows:
            tag = f"{name}" + (f" blk={blk}" if blk else "")
            star = "  <- winner" if (name, blk) == best[:2] else ""
            print(f"seq {t} bh {bh}: {tag}: fwd {fwd * 1e3:.2f} ms  "
                  f"fwd+bwd {bwd * 1e3:.2f} ms{star}", flush=True)

        flash_rows = [r for r in rows if r[0] == "flash"]
        if args.emit_cache and flash_rows:
            # key by the kernel's padded seq so resolve() finds it
            blk = min(flash_rows, key=lambda r: r[3])[1]
            t_pad = -(-t // 128) * 128
            cache_entries[autotune.cache_key(t_pad, d, "bfloat16",
                                             False)] = \
                {"block_q": int(blk), "block_k": int(blk)}

    if args.emit_cache and cache_entries:
        path = autotune.store(cache_entries, args.emit_cache,
                              source="attn_micro")
        print(f"wrote {len(cache_entries)} autotune entries -> {path}",
              flush=True)


if __name__ == "__main__":
    main()
