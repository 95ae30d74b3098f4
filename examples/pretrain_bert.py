"""BERT-base masked-LM-style pretraining step — the benchmark flagship
(bench.py config 3) as a runnable script.

    python examples/pretrain_bert.py [--cpu] [--tiny] [--steps N]

Shows: AMP bf16 (contrib.mixed_precision), the Pallas flash-attention
kernel, and state donation (parameters update in place at the XLA
buffer level, no per-step host copies).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="4-layer d=128 config for a quick local run")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    if args.tiny:
        cfg = transformer.TransformerConfig(
            vocab_size=1000, d_model=128, n_heads=4, n_layers=4,
            d_ff=512, dropout=0.1, attn_dropout=0.0)
    else:
        cfg = transformer.bert_base(dropout=0.1, attn_dropout=0.0)

    main_prog, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.program_guard(main_prog, startup), fluid.scope_guard(scope):
        loss, feeds = transformer.build_train(cfg, args.batch, args.seq,
                                              lr=1e-4, amp=True)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        toks = rng.randint(0, cfg.vocab_size, (args.batch, args.seq)) \
            .astype(np.int64)
        feed = {"tokens": toks, "labels": toks}
        exe.run(main_prog, feed=feed, fetch_list=[loss])  # compile
        t0 = time.perf_counter()
        for i in range(args.steps):
            lv, = exe.run(main_prog, feed=feed, fetch_list=[loss])
            if (i + 1) % 5 == 0:
                print(f"step {i + 1}: loss {float(np.asarray(lv)):.4f}")
        dt = (time.perf_counter() - t0) / args.steps
    print(f"{args.batch * args.seq / dt:,.0f} tokens/s "
          f"({dt * 1e3:.1f} ms/step, includes host sync each step — "
          f"see bench.py for the async-window measurement)")


if __name__ == "__main__":
    main()
