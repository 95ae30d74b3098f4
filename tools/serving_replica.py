"""Standalone subprocess replica: one engine + HTTP front end per
process.

The missing piece between the in-process router drills and a real
fleet: `serving_loadgen --router --disagg` (and anything else that
wants genuine process isolation) launches N of these, each binding an
ephemeral port and writing it to --port-file, then registers them with
the Router as ``Replica(url=...)``. Two backends:

* --model-dir DIR: a saved inference model behind a warmed
  ServingEngine (/v1/predict).
* --weights FILE.npz: a tiny-GPT GenerationEngine (/v1/generate,
  /v1/kv/export, /v1/kv/adopt). The npz holds the trained (or scratch)
  parameter tensors under their training-graph names; the engine's
  startup program is never run, so the loaded weights survive and
  every replica process decodes from IDENTICAL parameters — the
  property the disagg wrong-answers gate leans on.

Lifecycle: build -> warm (all compiles) -> bind -> write --port-file
(atomically, AFTER readiness) -> print one ``{"kind":
"replica_ready"}`` line -> serve until SIGTERM/SIGINT -> drain and
exit 0. SIGTERM-clean by construction: the handler only sets an
event; draining happens on the main thread.

Usage (normally spawned by tools/serving_loadgen.py):
    python tools/serving_replica.py --weights w.npz --vocab 64 \
        --max-seq 96 --block-size 8 --port-file /tmp/r0.port
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build_gen_engine(args):
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt
    from paddle_tpu.serving import GenerationEngine

    cfg = gpt.gpt_small(vocab_size=args.vocab, d_model=args.d_model,
                        n_heads=args.n_heads, n_layers=args.n_layers,
                        d_ff=args.d_ff, max_seq_len=args.max_seq,
                        dropout=0.0, use_flash=False)
    scope = fluid.Scope()
    data = np.load(args.weights)
    for name in data.files:
        scope.var(name)
        scope.set(name, np.array(data[name]))
    engine = GenerationEngine(
        cfg, scope, max_slots=args.slots, max_seq=args.max_seq,
        default_timeout_ms=args.timeout_ms,
        block_size=args.block_size or None,
        kv_pool_blocks=args.kv_pool_blocks or None,
        spec_decode=args.spec_decode or None,
        spec_k=args.spec_k or None)
    # start() seeds only the decode state ("gen." names) and warms the
    # executables; the loaded weights are untouched
    return engine


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="standalone subprocess serving replica")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 (default) binds an ephemeral port")
    ap.add_argument("--port-file",
                    help="write the bound port here once READY "
                         "(written atomically after warmup + bind)")
    ap.add_argument("--model-dir",
                    help="saved inference model -> ServingEngine "
                         "(/v1/predict)")
    ap.add_argument("--weights",
                    help="npz of tiny-GPT parameters -> "
                         "GenerationEngine (/v1/generate + /v1/kv/*)")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=0)
    ap.add_argument("--kv-pool-blocks", type=int, default=0)
    ap.add_argument("--timeout-ms", type=float, default=10000.0)
    ap.add_argument("--max-batch-size", type=int, default=8)
    ap.add_argument("--seq-buckets", default="8,16,32")
    ap.add_argument("--spec-decode", action="store_true")
    ap.add_argument("--spec-k", type=int, default=0)
    args = ap.parse_args(argv)

    if not args.model_dir and not args.weights:
        print("need --model-dir and/or --weights", file=sys.stderr)
        return 2

    from paddle_tpu.core.compile_cache import configure_compile_cache
    from paddle_tpu.serving import serve

    configure_compile_cache()
    engine = None
    gen = None
    if args.model_dir:
        from paddle_tpu.serving import EngineConfig, ServingEngine
        engine = ServingEngine(EngineConfig(
            args.model_dir, max_batch_size=args.max_batch_size,
            default_timeout_ms=args.timeout_ms,
            seq_buckets=tuple(int(s) for s in
                              args.seq_buckets.split(",")),
            warmup=True))
    if args.weights:
        gen = build_gen_engine(args)

    stop_evt = threading.Event()

    def _on_signal(signum, frame):
        stop_evt.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    # serve() warms the engines (every compile of the process's
    # lifetime) before binding, so the port's appearance IS readiness
    srv = serve(engine, port=args.port, gen_engine=gen)
    port = srv.port
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(port))
        os.replace(tmp, args.port_file)  # atomic: readers never see ""
    import jax
    print(json.dumps({"kind": "replica_ready", "pid": os.getpid(),
                      "platform": jax.default_backend(),
                      "port": port, "url": f"http://{args.host}:{port}",
                      "predict": engine is not None,
                      "generate": gen is not None}), flush=True)

    while not stop_evt.wait(0.2):
        pass

    # SIGTERM-clean: finish in-flight work, then release everything
    srv.close(drain=True)
    if gen is not None:
        gen.stop(drain=True)
    if engine is not None:
        engine.stop(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
