"""Serving subsystem: dynamic batching + shape-bucketed warmup + HTTP.

Reference: the reference framework's dedicated inference/serving layer
(predictor pools, request queues, service front ends). The TPU-native
redesign centers on XLA's whole-program, shape-specialized compilation:
naive serving recompiles on every novel (batch, seq) shape, so the
engine quantizes all traffic onto a fixed bucket ladder
(`BucketLadder`), coalesces concurrent requests into padded batches
(`DynamicBatcher`), and precompiles every ladder cell before accepting
traffic (`ServingEngine.warmup`). A stdlib HTTP front end
(`serving.http.serve`) exposes /v1/predict, /v1/generate, /healthz and
/metrics.

Autoregressive LLM traffic goes through `GenerationEngine`
(serving/generation.py): Orca-style continuous batching over the
paged programs the model's configuration builds
(`cfg.build_paged_step`) — requests join and leave a running decode
batch between steps, with the whole serving lifetime covered by the
two or three executables that `start()` compiles.

Quick start::

    from paddle_tpu.serving import EngineConfig, ServingEngine, serve
    cfg = EngineConfig(model_dir, max_batch_size=8, seq_buckets=(32, 64))
    srv = serve(ServingEngine(cfg), port=8000)   # warms up, then binds

See docs/serving.md for the architecture and the full stat inventory.
"""
from .batcher import (BucketLadder, DeadlineExceededError,  # noqa: F401
                      DynamicBatcher, EngineClosedError, OverloadedError,
                      QueueFullError, ServingError)
from .engine import EngineConfig, ServingEngine  # noqa: F401
from .generation import (GenerationEngine, GenerationRequest,  # noqa: F401
                         SlotManager)
from .http import ServingHTTPServer, serve  # noqa: F401
from .kv_blocks import (BlockPool, PrefixCache,  # noqa: F401
                        blocks_for_tokens)
from .disagg import (FleetPrefixStore, adopt_prefix,  # noqa: F401
                     export_prefix)
from .kv_wire import (KVShipment, pack_blocks,  # noqa: F401
                      unpack_blocks)
from .router import Replica, Router, RouterHTTP  # noqa: F401
from .spec_decode import NgramDrafter, update_spec_k  # noqa: F401

__all__ = ["BucketLadder", "DynamicBatcher", "EngineConfig",
           "ServingEngine", "ServingHTTPServer", "serve", "ServingError",
           "QueueFullError", "DeadlineExceededError", "EngineClosedError",
           "OverloadedError", "GenerationEngine", "GenerationRequest",
           "SlotManager", "BlockPool", "PrefixCache",
           "blocks_for_tokens", "Replica", "Router", "RouterHTTP",
           "NgramDrafter", "update_spec_k", "FleetPrefixStore",
           "export_prefix", "adopt_prefix", "KVShipment",
           "pack_blocks", "unpack_blocks"]
