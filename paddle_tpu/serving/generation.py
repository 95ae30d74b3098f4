"""Continuous-batching generation: slot-based KV-cache decode serving.

Reference: the reference framework ships autoregressive inference as
while_op beam-search decoders inside the graph — one request per
invocation. Serving LLM traffic needs the Orca model instead:
iteration-level scheduling, where the scheduler re-decides the batch
composition BETWEEN decode steps, so a finished request's slot is handed
to a queued request immediately rather than waiting for the whole batch
to finish.

On TPU the constraint that shapes this design is XLA shape
specialization: each step must be ONE fixed-shape executable for the
engine's whole lifetime. The engine asks the model's configuration for
its programs (`cfg.build_paged_step`: models/transformer.
TransformerConfig, models/hybrid.HybridConfig), and every one of them
takes the same per-step control feeds, a `block_table` row, a
`start_pos` and an `n_valid` for each slot: a new request joins a
running batch by being given blocks and a row of the table (no host
zero upload, no recompile), and an empty slot rides along muted with
n_valid=0, its writes landing in the scratch block. Admission, prefill,
sampling (host-side, models/sampling.py), eviction and re-admission all
happen without ever presenting XLA a novel shape —
`Executor.cache_stats()` misses stay frozen after the warmup compiles,
the same zero-post-warmup-compile contract `ServingEngine` keeps for
encoder traffic.

Queueing reuses the `batcher.py` vocabulary: bounded queue with
`QueueFullError` backpressure, per-request deadlines failing with
`DeadlineExceededError`, `EngineClosedError` + drain semantics on
shutdown, `_Response` future handles.

Paged KV: K/V lives in per-layer physical POOLS of fixed-size blocks
(`serving/kv_blocks.py`), addressed through per-slot block tables fed
to the `paged_attention` op every step; the op reads a slot's table
only as far as the slot's own length (a Pallas kernel,
ops/pallas/paged_attention.py), so a step costs what the slots hold and
not `max_seq`, and its outputs agree token for token with the serial
reference (`gpt.kv_generate` over one contiguous `[batch, max_seq]`
cache). Peak KV HBM is `num_blocks x block_bytes` — budget-derived and
decoupled from the longest POSSIBLE sequence — and three scheduler
moves fall out of the indirection: admission gates on free BLOCKS
(actual tokens) rather than slots alone; a slot "reset" is just
releasing its blocks back to the pool (no in-graph wipe — the table
simply never maps the old blocks again); and shared prompt prefixes
hit a content-hash `PrefixCache` so identical system prompts reuse the
same physical blocks and skip re-prefill. Prompts retire through a
second fixed-shape executable, `[max_slots, block_size]` (chunked
prefill), whose rows are TILES: a row is one page of some request's
prompt, and a step spends its rows on the oldest prompts first, many
pages of ONE request side by side (`_prefill_plan`). The executable is
a budget of max_slots x block_size prompt tokens a step, so a 10k-token
prompt costs ~10k / (max_slots x block_size) prefill steps, each
followed by the decode batch's step. A model with per-slot recurrent
state keeps a page a request a step. The compile contract is exactly
two executables (decode + chunk prefill), both compiled in `start()`:
`post_warmup_compiles()` stays 0 for the engine's lifetime.

Speculative decoding (FLAGS_gen_spec_decode / GenerationRequest
.spec_decode): a host-side n-gram drafter (`serving/spec_decode.py`)
proposes up to FLAGS_spec_decode_k tokens per slot between steps, and a
THIRD fixed-shape executable — the `[max_slots, k+1]` batched verify
step, `cfg.build_paged_step(seq_tokens=k+1)` — scores every draft
position in one pass. `models/sampling.py:accept_draft` commits the
longest agreeing prefix through the same sample_token path as serial
decode, so outputs stay token-for-token identical at any temperature;
each accepted token skips one whole decode iteration. The verify
executable is compiled in `start()` alongside the other two, keeping
`post_warmup_compiles()` at 0.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Callable, List, Optional, Sequence

import numpy as np

from .. import goodput as _goodput
from .. import trace
from ..monitor import STAT_ADD, STAT_OBSERVE, STAT_SET
from ..monitor import enabled as _monitor_on
from ..resilience.breaker import CLOSED, HALF_OPEN, OPEN, CircuitBreaker
from ..resilience.faults import TransientFault
from ..resilience.faults import injector as _fault_injector
from ..resilience.retry import RetryPolicy, is_transient
from .batcher import (DeadlineExceededError, EngineClosedError,
                      FRACTION_BUCKETS, MS_BUCKETS, OverloadedError,
                      QueueFullError, ServingError, _Response)
from .kv_blocks import (SCRATCH_BLOCK, BlockPool, PrefixCache,
                        blocks_for_tokens)

__all__ = ["GenerationRequest", "SlotManager", "GenerationEngine"]

# Effective tokens committed per verify step: 1 (full reject) through
# spec_k + 1 (full accept + bonus token). Count-valued, so the ms/
# fraction bucket ladders don't fit; upper rungs leave headroom for
# larger FLAGS_spec_decode_k settings.
SPEC_TOKEN_BUCKETS = (1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 8.0, 12.0, 16.0)

# The share of the prefill executable's rows that a step may fill while
# some row is decoding and no request waits in the queue. Every decoding
# row waits for the prefill step of its turn, and that step grows by
# the real tiles in it (0.35 ms a tile for gpt2_medium), so the share
# bounds the gap between a request's tokens. Of 3/4, 1/2 and 1/4 the
# largest that kept gpt2_medium.long_in_open_v2's gap_p95_ms within 3%
# of a page a slot a turn: unbounded +20%, 1/2 +7%, 1/4 -0.1%, TTFT
# p90 870 -> 63, 100 and 380 ms (PERF.md section 6, PR 34). With
# nothing decoding nobody waits on a gap; with requests queued for a
# slot the sooner a row reaches decode the sooner a slot comes free,
# and tokens a second is what is short (a share of 1/2 there took
# kimi_k2_5_ep32_l5.batch_long_ctx's gain away): the whole shape then.
PREFILL_ROWS_WHILE_DECODING = 0.25


def _pick_on_device(step, name):
    """What a decode (or verify) step hands to the host, appended to a
    step with logits inside its `program_guard`: `step.picks_var`,
    float32 `[2, B, T]`, and nothing else. `picks[0]` is each
    position's arg-max (the first of equals, as `np.argmax`; a
    vocabulary index is exact in float32), `picks[1]` its largest
    |logit|, finite exactly when every logit of the position is. They
    are ONE fetched variable because the fetch list is part of an
    executable's identity (`Executor._cache_key`) and a caller of
    `executables()` compiles `[fetch]`. The logits themselves stay on
    the device, under `step.logits_name`: a state variable the step
    writes, as it writes a KV pool, and that no op reads, so the
    Executor neither passes nor donates it and an array taken from the
    scope stays readable after later steps have run."""
    from .. import layers
    logits = step.logits_var
    B, T, V = (int(d) for d in logits.shape)
    if V >= 1 << 24:
        raise ValueError(f"a vocabulary of {V} has indices that float32 "
                         "does not hold exactly")
    pick = layers.cast(layers.argmax(logits, axis=2), "float32")
    top = layers.elementwise_max(
        layers.reduce_max(logits, dim=2),
        layers.scale(layers.reduce_min(logits, dim=2), scale=-1.0))
    step.picks_var = layers.stack([pick, top], axis=0)
    kept = layers.create_global_var(
        [B, T, V], 0.0, "float32", persistable=True,
        name=f"{step.state_prefix}logits.{name}")
    layers.assign(logits, output=kept)
    step.logits_name = kept.name


class _DeviceLogits:
    """A step's logits `[B, T, V]` as they lie on the device, or the
    rows `[i, j0:j1]` or the one row `[i, j]` of them: what
    `_run_paged` returns and what indexing it gives. Nothing crosses to
    the host until someone reads the numbers (`np.asarray`, `np.array`:
    `__array__`). The first row asked of a step brings the step's whole
    array over, once, and every later row is cut from that copy: on the
    chip a row gathered and copied alone takes as long as the whole
    array copied (1.3 ms either way for `[32, 1, 50257]`: PERF.md,
    PR 32), and a second row would pay it again. `rows_read` counts the
    rows that were asked for, over every view of the step. A row
    answers `argmax()` from the step's fetched picks and reads nothing
    (models/sampling.py: the greedy case)."""

    __slots__ = ("_arr", "_picks", "_host", "_i", "_j")

    def __init__(self, arr, picks, host=None, i=None, j=None):
        self._arr = arr          # jax.Array [B, T, V], not donated
        self._picks = picks      # np [B, T], the positions' arg-maxes
        # shared by the views of a step: the host's copy once someone
        # asked ("whole"), and the (i, j) that were asked for
        self._host = {"asked": set()} if host is None else host
        self._i = i              # None: every slot
        self._j = j              # a range: rows of slot i; an int: a row

    @property
    def rows_read(self):
        return len(self._host["asked"])

    @property
    def shape(self):
        B, T, V = self._arr.shape
        if self._i is None:
            return (B, T, V)
        return (V,) if isinstance(self._j, int) else (len(self._j), V)

    def __len__(self):
        return self.shape[0]

    def __getitem__(self, key):
        if self._i is None:
            i, j = key
            B, T, _ = self._arr.shape
            return _DeviceLogits(self._arr, self._picks, self._host,
                                 range(B)[i], range(T)[j])
        if isinstance(self._j, int):
            return np.asarray(self)[key]
        return _DeviceLogits(self._arr, self._picks, self._host,
                             self._i, self._j[key])

    def argmax(self):
        if self._i is None or not isinstance(self._j, int):
            return np.asarray(self).argmax()
        return int(self._picks[self._i, self._j])

    def __array__(self, dtype=None, copy=None):
        host, i, j = self._host, self._i, self._j
        if "whole" not in host:
            host["whole"] = np.asarray(self._arr)
        out = host["whole"]
        if i is None:
            B, T, _ = out.shape
            asked = [(b, t) for b in range(B) for t in range(T)]
        elif isinstance(j, int):
            asked, out = [(i, j)], out[i, j]
        else:
            asked = [(i, t) for t in j]
            out = out[i, j.start:j.stop:j.step]
        host["asked"].update(asked)
        if dtype is not None and out.dtype != dtype:
            return out.astype(dtype)
        return out.copy() if copy else out


class GenerationRequest:
    """One generation job: prompt in, up to `max_new_tokens` out.

    `temperature`/`top_k` select the sampling policy (see
    models/sampling.py; temperature 0 = greedy, fully deterministic
    given `seed`). `eos_id` stops the request early when sampled.
    `timeout_ms` is a wall-clock deadline covering queue wait AND
    decode; None falls back to the engine default. `stream_cb(token_id)`
    fires from the engine thread after every generated token — the
    streaming hook (and the loadgen's TTFT/inter-token probe).
    `logits_cb(row)` fires from the engine thread too, once for every
    generated token and just before its `stream_cb`, with the logits row
    that the token was sampled from, float32 `[vocabulary]`: asking is
    what brings a step's logits to the host (a step leaves them on the
    device), one copy a step for all the rows that ask in it. None
    costs one attribute check.
    `spec_decode` opts this request in/out of speculative decoding
    (serving/spec_decode.py): None defers to the engine default
    (FLAGS_gen_spec_decode), False forces plain one-token decode, True
    speculates when the engine carries the verify executable (and
    degrades silently to plain decode when it does not — outputs are
    identical either way, only the step count changes).
    """

    __slots__ = ("prompt", "max_new_tokens", "temperature", "top_k",
                 "eos_id", "timeout_ms", "seed", "stream_cb",
                 "spec_decode", "logits_cb")

    def __init__(self, prompt: Sequence[int], max_new_tokens: int,
                 temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None,
                 timeout_ms: Optional[float] = None, seed: int = 0,
                 stream_cb: Optional[Callable[[int], None]] = None,
                 spec_decode: Optional[bool] = None,
                 logits_cb: Optional[Callable[[np.ndarray], None]] = None):
        self.prompt = [int(t) for t in prompt]
        if not self.prompt:
            raise ValueError("GenerationRequest: prompt must be "
                             "non-empty")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("GenerationRequest: max_new_tokens must "
                             "be >= 1")
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.timeout_ms = timeout_ms
        self.seed = int(seed)
        self.stream_cb = stream_cb
        self.spec_decode = None if spec_decode is None \
            else bool(spec_decode)
        self.logits_cb = logits_cb


class SlotManager:
    """Free-list over the decode graph's B slots.

    Owned by the engine worker thread (admission and eviction both
    happen between steps on that thread), so no internal locking.
    """

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError("SlotManager: need at least one slot")
        self.n_slots = int(n_slots)
        self._free = list(range(self.n_slots - 1, -1, -1))  # pop() -> 0 first

    def acquire(self) -> Optional[int]:
        """Lowest free slot index, or None when fully occupied."""
        return self._free.pop() if self._free else None

    def release(self, slot: int):
        if slot in self._free or not 0 <= slot < self.n_slots:
            raise ValueError(f"SlotManager: bad release of slot {slot}")
        self._free.append(slot)
        self._free.sort(reverse=True)

    def free_count(self) -> int:
        return len(self._free)

    def active_count(self) -> int:
        return self.n_slots - len(self._free)


class _SlotState:
    """Per-occupied-slot decode progress (worker-thread private)."""

    __slots__ = ("req", "response", "fed", "cur", "generated", "rng",
                 "deadline", "t_submit", "t_prev_token",
                 "ttft_ms", "blocks", "n_cached", "registered",
                 "span", "phase_span", "fetch_s",
                 "spec_k_cur", "spec_acc_ewma")

    def __init__(self, req: GenerationRequest, response: _Response,
                 deadline: Optional[float], t_submit: float):
        self.req = req
        self.response = response
        self.fed = 0                  # tokens already stepped (== the
        #                               slot's next KV write position)
        self.cur = req.prompt[0]      # next token to feed
        self.generated: List[int] = []
        self.rng = np.random.RandomState(req.seed)
        self.deadline = deadline
        self.t_submit = t_submit
        self.t_prev_token: Optional[float] = None
        self.ttft_ms: Optional[float] = None
        # paged-KV bookkeeping: the slot's block table (shared prefix
        # blocks first, then owned), prefix-cache hit length in tokens,
        # and whether the full prompt blocks have been registered
        self.blocks: List[int] = []
        self.n_cached = 0
        self.registered = False
        # Tracing: the request span (carried over from _Queued — spans
        # cross the submit -> worker thread hand-off ON these objects),
        # the current lifecycle phase span (prefill, then decode), and
        # accumulated fetch-block seconds from the steps this slot rode
        # during that phase.
        self.span = None
        self.phase_span = None
        self.fetch_s = 0.0
        # adaptive speculative decoding: per-slot draft budget and
        # acceptance-rate EWMA (None until the first measured ratio)
        self.spec_k_cur: Optional[int] = None
        self.spec_acc_ewma: Optional[float] = None


class _Queued:
    __slots__ = ("req", "response", "deadline", "t_submit",
                 "span", "qspan")

    def __init__(self, req, response, deadline, t_submit):
        self.req = req
        self.response = response
        self.deadline = deadline
        self.t_submit = t_submit
        self.span = None   # request span (hand-off to the worker)
        self.qspan = None  # its queue-wait child


class GenerationEngine:
    """Iteration-level (continuous-batching) generation service.

    Construct with a trained `scope` (weights under the training-graph
    names) and the model's configuration; the engine builds its own
    `max_slots`-wide programs whose STATE names carry `state_prefix`,
    so it can share the scope with training graphs or a serial batch=1
    decode graph without collision. Lifecycle mirrors `ServingEngine`:
    `start()` (state init + one warmup step an executable = all the
    compiles of the engine's lifetime), `submit`/`generate` from any
    thread, `stop(drain=True)`.
    """

    def __init__(self, cfg, scope, exe=None,
                 max_slots: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 queue_capacity: Optional[int] = None,
                 default_timeout_ms: Optional[float] = None,
                 state_prefix: str = "gen.",
                 paged: Optional[bool] = None,
                 block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 spec_decode: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_adaptive: Optional[bool] = None):
        import paddle_tpu as fluid
        from ..core.flags import FLAGS

        # `paged` is there for benchmark/families/*_serve.py, which pass
        # True, and goes with the `benchmark` issue that retires their
        # wrapper of `_run_paged` (PERF.md, Open question 11).
        if paged is not None and not paged:
            raise ValueError(
                "GenerationEngine(paged=False): the slab-KV engine was "
                "removed in PR 31; the engine serves paged KV only")
        # The model's configuration says how it is served: it builds
        # the paged programs (`build_paged_step`) and prices a token of
        # KV and a slot of recurrent state (`kv_token_bytes`,
        # `state_slot_bytes`): models/transformer.TransformerConfig,
        # models/hybrid.HybridConfig.
        self.cfg = cfg
        self.scope = scope
        self.exe = exe if exe is not None else fluid.Executor()
        self.max_slots = int(max_slots if max_slots is not None
                             else FLAGS.serving_max_batch_size)
        self.max_seq = int(max_seq if max_seq is not None
                           else cfg.max_seq_len)
        self.queue_capacity = int(queue_capacity
                                  if queue_capacity is not None
                                  else FLAGS.serving_queue_capacity)
        self.default_timeout_ms = (
            default_timeout_ms if default_timeout_ms is not None
            else FLAGS.serving_default_timeout_ms)
        # the decode-step program(s); their startup is never run (it
        # would re-init the shared trained weights) — state is seeded
        # by _ensure_decode_state in start()
        self._prog = fluid.Program()
        self._startup = fluid.Program()
        self.block_size = int(
            min(block_size if block_size is not None
                else FLAGS.gen_kv_block_size, self.max_seq))
        self.num_blocks = self._resolve_pool_blocks(kv_pool_blocks)
        # what every program of the engine is built with but its tokens
        # a row
        dims = dict(batch=self.max_slots, max_seq=self.max_seq,
                    block_size=self.block_size,
                    num_blocks=self.num_blocks, state_prefix=state_prefix)
        with fluid.program_guard(self._prog, self._startup):
            self.step = cfg.build_paged_step(seq_tokens=1, **dims)
            _pick_on_device(self.step, "decode")
        # the second (and last) executable of the lifetime: a row
        # retires one page of SOME request's prompt (`_prefill_plan`
        # says whose), so a step has room for max_slots pages
        self._prefill_prog = fluid.Program()
        self._prefill_startup = fluid.Program()
        with fluid.program_guard(self._prefill_prog,
                                 self._prefill_startup):
            self.prefill_step = cfg.build_paged_step(
                seq_tokens=self.block_size, with_logits=False, **dims)
        self._pool = BlockPool(self.num_blocks, self.block_size)
        self._prefix = PrefixCache(self._pool)
        # speculative decoding (serving/spec_decode.py): the verify
        # step is the THIRD and last fixed-shape executable, sharing
        # the decode/prefill programs' K/V pools via state_prefix.
        # Engines with spec off build nothing extra and keep the
        # two-executable warmup unchanged.
        self.spec_decode = bool(FLAGS.gen_spec_decode
                                if spec_decode is None else spec_decode)
        self.spec_k = int(spec_k if spec_k is not None
                          else FLAGS.spec_decode_k)
        self._spec_prog = None
        self.spec_step = None
        self._drafter = None
        # Recurrent layers (a model whose step names `state_names`):
        # their per-slot state moves forward only. A verify step would
        # advance it through drafts that are then rejected, and nothing
        # rolls it back; a cached KV block carries none of it.
        self.recurrent = bool(self.step.state_names)
        if self.recurrent and self.spec_decode and self.spec_k >= 1:
            raise ValueError(
                f"speculative decoding (spec_k={self.spec_k}) cannot "
                f"serve {type(cfg).__name__}: its recurrent layers' "
                "state cannot be rolled back past a rejected draft")
        if self.spec_decode and self.spec_k >= 1:
            from .spec_decode import NgramDrafter
            self._spec_prog = fluid.Program()
            self._spec_startup = fluid.Program()
            with fluid.program_guard(self._spec_prog,
                                     self._spec_startup):
                self.spec_step = cfg.build_paged_step(
                    seq_tokens=self.spec_k + 1, **dims)
                _pick_on_device(self.spec_step, "spec_verify")
            self._drafter = NgramDrafter(
                max_ngram=int(FLAGS.spec_decode_ngram), k=self.spec_k)
        else:
            self.spec_decode = False
        # acceptance-aware adaptive draft length: host-side only (the
        # verify executable is still [max_slots, spec_k+1]); a slot
        # whose measured acceptance stops paying for the verify premium
        # shrinks its own proposal budget toward 1
        self.spec_adaptive = bool(
            FLAGS.spec_decode_adaptive if spec_adaptive is None
            else spec_adaptive) and self.spec_decode
        self._slots = SlotManager(self.max_slots)
        self._state: List[Optional[_SlotState]] = \
            [None] * self.max_slots
        # serializes paged KV structures (BlockPool / PrefixCache / the
        # pool arrays themselves) between the worker's iteration and
        # cross-process export/adopt (serving/disagg.py)
        self._kv_mutex = threading.Lock()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: List[_Queued] = []
        self._closed = False
        self._draining = True
        self._worker: Optional[threading.Thread] = None
        self._ready = threading.Event()
        self._warm_misses: Optional[int] = None
        # the side-fetch of the last step, where the model has one, and
        # what the last decode (or verify) step fetched: `_pick_on_device`
        self._probe: Optional[np.ndarray] = None
        self._picks: Optional[np.ndarray] = None
        # resilience: a failed decode step fails the requests that were
        # mid-step (their KV state is unreplayable) but never the
        # worker; repeated failures trip the breaker and submissions
        # shed with OverloadedError
        self._breaker = CircuitBreaker(name="generation")
        self._step_retry = RetryPolicy(
            is_retryable=lambda e: isinstance(e, TransientFault))
        self._engine_state = "warming"  # warming -> ready -> stopped

    # -- paged-pool sizing ----------------------------------------------
    def kv_block_bytes(self) -> int:
        """HBM bytes one block occupies across every layer's K+V pool,
        as the model's configuration prices a token (`kv_token_bytes`:
        the layers that attend, their KV heads, the pool's type, in
        whole lane tiles)."""
        return self.block_size * self.cfg.kv_token_bytes()

    def state_bytes(self) -> int:
        """HBM bytes of per-slot recurrent state, every slot (0 for a
        model without recurrent layers): pinned beside the pools and
        priced the same way, as persistables of the programs."""
        return self.max_slots * self.cfg.state_slot_bytes()

    def kv_pool_bytes(self) -> int:
        """Total K/V pool HBM across layers — what the static memory
        planner prices for the engine's programs (pool persistables)."""
        return self.num_blocks * self.kv_block_bytes()

    def _resolve_pool_blocks(self, kv_pool_blocks) -> int:
        """Pool size precedence: ctor arg > FLAGS_gen_kv_pool_blocks >
        FLAGS_gen_kv_pool_bytes (budget // block_bytes) > full capacity
        (every slot can hold max_seq — no eviction pressure, but also
        no savings; production sets the budget)."""
        from ..core.flags import FLAGS
        per_slot = blocks_for_tokens(self.max_seq, self.block_size)
        if kv_pool_blocks is not None:
            # an explicit ctor arg is honored exactly (tests build
            # deliberately tight pools; submit reports requests that
            # can never fit) — only the BlockPool minimum applies
            return max(int(kv_pool_blocks), 2)
        if FLAGS.gen_kv_pool_blocks > 0:
            n = int(FLAGS.gen_kv_pool_blocks)
        elif FLAGS.gen_kv_pool_bytes > 0:
            # the budget is for all per-slot state: what the recurrent
            # layers pin comes off it before it is cut into blocks
            n = max(int(FLAGS.gen_kv_pool_bytes) - self.state_bytes(), 0) \
                // self.kv_block_bytes()
        else:
            n = self.max_slots * per_slot + 1
        # floor: scratch + one slot's worth, or nothing ever admits
        return max(n, per_slot + 1)

    # -- lifecycle -------------------------------------------------------
    def init_scope(self):
        """Run the decode program's startup to give the scope FRESH
        random weights. Only for scratch scopes (loadgen, smoke tests):
        on a scope holding trained parameters this would wipe them —
        trained deployments skip this and let `start()` seed just the
        decode state."""
        self.exe.run(self._startup, scope=self.scope)
        return self

    def executables(self):
        """The fixed-shape executables of the engine's lifetime, as
        (name, program, feed, fetch_var) with every slot muted: decode
        + chunk prefill (+ the spec verify step). `start()` warms
        exactly these; a caller can hand one to `exe.compiled(...)` to
        read its HLO or XLA's memory analysis, or run it again with
        other feed containers of the same shapes to check that nothing
        recompiles. `fetch_var` is the first of `fetch_list(prog)`: a
        step's picks (its probe row, for the prefill step)."""
        B = self.max_slots
        mb = self.step.max_blocks_per_slot
        return [(name, prog,
                 {step.token_var.name: np.zeros((B, t), np.int64),
                  step.table_var.name: np.zeros((B, mb), np.int64),
                  step.start_var.name: np.zeros(B, np.int64),
                  step.nvalid_var.name: np.zeros(B, np.int64)},
                 step.fetch_vars[0])
                for name, prog, step, t in self._paged_cells()]

    def _paged_cells(self):
        """(name, program, step handle, tokens a row) of the engine's
        executables."""
        cells = [("decode", self._prog, self.step, 1),
                 ("prefill", self._prefill_prog, self.prefill_step,
                  self.block_size)]
        if self.spec_step is not None:
            cells.append(("spec_verify", self._spec_prog, self.spec_step,
                          self.spec_k + 1))
        return cells

    def fetch_list(self, prog):
        """What a run of `prog` fetches: the step's picks (or probe
        row) and, in the same fetch, the few int32 a model with a
        `probe_var` counts in its decode step (`step.fetch_vars`).
        `executables()` names the first; a caller that compiles or
        re-runs an executable passes this list to get the one the
        engine runs."""
        return next(step.fetch_vars
                    for _, p, step, _ in self._paged_cells() if p is prog)

    def start(self):
        """Seed the decode state, run one warmup step per executable
        (`executables()` — ALL the compiles of the engine's lifetime,
        slots muted), then start the worker thread."""
        if self._worker is not None:
            return self
        from ..models import gpt
        blk = self._prog.global_block()
        gpt._ensure_decode_state(
            self.scope, blk,
            self.step.cache_names + self.step.state_names)
        for _, prog, feed, _ in self.executables():
            self.exe.run(prog, feed=feed, fetch_list=self.fetch_list(prog),
                         scope=self.scope)
        STAT_SET("serving.gen_kv_blocks_total", self._pool.capacity())
        STAT_SET("serving.gen_kv_blocks_free", self._pool.free_count())
        self._warm_misses = self.cache_stats()["misses"]
        # what lives now (weights' handles, compiled programs, a
        # caller's queue of requests with their token lists) lives for
        # as long as the engine serves: a full collection that walks it
        # again stops the loop for 0.1 s and more and frees nothing
        gc.collect()
        gc.freeze()
        self._closed = False
        self._worker = threading.Thread(target=self._worker_loop,
                                        name="ptn-generation-worker",
                                        daemon=True)
        self._worker.start()
        self._engine_state = "ready"
        self._ready.set()
        return self

    def stop(self, drain: bool = True,
             timeout: Optional[float] = 30.0):
        """Reject new submissions; drain=True finishes queued + active
        requests first, drain=False fails them with EngineClosedError."""
        self._ready.clear()
        self._engine_state = "stopped"
        with self._cond:
            self._closed = True
            self._draining = drain
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join(timeout)
            self._worker = None
        gc.unfreeze()

    @property
    def ready(self) -> bool:
        return self._ready.is_set()

    @property
    def breaker(self) -> CircuitBreaker:
        return self._breaker

    def health(self) -> dict:
        """Same shape as ServingEngine.health(): state warming / ready
        / degraded / open / stopped + breaker detail (for /healthz)."""
        if self._engine_state != "ready":
            return {"state": self._engine_state,
                    "breaker": self._breaker.state, "retry_after_s": 0.0}
        b = self._breaker.state
        state = {OPEN: "open", HALF_OPEN: "degraded",
                 CLOSED: "ready"}[b]
        return {"state": state, "breaker": b,
                "retry_after_s": self._breaker.retry_after_s()}

    def load(self) -> int:
        """Queued + active requests — what the router's least-loaded
        dispatch compares (the serving.gen_queue_depth /
        gen_active_slots gauges, read directly)."""
        with self._cond:
            queued = len(self._queue)
        return queued + self._slots.active_count()

    def cache_stats(self):
        """The executor's per-instance executable-cache counters; after
        `start()` the `misses` count must never move again — the
        zero-post-warmup-compile acceptance check
        (tools/serving_loadgen.py --generate --check-compiles)."""
        return self.exe.cache_stats()

    def kv_block_stats(self) -> dict:
        """Snapshot of the paged pool for reporting (loadgen records,
        sweep ledgers): capacity/free in blocks, the bytes the pool
        pins, and how many prefix-cache entries are resident."""
        return {"block_size": self.block_size,
                "blocks_total": self._pool.capacity(),
                "blocks_free": self._pool.free_count(),
                "prefix_entries": len(self._prefix),
                "pool_bytes": self.kv_pool_bytes()}

    def post_warmup_compiles(self) -> int:
        if self._warm_misses is None:
            return 0
        return self.cache_stats()["misses"] - self._warm_misses

    # -- request path ----------------------------------------------------
    def submit(self, req: GenerationRequest) -> _Response:
        """Enqueue; returns a future handle whose `.result()` blocks for
        ``{"tokens", "finish_reason", "ttft_ms", "e2e_ms", "queue_ms",
        "cached_tokens"}``; its `.timings` holds what is known of the
        request before it finishes (`queue_ms`, `cached_tokens`,
        `prefill_steps`, `ttft_ms`)."""
        need = len(req.prompt) + req.max_new_tokens - 1
        # block-aware admission: a request that can never fit is
        # rejected here; one that merely has to WAIT for blocks queues
        # and is admitted by the worker when the pool drains
        need_blocks = blocks_for_tokens(need, self.block_size)
        if need_blocks > self.step.max_blocks_per_slot:
            raise ValueError(
                f"request needs {need_blocks} KV blocks but a "
                f"slot's block table holds at most "
                f"{self.step.max_blocks_per_slot} "
                f"(max_seq={self.max_seq}, "
                f"block_size={self.block_size})")
        if need_blocks > self._pool.capacity():
            raise ValueError(
                f"request needs {need_blocks} KV blocks but the "
                f"engine's pool has only {self._pool.capacity()} "
                f"allocatable blocks "
                f"({self._pool.free_count()} free now)")
        timeout_ms = req.timeout_ms if req.timeout_ms is not None \
            else self.default_timeout_ms
        now = time.perf_counter()
        deadline = now + timeout_ms / 1e3 if timeout_ms else None
        if not self._breaker.allow():
            raise OverloadedError(
                "generation backend is unhealthy (circuit breaker "
                "open)", retry_after_s=self._breaker.retry_after_s())
        resp = _Response()
        q = _Queued(req, resp, deadline, now)
        if trace.enabled():
            # Child of the caller's span (http.request, loadgen's
            # per-request root) when one is current, else a new root.
            # Both begin at t_submit, where e2e, TTFT and queue_ms
            # begin: queue + prefill + decode then tile the request.
            q.span = trace.start_span(
                "gen.request", perf0=now,
                attrs={"prompt_tokens": len(req.prompt),
                       "max_new_tokens": req.max_new_tokens})
            resp.span = q.span
            q.qspan = trace.start_span("queue", parent=q.span,
                                       perf0=now)
        try:
            with self._cond:
                if self._closed:
                    raise EngineClosedError(
                        "generation engine is shut down")
                if len(self._queue) >= self.queue_capacity:
                    STAT_ADD("serving.gen_rejected")
                    raise QueueFullError(
                        f"generation queue at capacity "
                        f"({len(self._queue)}/{self.queue_capacity})")
                self._queue.append(q)
                STAT_ADD("serving.gen_requests")
                STAT_SET("serving.gen_queue_depth", len(self._queue))
                self._cond.notify_all()
        except ServingError as e:
            # Rejected before any worker saw it: the raise is the
            # completion (errored -> the tail rules keep the trace).
            trace.end_span(q.qspan, error=type(e).__name__)
            trace.complete_request(q.span,
                                   error=f"{type(e).__name__}: {e}")
            raise
        return resp

    def generate(self, prompt: Sequence[int], max_new_tokens: int,
                 **kw) -> dict:
        """Blocking submit+wait convenience."""
        return self.submit(GenerationRequest(
            prompt, max_new_tokens, **kw)).result()

    # -- decode step -----------------------------------------------------
    def _run_paged(self, prog, step, tokens, table, start, nvalid):
        """One run of an executable; returns its logits (the prefill
        step's probe row, a NumPy `[B]`). What a decode or verify step
        brings to the host is its picks (`_pick_on_device`: each
        position's arg-max and health number), left in `_picks` as a
        model's side-fetch is left in `_probe` for the iteration's
        record. The logits stay where the step wrote them: the return
        is `[B, T, V]` on the device, `out[i, j]` a row that `np.array`
        reads, then and after later steps have run, and that crosses to
        the host only when it is read."""
        out, *probe = self.exe.run(
            prog,
            feed={step.token_var.name: tokens,
                  step.table_var.name: table,
                  step.start_var.name: start,
                  step.nvalid_var.name: nvalid},
            fetch_list=step.fetch_vars,
            scope=self.scope)
        self._probe = np.asarray(probe[0]) if probe else None
        if step.logits_name is None:
            return np.asarray(out)
        self._picks = np.asarray(out)
        return _DeviceLogits(self.scope.find_var(step.logits_name),
                             self._picks[0])

    # -- KV-block bookkeeping (worker thread only) -----------------------
    def _alloc_block(self) -> Optional[int]:
        """Pool alloc with prefix-cache pressure relief: when the free
        list is empty, evict cold cached prefixes (LRU, only blocks no
        live slot references) until one frees."""
        bid = self._pool.alloc()
        while bid is None:
            if self._prefix.evict_lru() is None:
                return None
            bid = self._pool.alloc()
        return bid

    def _set_block_gauges(self):
        """Once an iteration from `_publish_iteration`, and after an
        export or adoption between iterations (serving/disagg.py)."""
        STAT_SET("serving.gen_kv_blocks_free", self._pool.free_count())

    def _adapt_spec_k(self, st: _SlotState, rate: float):
        """Fold one measured acceptance ratio into the slot's draft
        budget (spec_decode.update_spec_k). Gauge reflects the most
        recently adapted slot's budget."""
        from .spec_decode import update_spec_k
        from ..core.flags import FLAGS
        st.spec_k_cur, st.spec_acc_ewma, moved = update_spec_k(
            st.spec_k_cur, st.spec_acc_ewma, rate,
            k_max=self.spec_k, low=float(FLAGS.spec_adapt_low),
            high=float(FLAGS.spec_adapt_high))
        if moved < 0:
            STAT_ADD("serving.gen_spec_k_shrinks")
        elif moved > 0:
            STAT_ADD("serving.gen_spec_k_grows")
        STAT_SET("serving.gen_spec_k_effective", st.spec_k_cur)

    def _admitted(self, st: _SlotState, q: "_Queued"):
        """The request has a slot: its `timings` get the queue wait, and
        its span tree moves from queue to prefill (admission happens on
        the worker thread — the span rode the _Queued object across)."""
        st.response.timings.update(
            queue_ms=(time.perf_counter() - q.t_submit) * 1e3,
            cached_tokens=st.n_cached, prefill_steps=0)
        st.span = q.span
        trace.end_span(q.qspan)
        st.phase_span = trace.start_span("prefill", parent=st.span)

    def _admit_locked(self, rec) -> bool:
        """Move the queue head into a free slot, which also needs its
        blocks: shared prefix blocks come from the PrefixCache
        (refcounted, zero prefill cost), the rest are allocated upfront
        for the request's worst case — so a decode can never die
        mid-flight from pool exhaustion. Returns False (leaving the
        queue untouched) when the head cannot be placed yet."""
        q = self._queue[0]
        slot = self._slots.acquire()
        if slot is None:
            return False
        st = _SlotState(q.req, q.response, q.deadline, q.t_submit)
        prompt = q.req.prompt
        need = len(prompt) + q.req.max_new_tokens - 1
        # the last prompt position must stay writable (its KV is
        # written by this slot's first decode step), so the prefix
        # match is capped one token short of the prompt
        if self.recurrent:
            # a cached KV block carries no recurrent state: a slot
            # that skipped its tokens would decode from a state
            # that has not seen them. Nothing is adopted.
            n_cached, shared = 0, []
            rec.prefix_skipped_recurrent += 1
            STAT_ADD("serving.gen_prefix_skipped_recurrent")
        else:
            n_cached, shared = self._prefix.lookup(
                prompt, max_tokens=len(prompt) - 1)
        owned: List[int] = []
        missing = blocks_for_tokens(need, self.block_size) - len(shared)
        while len(owned) < missing:
            bid = self._alloc_block()
            if bid is None:
                break
            owned.append(bid)
        else:
            st.blocks = shared + owned
            st.n_cached = n_cached
            st.fed = n_cached
            st.cur = prompt[n_cached]
            STAT_ADD("serving.gen_prefix_hits" if n_cached
                     else "serving.gen_prefix_misses")
            self._admitted(st, q)
            if st.phase_span is not None and n_cached:
                st.phase_span.set_attr("cached_tokens", n_cached)
            self._state[slot] = st
            self._queue.pop(0)
            return True
        # not enough blocks: roll back and wait for releases
        for bid in owned + shared:
            self._pool.decref(bid)
        self._slots.release(slot)
        return False

    def _release_slot(self, i: int):
        """Retire slot i: 'reset' IS this — the blocks go back to the
        pool (or stay resident for the prefix cache / other slots
        holding refs); the graph never wipes anything."""
        st = self._state[i]
        if st is not None:
            for bid in st.blocks:
                self._pool.decref(bid)
            st.blocks = []
        self._state[i] = None
        self._slots.release(i)

    def _register_prefix(self, st: _SlotState):
        """After the first decode step, every full prompt block is
        immutable (all later writes land at positions past the prompt)
        — publish them to the prefix cache so the NEXT identical
        prefix skips its prefill."""
        bs = self.block_size
        n_full = len(st.req.prompt) // bs
        if n_full == 0 or self.recurrent:   # nothing would adopt them
            return
        hashes = self._prefix.chunk_hashes(st.req.prompt[:n_full * bs],
                                           bs)
        for j, h in enumerate(hashes):
            self._prefix.insert(h, st.blocks[j])

    # -- worker ----------------------------------------------------------
    def _expire_queued_locked(self, now) -> List[_Queued]:
        dead = [q for q in self._queue
                if q.deadline is not None and now >= q.deadline]
        if dead:
            self._queue = [q for q in self._queue if q not in dead]
        return dead

    def _close_phase(self, st: _SlotState):
        """End the slot's lifecycle phase span (prefill, then decode).
        Aggregated device-sync attribution: one synthetic "fetch" child
        of the phase carrying the summed fetch-block time of every step
        the slot rode DURING it (NESTED, so the queue+prefill+decode
        critical path doesn't double-count; by phase, so the child
        always fits inside its parent)."""
        if st.phase_span is not None and st.fetch_s > 0:
            trace.record_span(
                "fetch", st.phase_span.t_start,
                st.phase_span.t_start + st.fetch_s, st.phase_span,
                attrs={"aggregated": True,
                       "fetch_ms": round(st.fetch_s * 1e3, 3)})
        st.fetch_s = 0.0
        trace.end_span(st.phase_span)

    def _finish(self, st: _SlotState, reason: str):
        now = time.perf_counter()
        e2e_ms = (now - st.t_submit) * 1e3
        if st.span is not None:
            self._close_phase(st)
            st.span.attrs.update({
                "e2e_ms": round(e2e_ms, 3),
                "ttft_ms": None if st.ttft_ms is None
                else round(st.ttft_ms, 3),
                "tokens": len(st.generated),
                "finish_reason": reason,
                "cached_tokens": st.n_cached})
        st.response._complete({
            "tokens": list(st.generated),
            "finish_reason": reason,
            "ttft_ms": st.ttft_ms,
            "e2e_ms": e2e_ms,
            "queue_ms": st.response.timings.get("queue_ms"),
            "cached_tokens": st.n_cached,
        })
        if _monitor_on():
            STAT_OBSERVE("serving.gen_e2e_ms", e2e_ms,
                         buckets=MS_BUCKETS,
                         exemplar=st.span.trace_id if st.span else None)

    def _worker_loop(self):
        total = self._pool.capacity()
        alive = True
        while alive:
            rec = trace.begin_iteration(self.max_slots, self.block_size,
                                        total)
            with trace.region("gen.iteration"):
                alive = self._turn(rec)
                ran = self._publish_iteration(rec)
            trace.end_iteration(rec, keep=ran)
            if ran:
                _goodput.gen_busy(rec.t_end - rec.t_start)
            # no active slot = idle wait
            _goodput.gen_idle(rec.host_s.get("gen.idle_wait", 0.0))

    def _publish_iteration(self, rec) -> bool:
        """The loop's one bookkeeping site: what the live slots hold at
        the turn's end goes into its record, and the monitor's gauges
        that are fields of the record are published from it. Still
        under `gen.iteration`, so that on a profiler's trace no host
        time of the loop lies between two turns. Returns whether the
        turn ran a step (trace's ring keeps the record then)."""
        live = [st for st in self._state if st is not None]
        rec.kv_blocks_held = sum(len(st.blocks) for st in live)
        rec.kv_tokens_resident = sum(st.fed for st in live)
        STAT_SET("serving.gen_queue_depth", rec.queue_depth)
        STAT_SET("serving.gen_active_slots", rec.active_slots)
        self._set_block_gauges()
        if self.recurrent:
            rec.state_slots_live = len(live)
            rec.state_bytes = len(live) * self.cfg.state_slot_bytes()
            STAT_SET("serving.gen_state_slots_live", rec.state_slots_live)
            STAT_SET("serving.gen_state_bytes", rec.state_bytes)
            STAT_ADD("serving.gen_state_bytes_moved", rec.state_bytes_moved)
        if rec.kv_pages_read:
            rec.kv_bytes_read = rec.kv_pages_read * self.kv_block_bytes()
            STAT_ADD("serving.gen_kv_bytes_read", rec.kv_bytes_read)
        if rec.moe_selected:
            STAT_SET("serving.gen_moe_held_share",
                     rec.moe_selected_held / rec.moe_selected)
            STAT_SET("serving.gen_moe_experts_hit", rec.moe_experts_hit)
            STAT_SET("serving.gen_moe_load_max", rec.moe_load_max)
        if rec.prefill_tiles:
            STAT_ADD("serving.gen_prefill_tiles", rec.prefill_tiles)
        if rec.logit_rows_fetched:
            STAT_ADD("serving.gen_logit_rows_fetched",
                     rec.logit_rows_fetched)
        if rec.decode_rows and _monitor_on():
            STAT_OBSERVE("serving.gen_slot_occupancy",
                         rec.decode_rows / float(rec.slots),
                         buckets=FRACTION_BUCKETS)
        return rec.prefill_rows + rec.decode_rows > 0

    def _turn(self, rec) -> bool:
        """One turn of the worker loop: admission, then one iteration
        over the slots, or a wait where none is active. False ends the
        loop."""
        B = self.max_slots
        expired: List[_Queued] = []
        failed: List[_Queued] = []
        exit_loop = False
        with self._cond:
            with trace.region("gen.admit"):
                now = time.perf_counter()
                expired = self._expire_queued_locked(now)
                if self._closed and not self._draining:
                    failed = self._queue
                    self._queue = []
                # admit queued requests into free slots (iteration-level
                # scheduling: this runs BETWEEN decode steps, so a slot
                # and its KV blocks freed by the previous step are
                # reusable right now)
                while self._queue and self._slots.free_count() \
                        and self._admit_locked(rec):
                    pass
                active_idx = [i for i in range(B)
                              if self._state[i] is not None]
                rec.queue_depth = len(self._queue)
                rec.active_slots = len(active_idx)
            if not active_idx:
                if self._closed and not self._queue:
                    exit_loop = True
                elif not (self._closed and not self._draining):
                    with trace.region("gen.idle_wait"):
                        self._cond.wait(0.05)
        for q in expired:
            STAT_ADD("serving.gen_timeouts")
            trace.end_span(q.qspan, error="DeadlineExceededError")
            q.response._complete(error=DeadlineExceededError(
                "generation request waited past its deadline"))
        for q in failed:
            trace.end_span(q.qspan, error="EngineClosedError")
            q.response._complete(error=EngineClosedError(
                "generation engine shut down before the request "
                "ran"))
        if self._closed and not self._draining:
            # fail whatever is mid-decode and exit
            for i in range(B):
                st = self._state[i]
                if st is not None:
                    st.response._complete(error=EngineClosedError(
                        "generation engine shut down mid-decode"))
                    self._release_slot(i)
            return False
        if exit_loop:
            return False
        if not active_idx:
            return True
        # _kv_mutex: disagg export/adopt (serving/disagg.py) mutates
        # the same pools/PrefixCache between iterations
        with self._kv_mutex:
            self._paged_iteration(rec)
        return True

    def _emit(self, rec, st: _SlotState, tok: int, row, t_step: float):
        """Commit one sampled token of a slot: the iteration's and the
        request's counters, the first token's time and the span tree's
        prefill -> decode flip, then the request's hooks (`row` is the
        logits row the token was sampled from)."""
        st.generated.append(tok)
        rec.tokens_emitted += 1
        STAT_ADD("serving.gen_tokens")
        if len(st.generated) == 1:
            st.ttft_ms = (t_step - st.t_submit) * 1e3
            st.response.timings["ttft_ms"] = st.ttft_ms
            if _monitor_on():
                STAT_OBSERVE("serving.gen_ttft_ms", st.ttft_ms,
                             buckets=MS_BUCKETS)
            if st.span is not None:
                # prefill -> decode phase flip at first token
                self._close_phase(st)
                st.phase_span = trace.start_span(
                    "decode", parent=st.span)
            if not st.registered:
                # the whole prompt (every full block of it) is now
                # resident and immutable — shareable from here on
                self._register_prefix(st)
                st.registered = True
        elif _monitor_on() and st.t_prev_token is not None:
            STAT_OBSERVE("serving.gen_inter_token_ms",
                         (t_step - st.t_prev_token) * 1e3,
                         buckets=MS_BUCKETS)
        st.t_prev_token = t_step
        if st.req.logits_cb is not None:
            st.req.logits_cb(np.asarray(row))
        if st.req.stream_cb is not None:
            st.req.stream_cb(tok)
            if st.phase_span is not None:
                st.phase_span.add_event(
                    "stream_flush", token_index=len(st.generated))

    def _prefill_plan(self, prefill_idx, queue_depth):
        """How one prefill step spends the executable's rows, as
        `(slot, first row, rows)` for every request it advances. A row
        is a TILE: up to one page of one request's prompt, fed with
        that request's block-table row, its own `start_pos` and
        `n_valid`. Nothing in the programs ties row i to slot i, and
        `paged_attention` writes every row's keys before any row reads,
        so successive pages of ONE request may ride in one step: the
        later tile reads from the pool what the earlier tile wrote in
        that step (ops/attention.py). The rows go to the oldest request
        first, as many as it has pages left, then to the next, until
        the step's rows are spent: a long prompt is a few steps, not a
        step a page, and the rest of the rows stay muted. The step has
        all `max_slots` rows to spend, or `PREFILL_ROWS_WHILE_DECODING`
        of them while a slot decodes and no request waits in the queue.

        A model with per-slot recurrent state (`self.recurrent`: the
        step the engine built names `state_names`) keeps row i = slot
        i and one tile a request a step: its mixers carry row b's state
        in row b of the state variable, and a request's tiles would
        have to be scanned in order, not side by side."""
        if self.recurrent:
            return [(i, i, 1) for i in prefill_idx]
        rows = self.max_slots
        if queue_depth == 0 \
                and len(prefill_idx) < self._slots.active_count():
            rows = max(1, int(rows * PREFILL_ROWS_WHILE_DECODING))
        plan, row = [], 0
        for i in sorted(prefill_idx,
                        key=lambda i: self._state[i].t_submit):
            st = self._state[i]
            k = min(blocks_for_tokens(len(st.req.prompt) - 1 - st.fed,
                                      self.block_size),
                    rows - row)
            if k == 0:
                break
            plan.append((i, row, k))
            row += k
        return plan

    # -- one iteration ---------------------------------------------------
    def _paged_iteration(self, rec):
        """One scheduler iteration: (1) chunked prefill: the prefill
        executable's rows go, a page a row, to the requests still
        consuming their prompts, oldest first and as many pages of one
        request as it has left (`_prefill_plan`); (2) one decode step
        for every slot past its prompt. Both run the same two warmed
        executables every time (fixed shapes; muted rows write to the
        scratch block), so admission, tile packing, release and prefix
        reuse never cost a compile, and a long prompt delays the decode
        batch by one prefill step a turn, of at most the shape's rows
        (`PREFILL_ROWS_WHILE_DECODING` of them while that matters)."""
        from ..core.flags import FLAGS
        B = self.max_slots
        bs = self.block_size
        mb = self.step.max_blocks_per_slot
        now = time.perf_counter()
        for i in range(B):
            st = self._state[i]
            if st is not None and st.deadline is not None \
                    and now >= st.deadline:
                STAT_ADD("serving.gen_timeouts")
                st.response._complete(error=DeadlineExceededError(
                    "generation deadline passed mid-decode"))
                self._release_slot(i)

        def fill_row(arr_table, arr_start, i, st):
            arr_table[i, :len(st.blocks)] = st.blocks
            arr_start[i] = st.fed

        def run_guarded(prog, step, tokens, table, start, nvalid,
                        idx, what, site="generation"):
            """Shared failure envelope: injector pre-step faults retry
            (RetryPolicy), anything after the real dispatch fails the
            involved slots — KV already advanced, a replay would
            double-write. Returns the fetch or None. `site` names the
            fault-injection hook (prefill chunks get their own,
            "gen_prefill", so drills can slow prefill without touching
            decode — the disagg loadgen's machine-independent
            service-time knob)."""
            def _attempt():
                inj = _fault_injector()
                if inj is not None:
                    inj.pre_step(site)
                return self._run_paged(prog, step, tokens, table,
                                       start, nvalid)
            try:
                out = self._step_retry.call(_attempt)
            except Exception as e:  # noqa: BLE001 — worker must survive
                if is_transient(e):
                    self._breaker.record_failure()
                STAT_ADD("resilience.gen_step_failures")
                for i in idx:
                    st = self._state[i]
                    st.response._complete(error=RuntimeError(
                        f"{what} step failed: {e!r}"))
                    self._release_slot(i)
                return None
            self._breaker.record_success()
            # what the step read of the pool, by the rule the kernel
            # follows: a row the pages its length start + n_valid
            # covers, a muted row none
            rec.kv_pages_read += sum(
                blocks_for_tokens(s + n, bs)
                for s, n in zip(start.tolist(), nvalid.tolist()) if n)
            rec.kv_pages_table += table.size
            if self.recurrent:
                # the rows fed had their recurrent state read and
                # written, once each: the algorithm's count
                rec.state_bytes_moved += 2 * int(np.count_nonzero(nvalid)) \
                    * self.cfg.state_slot_bytes()
            if self._probe is not None:
                # a row an expert layer: selections made, those on held
                # experts, held experts hit, the busiest one's tokens
                sel, held, hit, busiest = self._probe.sum(axis=0).tolist()
                rec.moe_selected += sel
                rec.moe_selected_held += held
                rec.moe_experts_hit += hit
                rec.moe_load_max += busiest
            if trace.enabled():
                lt = self.exe.last_step_timings
                if lt is not None:
                    for i in idx:
                        st = self._state[i]
                        if st is not None:
                            st.fetch_s += lt["fetch_s"]
            return out

        # ---- phase 1: chunked prefill ---------------------------------
        prefill_idx = [
            i for i in range(B) if self._state[i] is not None
            and self._state[i].fed < len(self._state[i].req.prompt) - 1]
        if prefill_idx:
            with trace.region("gen.prefill.stage"):
                tokens = np.zeros((B, bs), np.int64)
                table = np.zeros((B, mb), np.int64)
                start = np.zeros(B, np.int64)
                nvalid = np.zeros(B, np.int64)
                plan = self._prefill_plan(prefill_idx, rec.queue_depth)
                chunk_n = {}
                for i, r0, k in plan:
                    # rows r0 .. r0 + k are successive pages of slot i's
                    # prompt: each has the request's table and its own
                    # start, and the last may be partial
                    st = self._state[i]
                    tiles = slice(r0, r0 + k)
                    chunk = st.req.prompt[
                        st.fed:min(st.fed + k * bs, len(st.req.prompt) - 1)]
                    tokens[tiles].reshape(-1)[:len(chunk)] = chunk
                    table[tiles, :len(st.blocks)] = st.blocks
                    offs = bs * np.arange(k)
                    start[tiles] = st.fed + offs
                    nvalid[tiles] = np.minimum(bs, len(chunk) - offs)
                    chunk_n[i] = len(chunk)
                prefill_idx = list(chunk_n)
            rec.prefill_rows = len(plan)
            rec.prefill_tiles = sum(k for _, _, k in plan)
            with trace.region("gen.prefill.step"):
                probe = run_guarded(self._prefill_prog,
                                    self.prefill_step, tokens, table,
                                    start, nvalid, prefill_idx,
                                    "prefill", site="gen_prefill")
            if probe is None:
                return
            if FLAGS.serving_nan_guard:
                # the probe is a number a row: a request fails when any
                # of its tiles reads non-finite
                bad = [i for i, r0, k in plan
                       if not np.isfinite(probe[r0:r0 + k]).all()]
                if bad:
                    self._breaker.record_failure()
                    STAT_ADD("resilience.gen_step_failures")
                    for i in bad:
                        st = self._state[i]
                        st.response._complete(error=RuntimeError(
                            "non-finite activations in chunked prefill "
                            "(cannot replay a stateful step)"))
                        self._release_slot(i)
                    prefill_idx = [i for i in prefill_idx
                                   if i not in bad]
                    rec.prefill_rows = len(prefill_idx)
            for i in prefill_idx:
                st = self._state[i]
                st.fed += chunk_n[i]
                st.cur = st.req.prompt[st.fed]
                rec.prefill_tokens += chunk_n[i]
                st.response.timings["prefill_steps"] += 1
                STAT_ADD("serving.gen_chunked_prefills")
                if st.phase_span is not None:
                    st.phase_span.add_event("prefill_chunk",
                                            tokens=chunk_n[i])

        # ---- phase 2: one decode (or spec verify) step ----------------
        decode_idx = [
            i for i in range(B) if self._state[i] is not None
            and self._state[i].fed >=
            len(self._state[i].req.prompt) - 1]
        if not decode_idx:
            return
        # speculative drafts (serving/spec_decode.py): host-side n-gram
        # lookup over each opted-in slot's prompt + generated tokens.
        # Any non-empty draft routes the WHOLE batch through the verify
        # executable — a draft-less row rides with n_valid=1, which is
        # semantically the decode step — while an all-empty round takes
        # the cheaper 1-token decode executable. Both were compiled in
        # start(), so the per-iteration choice never costs a compile.
        drafts = {}
        if self._drafter is not None:
            with trace.region("gen.decode.draft"):
                for i in decode_idx:
                    st = self._state[i]
                    if st.req.spec_decode is False:
                        continue
                    # cap drafts to the blocks admission reserved
                    # (need-1 is the slot's last writable position) and
                    # to the request's remaining token budget (the
                    # verify row already emits one token beyond the
                    # accepted drafts)
                    need = len(st.req.prompt) + \
                        st.req.max_new_tokens - 1
                    if st.spec_k_cur is None:
                        st.spec_k_cur = self.spec_k
                    k_slot = st.spec_k_cur if self.spec_adaptive \
                        else self.spec_k
                    cap = min(k_slot, need - 1 - st.fed,
                              st.req.max_new_tokens
                              - len(st.generated) - 1)
                    if cap < 1:
                        continue
                    d = self._drafter.draft(
                        st.req.prompt + st.generated, cap)
                    if d:
                        drafts[i] = d
        use_spec = bool(drafts)
        prog = self._spec_prog if use_spec else self._prog
        step = self.spec_step if use_spec else self.step
        T = self.spec_k + 1 if use_spec else 1
        with trace.region("gen.decode.stage"):
            tokens = np.zeros((B, T), np.int64)
            table = np.zeros((B, mb), np.int64)
            start = np.zeros(B, np.int64)
            nvalid = np.zeros(B, np.int64)
            n_draft = {}
            for i in decode_idx:
                st = self._state[i]
                d = drafts.get(i, ())
                n_draft[i] = len(d)
                tokens[i, 0] = st.cur
                if d:
                    tokens[i, 1:1 + len(d)] = d
                fill_row(table, start, i, st)
                nvalid[i] = 1 + len(d)
        rec.decode_rows = len(decode_idx)
        with trace.region("gen.decode.step"):
            logits = run_guarded(prog, step, tokens, table, start,
                                 nvalid, decode_idx,
                                 "spec verify" if use_spec else "decode")
        if logits is None:
            return
        with trace.region("gen.sample"):
            self._sample_paged(rec, logits, tokens, n_draft, decode_idx,
                               use_spec)
            # a NumPy array in the logits' place was fetched whole by
            # whoever returned it, and is not counted
            rec.logit_rows_fetched += getattr(logits, "rows_read", 0)

    def _sample_paged(self, rec, logits, tokens, n_draft, decode_idx,
                      use_spec):
        """What the host does after a decode (or verify) step: the
        finiteness guard over the step's fetched health numbers, then
        for every slot sampling (or draft acceptance), the request's
        hooks, and the finish, release and prefix registration that
        follow. `logits` is `_run_paged`'s return, on the device: a row
        is handed to `sampling.sample_token` as it lies there, and is
        read only by what asks for its numbers, a temperature or a
        `logits_cb`. A NumPy `[B, T, V]` in its place is served from as
        it is."""
        from ..core.flags import FLAGS
        from ..models import sampling
        health = self._picks[1]
        inj = _fault_injector()
        if inj is not None:
            arrs = [health]
            if inj.corrupt_fetches("generation", arrs):
                health = arrs[0]
        if FLAGS.serving_nan_guard:
            finite = np.isfinite(health)
            bad = [] if finite.all() else [
                i for i in decode_idx
                if not finite[i, :1 + n_draft[i]].all()]
            if bad:
                self._breaker.record_failure()
                STAT_ADD("resilience.gen_step_failures")
                for i in bad:
                    st = self._state[i]
                    st.response._complete(error=RuntimeError(
                        "non-finite logits (cannot replay a stateful "
                        "decode step)"))
                    self._release_slot(i)
                decode_idx = [i for i in decode_idx if i not in bad]
                rec.decode_rows = len(decode_idx)
                if not decode_idx:
                    return
        STAT_ADD("serving.gen_steps")
        if use_spec:
            STAT_ADD("serving.gen_spec_steps")

        t_step = time.perf_counter()
        for i in decode_idx:
            st = self._state[i]
            nd = n_draft[i]
            if nd:
                STAT_ADD("serving.gen_spec_draft_proposed", nd)
                # verify row j's logits condition on exactly the tokens
                # a serial decode would have fed; accept_draft draws
                # through the same sample_token path with the slot's
                # rng, so emitted tokens are bit-identical to serial
                # decode at any temperature (models/sampling.py)
                emitted, n_acc = sampling.accept_draft(
                    logits[i, :nd + 1], tokens[i, 1:1 + nd],
                    temperature=st.req.temperature,
                    top_k=st.req.top_k, rng=st.rng)
                STAT_ADD("serving.gen_spec_draft_accepted", n_acc)
                if _monitor_on():
                    STAT_OBSERVE("serving.gen_spec_acceptance_rate",
                                 n_acc / nd, buckets=FRACTION_BUCKETS)
                    STAT_OBSERVE("serving.gen_spec_tokens_per_step",
                                 len(emitted),
                                 buckets=SPEC_TOKEN_BUCKETS)
                # the committed token + accepted drafts are now valid
                # KV; writes past fed (rejected tail) sit beyond the
                # cursor and are rewritten before any mask reads them
                st.fed += 1 + n_acc
                if self.spec_adaptive:
                    self._adapt_spec_k(st, n_acc / nd)
            else:
                emitted = [sampling.sample_token(
                    logits[i, 0], temperature=st.req.temperature,
                    top_k=st.req.top_k, rng=st.rng)]
                st.fed += 1
            finished = False
            for j, tok in enumerate(emitted):
                # emitted[j] was drawn from row j (accept_draft)
                self._emit(rec, st, tok, logits[i, j], t_step)
                done_eos = (st.req.eos_id is not None
                            and tok == st.req.eos_id)
                if done_eos or len(st.generated) >= \
                        st.req.max_new_tokens:
                    self._finish(st, "eos" if done_eos else "length")
                    self._release_slot(i)
                    finished = True
                    break
            if not finished:
                st.cur = emitted[-1]
