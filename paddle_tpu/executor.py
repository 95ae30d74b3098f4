"""Executor: compile-and-run a Program on a Place.

Reference analogue: fluid.Executor (executor.py:672) -> C++ Executor::Run
(executor.cc:192), which interprets ops one-by-one. Here Executor.run lowers
the whole requested (feed, fetch) slice of the program to ONE jitted XLA
computation, caches the executable keyed by (program fingerprint, feed
shapes/dtypes, fetch names) — the TPU answer to the reference's per-program
`Prepare` cache (executor.py:_run_impl program cache) — and donates the
persistable state dict so parameter updates reuse buffers in place.

Feed/fetch semantics match the reference: feed is {name: ndarray}, fetch_list
is vars/names, results come back as numpy by default.
"""
from __future__ import annotations

import time
import weakref
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import goodput as _goodput
from . import trace as _trace
from .core import flags as _flags
from .core.dtypes import as_np_dtype
from .core.lowering import LowerCtx, lower_block
from .core.place import Place, default_place
from .core.scope import Scope, global_scope
from .framework import Program, Variable
from .monitor import STAT_ADD, STAT_OBSERVE, STAT_SET
from .monitor import enabled as _monitor_on
from .monitor import flight_step as _flight_step

__all__ = ["Executor", "global_scope", "scope_guard"]

from .core.scope import scope_guard  # re-export  # noqa: E402


def _as_device_array(v):
    return v if isinstance(v, jax.Array) else jnp.asarray(v)


def _not_initialised(name):
    return RuntimeError(
        f"persistable var {name!r} is not initialised — run the "
        f"startup program first")


class _CompiledStep:
    def __init__(self, fn, state_in_names, state_out_names, fetch_names,
                 donate_names):
        self.fn = fn
        self.state_in_names = state_in_names
        self.state_out_names = state_out_names
        self.fetch_names = fetch_names
        # the state inputs the jit donates, gets back and the scope is
        # written with: what some op of the program produces (the
        # donation planner's narrower set under FLAGS_graph_opt_level=2;
        # every state input of a CompiledProgram). The rest, the weights
        # of an inference step, is passed pinned: not donated, not
        # returned, gathered once by a bound step.
        self.donate_names = donate_names
        self.donated_names = tuple(n for n in state_in_names
                                   if n in donate_names)
        self.pinned_names = tuple(n for n in state_in_names
                                  if n not in donate_names)
        # run count: the first call pays XLA compile (jit is lazy), so
        # the monitor attributes it separately from steady-state steps
        self.runs = 0


class _SplitStateStep:
    """The (state, feeds, step) call surface over a jit that takes
    (donated_state, pinned_state, feeds, step) with donate_argnums=(0,).
    `split_call` is that jit itself, for a caller that already holds
    the two halves (Executor.run)."""

    def __init__(self, jit_fn, donate_names):
        self.split_call = jit_fn
        self._donate = donate_names

    def split(self, state):
        donated = {n: v for n, v in state.items() if n in self._donate}
        pinned = {n: v for n, v in state.items()
                  if n not in self._donate}
        return donated, pinned

    def __call__(self, state, feeds, step_idx):
        donated, pinned = self.split(state)
        return self.split_call(donated, pinned, feeds, step_idx)

    def lower(self, state, feeds, step_idx):
        donated, pinned = self.split(state)
        return self.split_call.lower(donated, pinned, feeds, step_idx)


class _BoundStep:
    """What one call signature of Executor.run resolved to, kept for as
    long as nothing it rests on has moved: the Program object and the
    very fingerprint string it cached (every mutation site resets
    `_fp_cache`, so identity is the program's stamp), the
    CompiledProgram, the Scope, the fetch names, the feeds' (name,
    kind, shape, dtype) in the caller's order, and the flags'
    generation. A call that matches stages its feeds by `feed_plan`,
    takes the pinned state from the scope's view and the donated state
    from the scope, and runs `step_fn`: no scan, no gates, no key."""

    __slots__ = ("program", "fp", "compiled", "scope_ref", "flags_gen",
                 "step_fn", "cache_key", "feed_plan", "sharded")

    def __init__(self, program, fp, compiled, scope, flags_gen, step_fn,
                 cache_key, feed_plan):
        self.program = program
        self.fp = fp
        self.compiled = compiled
        # weak: a binding must not keep a scope's arrays alive
        self.scope_ref = weakref.ref(scope)
        self.flags_gen = flags_gen
        self.step_fn = step_fn
        self.cache_key = cache_key
        # per fed name, in the caller's order: (name, dtype to cast to
        # or None, sharding to place on or None)
        self.feed_plan = feed_plan
        # counted a call, as _resolve_step counts it
        self.sharded = compiled is not None \
            and compiled._is_data_parallel \
            and compiled._state_spec_fn is not None


class Executor:
    def __init__(self, place: Optional[Place] = None):
        from collections import OrderedDict
        self.place = place or default_place()
        # LRU-ordered: bounded by FLAGS_executor_cache_capacity so
        # long-running sessions that rebuild programs don't accumulate
        # executables forever.
        self._cache: "OrderedDict[tuple, _CompiledStep]" = OrderedDict()
        self._step_counters: Dict[str, int] = {}
        self._last_cache_hit = False
        self._last_key = None
        # per-instance mirror of the global compile-cache counters: the
        # serving engine's warmup contract ("zero post-warmup compiles")
        # is about THIS executor, not every executor in the process
        self._cache_hits = 0
        self._cache_misses = 0
        # Strong refs to CompiledPrograms in the cache: keys use
        # id(compiled), which is only stable while the object is alive.
        self._compiled_refs: Dict[int, object] = {}
        # Sub-step timing of the most recent run() (feed staging /
        # dispatch / fetch-block, seconds). The generation engine reads
        # this after each step to attribute fetch time to the request
        # spans of the slots in flight.
        self.last_step_timings: Optional[Dict[str, float]] = None
        self._last_feed_s = 0.0
        self._last_build_s = 0.0
        # Bound steps (_BoundStep), LRU like the cache, keyed by the
        # objects and the signature a call arrives with. `_bind_steps`
        # is private: a test sets it False to send every call down
        # _resolve_step.
        self._bound: "OrderedDict[tuple, _BoundStep]" = OrderedDict()
        self._bind_steps = True
        self._bound_hits = 0
        self._bound_binds = 0
        self._bound_rebinds = 0

    # ------------------------------------------------------------------
    def run(self, program: Optional[Program] = None, feed=None,
            fetch_list=None, scope: Optional[Scope] = None,
            return_numpy=True, use_program_cache=True):
        from .compiler import CompiledProgram  # local: avoid cycle

        if program is None:
            from .framework import default_main_program
            program = default_main_program()

        compiled = None
        if isinstance(program, CompiledProgram):
            compiled = program
            program = compiled.program

        scope = scope or global_scope()
        if feed is None:
            feed = {}

        t_run0 = time.perf_counter()
        self._last_feed_s = 0.0
        self._last_build_s = 0.0
        may_bind = use_program_cache and self._bind_steps
        bound = self._find_bound(program, compiled, scope, feed,
                                 fetch_list) if may_bind else None
        if bound is not None:
            step_fn, fp = bound.step_fn, bound.fp
            with _trace.region("executor.resolve"):
                donated, pinned, feed_arrays = self._stage_bound(
                    bound, scope, feed)
        else:
            # A listen_and_serv program IS the parameter-server loop:
            # block in the host-side runtime instead of lowering (the
            # reference's exe.run(pserver_prog) does the same,
            # listen_and_serv_op.cc). Such a program is never bound.
            if any(op.type in ("listen_and_serv", "fl_listen_and_serv")
                   for op in program.global_block().ops):
                from .distributed.ps_server import run_pserver
                run_pserver(program, scope=scope)
                return []

            t_run0 = time.perf_counter()
            with _trace.region("executor.resolve"):
                step_fn, state, feed_arrays = self._resolve_step(
                    program, feed, fetch_list, scope, compiled,
                    use_program_cache)
                donated, pinned = step_fn.fn.split(state)
                fp = program.fingerprint()
                if may_bind:
                    self._bind(program, fp, compiled, scope, feed,
                               fetch_list, feed_arrays, step_fn)

        step = self._step_counters.get(fp, 0)
        self._step_counters[fp] = step + 1
        # a host scalar: jnp.uint32(step) is a device computation of
        # its own (a jit_convert_element_type run before every step);
        # fold_in sees the same uint32 either way
        step_idx = np.uint32(step)

        first_run = step_fn.runs == 0
        step_fn.runs += 1

        # Goodput ledger (FLAGS_enable_goodput): retry backoff inside the
        # dispatch span is attributed directly by RetryPolicy, so snapshot
        # the counter here and subtract the delta from dispatch time to
        # keep the ledger's categories exclusive.
        _gled = _goodput.active()
        _bk0 = (_gled.category_seconds("retry_backoff")
                if _gled is not None else 0.0)

        t_disp0 = time.perf_counter()
        with _trace.region("executor.dispatch"):
            # Fault injection (FLAGS_fault_spec; paddle_tpu/resilience).
            # Empty spec = one cached None-check. An injected
            # TransientFault fires BEFORE device dispatch, so retrying
            # here is donation-safe (the scope still holds valid
            # pre-step buffers); real dispatch errors are NOT retried at
            # this level — a failed dispatch may have invalidated
            # donated state.
            from .resilience.faults import injector as _fault_injector
            inj = _fault_injector()
            if inj is None:
                with jax.default_device(self.place.jax_device()):
                    fetches, new_state = step_fn.fn.split_call(
                        donated, pinned, feed_arrays, step_idx)
            else:
                from .resilience.faults import TransientFault
                from .resilience.retry import RetryPolicy

                def _dispatch():
                    inj.pre_step("executor", step=step)
                    with jax.default_device(self.place.jax_device()):
                        return step_fn.fn.split_call(
                            donated, pinned, feed_arrays, step_idx)

                policy = RetryPolicy(is_retryable=lambda e: isinstance(
                    e, TransientFault))
                fetches, new_state = policy.call(_dispatch)

            for n, val in new_state.items():
                scope.set(n, val)

        t_fetch0 = time.perf_counter()
        with _trace.region("executor.fetch"):
            if return_numpy:
                out = [np.asarray(f) for f in fetches]
                if inj is not None:
                    # step_nan corrupts only these host-side copies —
                    # the device state written back above stays clean,
                    # so a caller-level re-run of the same step is a
                    # valid cure
                    inj.corrupt_fetches("executor", out)
            else:
                out = list(fetches)
        now = time.perf_counter()
        self.last_step_timings = {
            "feed_s": self._last_feed_s,
            "dispatch_s": t_fetch0 - t_disp0,
            "fetch_s": now - t_fetch0,
            "total_s": now - t_run0,
        }
        if _gled is not None:
            _gled.note_step(
                feed_s=self._last_feed_s,
                dispatch_s=t_fetch0 - t_disp0,
                fetch_s=now - t_fetch0,
                total_s=now - t_run0,
                build_s=self._last_build_s,
                first_run=first_run,
                backoff_s=_gled.category_seconds("retry_backoff") - _bk0)
        if _monitor_on():
            tid = _trace.current_trace_id()
            # fetch/block time: device sync happens in np.asarray; with
            # return_numpy=False dispatch is async and this measures ~0
            STAT_OBSERVE("executor.fetch_block_seconds", now - t_fetch0,
                         exemplar=tid)
            STAT_OBSERVE("executor.step_seconds", now - t_run0,
                         exemplar=tid)
            if first_run:
                # lazy-jit compile is paid here: first-call wall time is
                # the compile + first-execute cost (amortization input
                # for tools/metrics_report.py)
                STAT_OBSERVE("executor.compile_first_step_seconds",
                             now - t_run0, exemplar=tid)
            from .core.memory import record_device_memory
            record_device_memory(self.place.jax_device())
        # flight recorder (FLAGS_flight_recorder): one bounded-ring
        # record per completed step — the post-mortem trail dumped on
        # crash/SIGTERM (monitor.dump_flight_recorder)
        _flight_step(step=step, program=fp[:12],
                     cache_hit=self._last_cache_hit,
                     first_run=first_run,
                     step_seconds=round(now - t_run0, 6),
                     fetch_block_seconds=round(now - t_fetch0, 6),
                     fetches=len(step_fn.fetch_names))
        return out

    # ------------------------------------------------------------------
    # The bound step: what a call signature resolved to, kept.
    @staticmethod
    def _bound_key(program, compiled, scope, feed, fetch_list):
        """The key a call arrives with, or None for a call that is
        never bound: a feed that is neither a plain ndarray nor a
        jax.Array (a LoDTensor, a list: _prepare_feed's business)."""
        if type(feed) is not dict:
            return None
        sig = []
        for name, val in feed.items():
            if type(val) is np.ndarray:
                kind = 0
            elif isinstance(val, jax.Array):
                kind = 1
            else:
                return None
            sig.append((name, kind, val.shape, val.dtype))
        fetch_names = tuple(v.name if isinstance(v, Variable) else str(v)
                            for v in (fetch_list or ()))
        return (id(program), id(compiled), id(scope), fetch_names,
                tuple(sig))

    def _find_bound(self, program, compiled, scope, feed, fetch_list):
        key = self._bound_key(program, compiled, scope, feed, fetch_list)
        b = self._bound.get(key) if key is not None else None
        if b is None:
            return None
        # ids are only unique among live objects: the binding holds the
        # program and the CompiledProgram, and checks the scope it only
        # weakly refers to. `_fp_cache is b.fp`: no mutation site has
        # reset the fingerprint since; the generation: no flag was
        # assigned since (the gates, trace_signature(), the fault spec).
        if (b.scope_ref() is not scope
                or program._fp_cache is not b.fp
                or _flags.generation() != b.flags_gen
                or scope.parent is not None or program.lod_link
                or b.cache_key not in self._cache):
            del self._bound[key]
            return None
        self._bound.move_to_end(key)
        self._cache.move_to_end(b.cache_key)  # LRU touch, as a hit's
        self._last_cache_hit = True
        self._cache_hits += 1
        self._bound_hits += 1
        STAT_ADD("executor.compile_cache_hit")
        STAT_ADD("executor.bound_step_hits")
        if b.sharded:
            STAT_ADD("parallel.sharded_steps")
        return b

    def _bind(self, program, fp, compiled, scope, feed, fetch_list,
              feed_arrays, step_fn):
        """Keep what this call resolved to, if every later call of its
        signature can be vouched for by _find_bound's tests."""
        from .resilience.faults import injector as _fault_injector
        key = self._bound_key(program, compiled, scope, feed, fetch_list)
        if (key is None or scope.parent is not None or program.lod_link
                or list(feed_arrays) != list(feed)
                or _fault_injector() is not None):
            return
        plan = []
        for name, val in feed.items():
            staged = feed_arrays[name]
            cast = staged.dtype if staged.dtype != val.dtype else None
            ns = compiled.feed_sharding(val.shape) \
                if compiled is not None else None
            plan.append((name, cast, ns))
        cache_key = self._last_key
        if self._cache.get(cache_key) is not step_fn:
            return
        self._pin_state(step_fn, scope)
        self._bound[key] = _BoundStep(
            program, fp, compiled, scope, _flags.generation(), step_fn,
            cache_key, plan)
        self._bound_binds += 1
        STAT_ADD("executor.bound_step_binds")
        cap = _flags.FLAGS.executor_cache_capacity
        while cap > 0 and len(self._bound) > cap:
            self._bound.popitem(last=False)

    @staticmethod
    def _pin_state(step_fn, scope):
        try:
            return scope.pin(step_fn, step_fn.pinned_names,
                             _as_device_array)
        except KeyError as e:
            raise _not_initialised(e.args[0]) from None

    def _stage_bound(self, bound, scope, feed):
        """A bound call's resolve: stage the feeds by the plan, take the
        pinned state from the scope's view (gathered again only when a
        pinned name was written: a rebind, no recompilation) and the
        donated state from the scope."""
        step_fn = bound.step_fn
        with _trace.region("executor.feed"):
            t0 = time.perf_counter()
            feed_arrays = {}
            presharded = 0
            for (name, cast, ns), val in zip(bound.feed_plan,
                                             feed.values()):
                staged = cast is not None
                if staged:
                    val = val.astype(cast)
                if ns is not None and not (
                        isinstance(val, jax.Array)
                        and val.sharding.is_equivalent_to(ns, val.ndim)):
                    val = jax.device_put(val, ns)
                    staged = True
                if not staged and isinstance(val, jax.Array):
                    presharded += 1
                feed_arrays[name] = val
            self._last_feed_s = time.perf_counter() - t0
            if _monitor_on():
                self._note_feed(feed_arrays, presharded)
        pinned = scope.pinned_view(step_fn)
        if pinned is None:
            pinned = self._pin_state(step_fn, scope)
            self._bound_rebinds += 1
            STAT_ADD("executor.bound_step_rebinds")
        donated = {}
        for n in step_fn.donated_names:
            v = scope.find_var(n)
            if v is None:
                raise _not_initialised(n)
            donated[n] = _as_device_array(v)
        return donated, pinned, feed_arrays

    def _drop_bound(self, dead=None):
        """Forget the bindings whose executable `dead(cache_key)` names
        (all without it), and the views they left on their scopes."""
        for key, b in list(self._bound.items()):
            if dead is None or dead(b.cache_key):
                del self._bound[key]
                scope = b.scope_ref()
                if scope is not None:
                    scope.drop_view(b.step_fn)

    # ------------------------------------------------------------------
    def _resolve_step(self, program, feed, fetch_list, scope, compiled,
                      use_program_cache=True):
        """Shared front half of run() and lowered_stablehlo(): feed
        preparation, compile-or-cache, and persistable state gathering.
        Returns (step_fn, state, feed_arrays)."""
        feed = dict(feed or {})
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]

        block = program.global_block()

        # FLAGS_sharded_exec gate: upgrade a plain data-parallel
        # CompiledProgram to the GSPMD SpecLayout path — mesh from
        # FLAGS_sharded_mesh ('8' / '4,2') or the parallel registry,
        # per-var PartitionSpecs (ZeRO moments on the data axis, params
        # on the model axis) from the layout table. An explicit
        # with_distributed(state_spec_fn=...) wins; the flag is traced,
        # so flipping it re-keys the executable cache instead of
        # stale-hitting the replicated build.
        if compiled is not None and compiled._is_data_parallel:
            from .core.flags import FLAGS
            if FLAGS.sharded_exec and compiled._state_spec_fn is None:
                from .parallel.layout import SpecLayout, mesh_from_spec
                from .parallel.mesh import get_mesh
                mesh = mesh_from_spec(FLAGS.sharded_mesh) \
                    if FLAGS.sharded_mesh else \
                    (compiled._mesh if compiled._mesh is not None
                     else get_mesh())
                layout = SpecLayout(mesh).add_program(program)
                axes = (layout.data_axis,) if layout.data_axis else ()
                compiled.with_distributed(mesh, state_spec_fn=layout,
                                          batch_axes=axes)
            if compiled._state_spec_fn is not None:
                STAT_ADD("parallel.sharded_steps")

        with _trace.region("executor.feed"):
            feed_arrays = self._prepare_feed(block, feed, compiled)

        # Surface fetch targets hidden inside recompute sub-blocks BEFORE
        # keying the cache: the rewrite mutates the program fingerprint
        # (parallel/recompute.py).
        from .parallel.recompute import expose_fetch_vars
        expose_fetch_vars(program, fetch_names)

        # Static verification gate (FLAGS_program_verify, default warn):
        # memoized per (fingerprint, feeds, fetches); in error mode a
        # malformed program raises HERE — before the cache records a
        # miss or any executable is built (paddle_tpu/analysis).
        from .analysis import verify_gate
        verify_gate(program, feed_names=feed_arrays.keys(),
                    fetch_names=fetch_names, where="executor")

        # Graph-optimization pipeline (FLAGS_graph_opt_level, default 1):
        # DCE/fold/CSE (+fusion scopes/donation at 2) on a verified
        # clone, memoized per (fingerprint, level, feeds, fetches). The
        # OPTIMIZED program keys the cache and feeds _compile, so every
        # artifact surface (run/HLO dumps) sees the same rewrite
        # (paddle_tpu/analysis/passes).
        from .analysis import optimize_gate
        program, _ = optimize_gate(program,
                                   feed_names=feed_arrays.keys(),
                                   fetch_names=fetch_names,
                                   where="executor")
        block = program.global_block()

        # Static memory gate (FLAGS_memory_gate, default error): peak-
        # HBM estimate of the OPTIMIZED program (so level-2 buffer
        # reuse counts) against FLAGS_memory_budget_bytes, with dynamic
        # dims resolved from the concrete feed shapes. An over-budget
        # program raises PTV050/PTV051 HERE — before the cache key, so
        # cache_stats() shows zero compiles attempted
        # (paddle_tpu/analysis/memory.py).
        from .analysis import memory_gate
        memory_gate(program,
                    feed_shapes={n: (tuple(a.shape), str(a.dtype))
                                 for n, a in feed_arrays.items()},
                    fetch_names=fetch_names, where="executor")

        # Static sharding gate (FLAGS_sharding_verify, default warn):
        # propagates the SpecLayout through the OPTIMIZED program and
        # prices the implied collectives; engages only when a layout is
        # in scope (sharded-exec state_spec_fn, or FLAGS_sharded_mesh).
        # A layout-inconsistent program raises PTV060 HERE — before the
        # cache key, so cache_stats() shows zero compiles attempted
        # (paddle_tpu/analysis/sharding.py).
        from .analysis import sharding_gate
        sharding_gate(program,
                      layout=getattr(compiled, "_state_spec_fn", None)
                      if compiled is not None else None,
                      feed_shapes={n: (tuple(a.shape), str(a.dtype))
                                   for n, a in feed_arrays.items()},
                      fetch_names=fetch_names, where="executor")

        key = self._last_key = self._cache_key(
            program, feed_arrays, fetch_names, compiled)
        step_fn = self._cache.get(key) if use_program_cache else None
        self._last_cache_hit = step_fn is not None
        if step_fn is not None:
            self._cache.move_to_end(key)  # LRU touch
            self._cache_hits += 1
            STAT_ADD("executor.compile_cache_hit")
        else:
            self._cache_misses += 1
            STAT_ADD("executor.compile_cache_miss")
            t0 = time.perf_counter()
            # on a miss only: the region that says WHICH step rebuilt
            # its executable
            with _trace.region("executor.compile"):
                step_fn = self._compile(program, block, feed_arrays,
                                        fetch_names, scope, compiled)
            # host-side lowering/closure build only — XLA compile itself
            # is lazy (first call; see executor.compile_first_step_seconds)
            self._last_build_s = time.perf_counter() - t0
            STAT_OBSERVE("executor.compile_build_seconds",
                         self._last_build_s)
            self._cache[key] = step_fn
            if compiled is not None:
                self._compiled_refs[id(compiled)] = compiled
                # once per executable: the scope's state goes onto the
                # mesh now, so that the first call sees what every later
                # call sees and the step compiles once
                # (CompiledProgram.state_sharding)
                compiled.place_state(scope, step_fn.state_in_names)
            from .core.flags import FLAGS
            cap = FLAGS.executor_cache_capacity
            while cap > 0 and len(self._cache) > cap:
                old_key, _ = self._cache.popitem(last=False)
                STAT_ADD("executor.compile_cache_evictions")
                self._drop_bound(lambda k: k == old_key)
                # drop the compiled-program strong ref if no other cache
                # entry still uses it
                cid = old_key[3]
                if cid is not None and all(k[3] != cid
                                           for k in self._cache):
                    self._compiled_refs.pop(cid, None)
            STAT_SET("executor.compile_cache_size", len(self._cache))
            STAT_SET("executor.compile_cache_capacity", cap)

        state = {}
        for n in step_fn.state_in_names:
            v = scope.find_var(n)
            if v is None:
                raise _not_initialised(n)
            if not isinstance(v, jax.Array):
                # the scope takes the device array too, as the step's
                # write-back would have given it: a weight is uploaded
                # once, not by every call that finds it on the host
                v = jnp.asarray(v)
                scope.set(n, v)
            state[n] = v
        return step_fn, state, feed_arrays

    @staticmethod
    def _canon_feed_dtype(dt):
        """The dtype a feed actually has once it reaches the jitted step.

        With x64 disabled (the default here), jnp.asarray/jax.device_put
        narrow int64->int32 and float64->float32. Casting host arrays to
        the canonical dtype up front keeps the executable-cache key
        identical whether a feed arrives as numpy or as a device-resident
        jax.Array — otherwise the same logical batch keys as 'int64' on
        the numpy path and 'int32' on the device path and compiles twice.
        """
        return np.dtype(jax.dtypes.canonicalize_dtype(dt))

    def _prepare_feed(self, block, feed, compiled):
        t0 = time.perf_counter()
        out = {}
        presharded = 0
        ragged_fed = set()  # names padded from a LoDTensor feed
        for name, val in feed.items():
            if isinstance(val, jax.Array):
                # device-resident feed: hand it to the jitted step as-is
                # so repeated runs skip the host->device copy entirely
                # (the TPU analogue of the reference's double-buffered
                # reader keeping batches device-side, buffered_reader.cc)
                staged = False
                if block.has_var(name):
                    want = self._canon_feed_dtype(
                        as_np_dtype(block.var(name).dtype))
                    if val.dtype != want:
                        val = val.astype(want)  # on-device cast
                        staged = True
                ns = compiled.feed_sharding(val.shape) \
                    if compiled is not None else None
                if ns is not None and not val.sharding.is_equivalent_to(
                        ns, val.ndim):
                    # committed to the wrong layout: re-place once here
                    # rather than letting jit gather + re-scatter it on
                    # every step
                    val = jax.device_put(val, ns)
                    staged = True
                if not staged:
                    presharded += 1
                out[name] = val
                continue
            if hasattr(val, "numpy_value"):  # LoDTensor wrapper
                if getattr(val, "lod", lambda: None)():
                    # ragged feed -> (padded, lengths): the TPU layout
                    # for LoD data (reference lod_tensor.h offsets).
                    # The companion lengths var (layers.data lod_level>0
                    # / program.lod_link) is auto-fed alongside. Pad to
                    # a multiple of 8 so varying batch max-lengths don't
                    # churn the per-shape executable cache.
                    padded, lengths = val.to_padded(multiple=8)
                    ragged_fed.add(name)
                    ln = block.program.lod_link.get(name)
                    if ln and block.has_var(ln) and ln not in feed:
                        out[ln] = np.asarray(
                            lengths, self._canon_feed_dtype(np.int64))
                    elif not ln:
                        import warnings
                        warnings.warn(
                            f"feed {name!r} carries LoD but the program "
                            f"declares no lengths var for it (was it "
                            f"created with lod_level=0?); sequence ops "
                            f"will treat padding as real data")
                    val = padded
                else:
                    val = val.numpy_value()
            arr = np.asarray(val)
            if block.has_var(name):
                want = self._canon_feed_dtype(
                    as_np_dtype(block.var(name).dtype))
            else:
                want = self._canon_feed_dtype(arr.dtype)
            if arr.dtype != want:
                arr = arr.astype(want)
            # Under a mesh, place the batch straight into its sharded
            # layout: each device receives only its batch slice, so no
            # replicated host gather ever materialises on-device.
            ns = compiled.feed_sharding(arr.shape) \
                if compiled is not None else None
            out[name] = arr if ns is None else jax.device_put(arr, ns)
        # Dense-feed fallback for ragged-declared vars: a lod_level>0
        # program hard-wires Lengths inputs at build time, but a user may
        # feed an already-padded plain ndarray. Synthesize full-length
        # lengths (= padded T) so those programs run maskless instead of
        # crashing on the unfed companion var.
        for name, ln in block.program.lod_link.items():
            if (ln not in out and name in out and block.has_var(ln)
                    and getattr(block.var(ln), "is_data", False)):
                arr = out[name]
                if arr.ndim >= 2:
                    out[ln] = np.full((arr.shape[0],), arr.shape[1],
                                      self._canon_feed_dtype(np.int64))
        # Rank validation: a wrong-rank feed otherwise surfaces as an
        # opaque XLA broadcast/shape error deep inside the lowering
        # (reference: the feed_op's dim check). Dims may differ (-1
        # batch/seq), rank may not. LoD vars are exempt: a ragged feed
        # is padded to (batch, T, ...) on purpose, which differs from
        # the declared per-timestep shape.
        lod_names = (set(block.program.lod_link)
                     | set(block.program.lod_link.values()) | ragged_fed)
        for name, arr in out.items():
            if name in lod_names or not block.has_var(name):
                continue
            var = block.var(name)
            declared = var.shape
            if not declared or getattr(var, "lod_level", 0):
                continue  # unknown shape / LoD-ragged — nothing to check
            got = tuple(getattr(arr, "shape", ()))
            if len(got) != len(declared):
                raise ValueError(
                    f"feed {name!r}: fed array has rank {len(got)} "
                    f"(shape {list(got)}) but the program declares "
                    f"rank {len(declared)} (shape {list(declared)}); "
                    f"reshape the feed or fix the data layer")
        self._last_feed_s = time.perf_counter() - t0
        if _monitor_on():
            self._note_feed(out, presharded)
        return out

    def _note_feed(self, staged, presharded):
        total = host = 0
        for a in staged.values():
            nb = int(getattr(a, "nbytes", 0) or 0)
            total += nb
            if isinstance(a, np.ndarray):
                host += nb  # will cross host->device inside the step
        STAT_ADD("executor.feed_bytes", total)
        STAT_ADD("executor.feed_host_bytes", host)
        # feeds that arrived already committed to the target
        # sharding/device and were handed through untouched
        STAT_ADD("exec.feed_presharded", presharded)
        STAT_OBSERVE("executor.feed_stage_seconds", self._last_feed_s,
                     exemplar=_trace.current_trace_id())

    def _cache_key(self, program, feed_arrays, fetch_names, compiled):
        from .core.flags import trace_signature
        feed_sig = tuple(sorted(
            (n, a.shape, str(a.dtype)) for n, a in feed_arrays.items()))
        return (program.fingerprint(), feed_sig, tuple(fetch_names),
                id(compiled) if compiled is not None else None,
                trace_signature())

    def _compile(self, program, block, feed_arrays, fetch_names, scope,
                 compiled) -> _CompiledStep:
        # State-in: persistables already initialised in scope OR consumed
        # by some op before being produced.
        persistables = {v.name for v in program.list_vars() if v.persistable}
        produced_all = set()
        consumed_first = set()
        consumed = set()
        for blk in program.blocks:
            for op in blk.ops:
                for n in op.input_names():
                    consumed.add(n)
                    if n in persistables and n not in produced_all:
                        consumed_first.add(n)
                for n in op.output_names():
                    produced_all.add(n)
        # State OUTPUTS come from the global block only: a persistable
        # produced solely inside a sub-block never surfaces in the
        # top-level env, so excluding it keeps build_jit's pinned
        # out_shardings aligned with exactly the keys the traced step
        # returns.
        produced_global = {n for op in block.ops
                           for n in op.output_names()}
        state_in = sorted(n for n in persistables
                          if scope.has(n) or n in consumed_first)
        state_out = sorted(persistables &
                           (produced_global | set(state_in)))
        seed = program.random_seed

        # What is donated. Every donated input must come back as an
        # output, else its scope buffer is invalidated with no
        # replacement; what is not donated is passed pinned and, where
        # no op produces it, not returned either, so XLA emits no
        # output for it at all and the scope keeps the array it has.
        # - a CompiledProgram: the whole state (build_jit pins the
        #   outputs' shardings to the inputs');
        # - a donation plan (analysis/passes/donation.py,
        #   graph_opt_level=2): the hazard-free inplace-updated subset;
        # - otherwise: the state inputs that some op of some block
        #   produces. An inference step donates its KV pools and pins
        #   its weights; a training step donates everything it updates.
        # - a persistable that no op of any block reads and the global
        #   block writes on every run (by an op without a sub-block, and
        #   no op under a condition or in a loop writes it too) is no
        #   input at all: nothing of what it held is wanted. The step
        #   returns a new array, and the one in the scope is neither
        #   passed nor donated: whoever took it from the scope keeps it
        #   (the logits a serving step leaves on the device,
        #   serving/generation.py).
        if compiled is None:
            maybe_written = {n for blk in program.blocks for op in blk.ops
                             if blk is not block or "sub_block" in op.attrs
                             for n in op.output_names()}
            write_only = produced_global - consumed - maybe_written
            state_in = [n for n in state_in if n not in write_only]
        donate_plan = getattr(program, "_donation_plan", None)
        if compiled is not None:
            donate_names = frozenset(state_in)
        elif donate_plan is not None:
            state_out = sorted(n for n in state_out
                               if n in produced_global)
            donate_names = frozenset(
                n for n in state_in
                if n in donate_plan and n in set(state_out))
        else:
            donate_names = frozenset(n for n in state_in
                                     if n in produced_all)
            state_out = sorted(persistables &
                               (produced_global | donate_names))

        mesh = compiled.mesh() if compiled is not None and \
            compiled._is_data_parallel else None

        from .core.flags import FLAGS
        prng_impl = FLAGS.prng_impl
        if prng_impl not in ("", "threefry2x32", "rbg", "unsafe_rbg"):
            raise ValueError(
                f"FLAGS_prng_impl={prng_impl!r}: expected '', "
                f"'threefry2x32', 'rbg' or 'unsafe_rbg'")

        def step(donated_state, pinned_state, feeds, step_idx):
            state = dict(pinned_state)
            state.update(donated_state)
            env = dict(state)
            env.update(feeds)
            if prng_impl:
                root = jax.random.key(seed, impl=prng_impl)
            else:
                root = jax.random.PRNGKey(seed)
            base_key = jax.random.fold_in(root, step_idx)
            ctx = LowerCtx(base_key, mesh=mesh)
            lower_block(block, env, ctx)
            fetches = [env[n] for n in fetch_names]
            # carry state-in values through unchanged if no op wrote
            # them; drop declared outputs a lowering never produced
            # (ops returning {} — comm init, delete_var): storing None
            # in the scope would poison the next run
            new_state = {}
            for n in state_out:
                v = env.get(n, state.get(n))
                if v is not None:
                    new_state[n] = v
            return fetches, new_state

        # ONE calling shape and one name: the device's `XLA Modules`
        # line reads jit_step on every path (the benchmark's trace
        # reduction counts a slice's runs by it)
        if compiled is not None:
            jit_fn = compiled.build_jit(step, state_in, feed_arrays,
                                        state_out_names=state_out)
        else:
            jit_fn = jax.jit(step, donate_argnums=(0,))
        return _CompiledStep(_SplitStateStep(jit_fn, donate_names),
                             state_in, state_out, fetch_names,
                             donate_names)

    def lowered_stablehlo(self, program=None, feed=None, fetch_list=None,
                          scope: Optional[Scope] = None) -> str:
        """StableHLO text of the jitted whole-block step for (program,
        feed, fetch_list) — the audit surface behind the bf16
        dot/conv checks (tools/hlo_audit.py). No reference equivalent:
        the reference interprets ops one-by-one, so there is no single
        compiled artifact to audit."""
        from .compiler import CompiledProgram  # local: avoid cycle

        if program is None:
            from .framework import default_main_program
            program = default_main_program()
        compiled = None
        if isinstance(program, CompiledProgram):
            compiled = program
            program = compiled.program
        scope = scope or global_scope()
        step_fn, state, feed_arrays = self._resolve_step(
            program, feed, fetch_list, scope, compiled)
        return step_fn.fn.lower(state, feed_arrays,
                                np.uint32(0)).as_text()

    def lowered_mlir_debug(self, program=None, feed=None, fetch_list=None,
                           scope: Optional[Scope] = None) -> str:
        """StableHLO/MLIR text WITH debug locations: each op carries a
        loc("...") whose path includes the FLAGS_op_trace_scopes
        annotation ('{op.type}:{block}/{idx}'), so the pre-optimization
        dump attributes to Program ops. (Plain as_text() strips
        locations.)"""
        from .compiler import CompiledProgram  # local: avoid cycle

        if program is None:
            from .framework import default_main_program
            program = default_main_program()
        compiled = None
        if isinstance(program, CompiledProgram):
            compiled = program
            program = compiled.program
        scope = scope or global_scope()
        step_fn, state, feed_arrays = self._resolve_step(
            program, feed, fetch_list, scope, compiled)
        ir = step_fn.fn.lower(state, feed_arrays,
                              np.uint32(0)).compiler_ir(
                                  dialect="stablehlo")
        return ir.operation.get_asm(enable_debug_info=True)

    def compiled(self, program=None, feed=None, fetch_list=None,
                 scope: Optional[Scope] = None):
        """The jitted step for (program, feed, fetch_list), lowered and
        compiled ahead of time: a `jax.stages.Compiled`, whose
        `as_text()` is the post-optimization HLO and whose
        `memory_analysis()` is XLA's own account of argument, output
        and temporary bytes — the compiled counterpart of the static
        planner's estimate (analysis/memory.py). Runs nothing; with a
        persistent compilation cache a step that already ran is read
        back from it."""
        from .compiler import CompiledProgram  # local: avoid cycle

        if program is None:
            from .framework import default_main_program
            program = default_main_program()
        compiled = None
        if isinstance(program, CompiledProgram):
            compiled = program
            program = compiled.program
        scope = scope or global_scope()
        step_fn, state, feed_arrays = self._resolve_step(
            program, feed, fetch_list, scope, compiled)
        # the same default device and the same kinds of argument as
        # run() (the step counter a host uint32): they are part of
        # jit's cache key, and without them a step that already ran is
        # traced, lowered and compiled all over again
        with jax.default_device(self.place.jax_device()):
            return step_fn.fn.lower(state, feed_arrays,
                                    np.uint32(0)).compile()

    def compiled_hlo(self, program=None, feed=None, fetch_list=None,
                     scope: Optional[Scope] = None) -> str:
        """Post-optimization HLO text of the jitted step. Every fused
        instruction carries metadata={op_name="...{op.type}:{blk}/{idx}
        ..."} (FLAGS_op_trace_scopes), which is the join key
        tools/op_profile.py uses to attribute XPlane trace events back
        to framework ops (reference print_profiler's per-op table)."""
        return self.compiled(program, feed, fetch_list, scope).as_text()

    def cache_stats(self) -> Dict[str, int]:
        """Per-instance executable-cache counters (the global
        executor.compile_cache_* stats aggregate every Executor in the
        process; warmup-coverage checks need this one's)."""
        return {"hits": self._cache_hits, "misses": self._cache_misses,
                "size": len(self._cache),
                # calls that ran a bound step (each also a hit), call
                # signatures bound, and bound steps that gathered their
                # pinned state again because the scope was written
                "bound_step_hits": self._bound_hits,
                "bound_step_binds": self._bound_binds,
                "bound_step_rebinds": self._bound_rebinds}

    def close(self):
        self._drop_bound()
        self._cache.clear()
        self._compiled_refs.clear()

    # ------------------------------------------------------------------
    # Dataset trainer path. Reference: Executor.train_from_dataset
    # (executor.py:1098) → TrainerFactory → C++ MultiTrainer with
    # HogwildWorker threads (trainer.h:64, device_worker.h:151). On TPU the
    # worker thread pool collapses into the single jitted step (XLA owns
    # device parallelism); the native C++ feed supplies ready batches.
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        return self._run_from_dataset(program, dataset, scope, thread,
                                      fetch_list, fetch_info, print_period,
                                      drop_last=True)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        # inference must see every sample — keep the final partial batch
        return self._run_from_dataset(program, dataset, scope, thread,
                                      fetch_list, fetch_info, print_period,
                                      drop_last=False)

    def _run_from_dataset(self, program, dataset, scope, thread,
                          fetch_list, fetch_info, print_period, drop_last):
        if dataset is None:
            raise ValueError("dataset must be provided")
        if thread:
            dataset.set_thread(thread)
        # TrainerFactory path (reference trainer_factory.py:26): fleet /
        # pipeline opt info on the program picks the trainer + worker
        from .trainer_desc import TrainerFactory
        opt_info = getattr(program, "_fleet_opt", None) or \
            getattr(program, "_pipeline_opt", None)
        trainer = TrainerFactory()._create_trainer(opt_info)
        trainer.set_fetch_var_and_info(fetch_list, fetch_info,
                                       print_period)
        return trainer.run(self, program, dataset, scope=scope,
                           drop_last=drop_last)
