"""CompiledProgram: parallel/optimized execution configuration.

Reference: compiler.py:138 CompiledProgram.with_data_parallel constructs a
ParallelExecutor — per-device graph clones + NCCL AllReduce op-handles
(parallel_executor.cc:393, multi_devices_graph_pass.cc:454). On TPU none of
that machinery exists as code you schedule: the SAME step function is jitted
with batch-sharded feed shardings over a jax Mesh, and XLA GSPMD inserts the
gradient all-reduces over ICI. BuildStrategy knobs that configured the graph
passes (fuse_all_reduce, etc.) become no-ops — XLA owns fusion — but remain
accepted for source compatibility.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy"]


class BuildStrategy:
    """Knob-compatible with fluid.BuildStrategy (build_strategy.h).

    reduce_strategy/gradient_scale_strategy etc. are accepted; on TPU the
    equivalents are handled by GSPMD sharding propagation.
    """

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.fuse_all_optimizer_ops = True
        self.sync_batch_norm = False
        self.enable_inplace = True
        self.memory_optimize = True
        self.nccl_comm_num = 1
        self.use_hierarchical_allreduce = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    """fluid.ExecutionStrategy (pybind.cc:1655) — scheduling knobs.
    XLA owns scheduling; fields kept for compatibility."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.num_iteration_per_run = 1
        self.use_experimental_executor = False


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy: Optional[
            BuildStrategy] = None):
        self.program = program_or_graph
        self.build_strategy = build_strategy or BuildStrategy()
        self.exec_strategy = None
        self._is_data_parallel = False
        self._loss_name = None
        self._places = None
        self._mesh = None
        self._state_spec_fn = None
        self._batch_axes = ("dp",)

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self.build_strategy = build_strategy
        self.exec_strategy = exec_strategy
        self._places = places
        return self

    def with_distributed(self, mesh: Mesh, state_spec_fn=None,
                         batch_axes=("dp",)):
        """Full SPMD: custom mesh (any dp/tp/sp/pp factorisation) +
        per-parameter PartitionSpecs. state_spec_fn(var_name) ->
        PartitionSpec or None (replicated). Feeds shard over batch_axes.
        This is what the reference needed BuildStrategy + transpilers +
        NCCL ring setup for; here it is three arguments to GSPMD."""
        self._is_data_parallel = True
        self._mesh = mesh
        self._state_spec_fn = state_spec_fn
        self._batch_axes = tuple(batch_axes)
        return self

    # -- executor hook ---------------------------------------------------
    def mesh(self) -> Mesh:
        if self._mesh is None:
            devs = np.array(jax.devices())
            self._mesh = Mesh(devs, axis_names=("dp",))
        return self._mesh

    def feed_sharding(self, shape) -> Optional[NamedSharding]:
        """Target placement for one feed of `shape`: dim 0 split over
        the batch axes when it divides their product, else replicated.
        None when sharding is inactive (single device / not parallel).
        Executor._prepare_feed uses this to device_put batches straight
        into their sharded layout (no host gather), and build_jit uses
        the SAME rule for in_shardings — the two must agree or jit
        re-stages every feed."""
        if not self._is_data_parallel or len(jax.devices()) == 1:
            return None
        mesh = self.mesh()
        batch_axes = tuple(a for a in self._batch_axes
                           if a in mesh.axis_names)
        nbatch = int(np.prod([mesh.shape[a] for a in batch_axes])) \
            if batch_axes else 1
        shape = tuple(shape or ())
        if (batch_axes and len(shape) >= 1 and nbatch > 1
                and shape[0] % nbatch == 0):
            return NamedSharding(mesh, P(batch_axes if len(batch_axes) > 1
                                         else batch_axes[0]))
        return NamedSharding(mesh, P())

    def state_sharding(self, name) -> Optional[NamedSharding]:
        """Placement of one persistable under the mesh: state_spec_fn's
        spec, replicated by default; None when sharding is inactive.
        build_jit pins the step's state in/out shardings to this, and
        the Executor places the scope's state with it before the first
        call: jax types a mesh-placed array differently from a
        single-device one, so state left where the startup program put
        it would trace and compile the whole step a second time, once
        the first step's outputs came back on the mesh."""
        if not self._is_data_parallel or len(jax.devices()) == 1:
            return None
        spec = self._state_spec_fn(name) \
            if self._state_spec_fn is not None else None
        return NamedSharding(self.mesh(), spec if spec is not None
                             else P())

    def place_state(self, scope, names):
        """Put the scope's persistables `names` where state_sharding
        says (the Executor calls this once per new executable).
        Multi-process runs globalize inside build_jit's wrapper
        instead: a process-local array cannot be device_put onto
        another process's devices."""
        if jax.process_count() > 1:
            return
        for n in names:
            ns = self.state_sharding(n)
            v = scope.find_var(n)
            if ns is not None and v is not None:
                scope.set(n, jax.device_put(v, ns))

    def build_jit(self, step_fn, state_in_names, feed_arrays,
                  state_out_names=()):
        """jit `step_fn(state, pinned, feeds, step_idx)` (the Executor's
        one calling shape; here the whole state is donated and `pinned`
        is empty) with SPMD shardings:
        feeds sharded on the batch axes, params per state_spec_fn
        (replicated by default). GSPMD then emits gradient AllReduces /
        TP collectives over ICI — the entire reference multi-device
        scheduler (SURVEY.md §2.1 details/) reduces to these
        in_shardings. State OUTPUTS are pinned to the same shardings so
        the round-tripped state dict feeds the next step (and sharded
        checkpoints) without GSPMD drifting a param's layout."""
        if not self._is_data_parallel or len(jax.devices()) == 1:
            return jax.jit(step_fn, donate_argnums=(0,))
        mesh = self.mesh()
        repl = NamedSharding(mesh, P())
        shard_of = self.state_sharding
        state_shard = {n: shard_of(n) for n in state_in_names}
        unknown = [a for a in self._batch_axes if a not in mesh.axis_names]
        if unknown:
            raise ValueError(
                f"batch_axes {unknown} not in mesh axes {mesh.axis_names}")
        feed_shard = {n: self.feed_sharding(a.shape)
                      for n, a in feed_arrays.items()}
        # Pin state out_shardings only when every state output is also a
        # state input — then each returned value provably exists and the
        # pytree matches. A program with produced-but-not-consumed
        # persistables may drop keys at trace time (lowerings returning
        # {}), so fall back to letting XLA choose.
        if set(state_out_names) <= set(state_in_names):
            out_state = {n: shard_of(n) for n in state_out_names}
        else:
            out_state = None
        jitted = jax.jit(step_fn, donate_argnums=(0,),
                         in_shardings=(state_shard, None, feed_shard,
                                       repl),
                         out_shardings=(None, out_state) if out_state
                         else None)
        if jax.process_count() <= 1:
            return jitted

        # Multi-process (multi-host) mesh: jit cannot shard raw numpy
        # feeds, and startup-produced params live on one process-local
        # device. Both carry the SAME value on every process (seeded
        # startup; the trainer feeds the global batch), so lift them to
        # global jax.Arrays explicitly. Step outputs are already global
        # and pass through untouched.
        global_devs = set(np.asarray(mesh.devices).flat)

        def _globalize(val, sharding):
            if isinstance(val, jax.Array):
                if val.sharding.device_set == global_devs:
                    return val
                val = np.asarray(val)  # process-local -> host
            arr = np.asarray(val)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])

        def run_global(state, pinned, feeds, step_idx):
            state = {n: _globalize(v, state_shard.get(n, repl))
                     for n, v in state.items()}
            feeds = {n: _globalize(v, feed_shard.get(n, repl))
                     for n, v in feeds.items()}
            return jitted(state, pinned, feeds, step_idx)

        return run_global
