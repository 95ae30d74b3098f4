"""TPU-native pipeline parallelism (GPipe schedule over a mesh axis).

Reference analogue: PipelineOptimizer (optimizer.py:3020) cuts a Program
into sections streamed through ScopeQueues by PipelineTrainer/SectionWorker
threads (trainer.h:115-160) — a host-scheduled, queue-based pipeline.

On TPU the idiomatic equivalent is an SPMD collective-permute pipeline
(scaling-book recipe): every pipeline stage lives on its own slice of a
``pp`` mesh axis, holds its own stage parameters, and activations flow
stage→stage over ICI via ``lax.ppermute`` inside a ``lax.scan`` over the
microbatch clock. Fill/drain bubbles, microbatch scheduling and the reverse
(backward) schedule all fall out of the scan + ppermute structure: jax.grad
differentiates through it, and the transpose of ppermute is the reverse
permute, so the backward pass is automatically the mirrored pipeline.

Homogeneous stages (e.g. N identical transformer layers) are required —
the same constraint the stacked-parameter SPMD formulation always has; the
reference's heterogeneous CPU↔GPU sections map instead to ``SectionPipeline``
below (sequential microbatching with gradient accumulation, the semantic
fallback).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from .mesh import get_mesh

__all__ = ["gpipe", "stack_stage_params", "SectionPipeline"]


def stack_stage_params(params_list):
    """Stack per-stage parameter pytrees along a new leading stage axis.

    [{'w': [d,d]}, ...] * n_stages -> {'w': [n_stages, d, d]} — the layout
    gpipe expects (stage axis sharded over the ``pp`` mesh axis).
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def gpipe(stage_fn: Callable, stacked_params, x, *, n_microbatches: int,
          mesh=None, axis: str = "pp"):
    """Run ``n_stages`` copies of ``stage_fn`` as a pipeline over ``axis``.

    stage_fn(stage_params, acts) -> acts   (activation shape preserved)
    stacked_params: pytree with leading dim n_stages (see stack_stage_params)
    x: [batch, ...] global input; batch must divide by n_microbatches.

    Differentiable end-to-end: wrap in jax.grad for pipelined training.
    """
    mesh = mesh or get_mesh()
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(f"batch {batch} % n_microbatches {n_microbatches}")
    x_mb = x.reshape(n_microbatches, batch // n_microbatches, *x.shape[1:])

    def run(params, x_mb):
        local = jax.tree.map(lambda a: a[0], params)  # this stage's slice
        idx = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        n_micro = x_mb.shape[0]

        def body(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t; later stages consume the
            # activation ppermuted from stage-1 on the previous tick
            inp = jnp.where(idx == 0, x_mb[jnp.clip(t, 0, n_micro - 1)],
                            state)
            y = stage_fn(local, inp)
            # last stage finishes microbatch m = t - (n_stages-1)
            m = t - (n_stages - 1)
            slot = jnp.clip(m, 0, n_micro - 1)
            keep = (idx == n_stages - 1) & (m >= 0)
            prev = jax.lax.dynamic_index_in_dim(outputs, slot, 0,
                                                keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(keep, y, prev), slot, 0)
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outputs), None

        # The carry is device-varying over the pp axis (each stage holds a
        # different activation), so the init must be cast to varying for
        # shard_map's per-axis type check to accept the scan.
        init = jax.lax.pcast((jnp.zeros_like(x_mb[0]),
                              jnp.zeros_like(x_mb)), axis, to="varying")
        (_, outputs), _ = jax.lax.scan(
            body, init, jnp.arange(n_microbatches + n_stages - 1))
        # outputs are only valid on the last stage; replicate across pp
        mask = (idx == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    out = jax.shard_map(run, mesh=mesh, in_specs=(pspec, P()),
                        out_specs=P(),
                        axis_names={axis})(stacked_params, x_mb)
    return out.reshape(batch, *out.shape[2:])


class SectionPipeline:
    """Heterogeneous-section fallback: reference PipelineOptimizer semantics
    (sections run in order per microbatch, gradients accumulated across
    microbatches). On one chip this is microbatched gradient accumulation —
    XLA overlaps section compute; there is no host queue to schedule.
    """

    def __init__(self, section_fns, n_microbatches: int):
        self.sections = list(section_fns)
        self.n_microbatches = n_microbatches

    def _check_batch(self, x):
        if x.shape[0] % self.n_microbatches:
            raise ValueError(f"batch {x.shape[0]} % n_microbatches "
                             f"{self.n_microbatches}")

    def forward(self, params_per_section, x):
        self._check_batch(x)
        mbs = jnp.split(x, self.n_microbatches)
        outs = []
        for mb in mbs:
            h = mb
            for fn, p in zip(self.sections, params_per_section):
                h = fn(p, h)
            outs.append(h)
        return jnp.concatenate(outs)

    def grad(self, loss_fn, params_per_section, x, y):
        """Mean loss + grads accumulated over microbatches (one XLA
        program; scan keeps the HLO small for many microbatches)."""
        self._check_batch(x)
        xm = jnp.stack(jnp.split(x, self.n_microbatches))
        ym = jnp.stack(jnp.split(y, self.n_microbatches))

        def one(carry, xy):
            xb, yb = xy

            def f(ps):
                h = xb
                for fn, p in zip(self.sections, ps):
                    h = fn(p, h)
                return loss_fn(h, yb)

            l, g = jax.value_and_grad(f)(params_per_section)
            loss_acc, grad_acc = carry
            return (loss_acc + l,
                    jax.tree.map(jnp.add, grad_acc, g)), None

        zero = (jnp.zeros(()),
                jax.tree.map(jnp.zeros_like, params_per_section))
        (loss, grads), _ = jax.lax.scan(one, zero, (xm, ym))
        k = self.n_microbatches
        return loss / k, jax.tree.map(lambda g: g / k, grads)
