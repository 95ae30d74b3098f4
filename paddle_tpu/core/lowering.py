"""Whole-block lowering: Program IR -> one JAX function -> one XLA computation.

Reference contrast: the fluid Executor interprets a block op-by-op with per-op
kernel dispatch and a device sync at the end (executor.cc:451-458). On TPU
that design throws away XLA fusion, so here the entire block becomes a single
traced JAX function; XLA owns scheduling, fusion, memory planning (its buffer
assignment subsumes the reference's eager-deletion GC passes,
ir/memory_optimize_pass/) and collective insertion. The architectural
precedent inside the reference itself is the nGraph subgraph engine
(ir/ngraph_subgraph_pass.cc:50 — compile a fused subgraph once, run many
times); we make it total instead of best-effort.

Also here:
- shape inference via jax.eval_shape over op lowerings (replaces ~500
  hand-written InferShape functions, operator.h:430);
- the generic vjp grad-op lowering used by backward.py (replaces per-op
  GradOpMakers, grad_op_desc_maker.h:36).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .dtypes import as_np_dtype, is_floating
from .registry import REGISTRY

GRAD_SUFFIX = "@GRAD"
# Placeholder for the dynamic (batch) dimension during build-time shape
# inference; outputs containing this dim are mapped back to -1. A large
# prime so it cannot collide with a real static layer width.
_DYN_DIM = 100003


class LowerCtx:
    """Per-trace context: PRNG derivation, train/infer mode, mesh info."""

    def __init__(self, base_key, is_test=False, mesh=None):
        self.base_key = base_key
        self.is_test = is_test
        self.mesh = mesh
        # True while lowering a sub-block inside lax.cond/while_loop —
        # ordered effects are not allowed there (see _nan_inf_guard)
        self.in_control_flow = False

    def rng_for(self, op_id: int):
        return jax.random.fold_in(self.base_key, np.uint32(op_id))


def _gather_slot(env, names):
    vals = []
    for n in names:
        if n == "":
            continue
        if n not in env:
            raise KeyError(f"var {n!r} not materialised before use")
        vals.append(env[n])
    return vals


def _nan_inf_guard(op, name, val, in_control_flow, op_idx):
    """FLAGS_check_nan_inf: host callback on every float op output
    (reference operator.cc:820-822 checks every output tensor when the
    flag is set). Top level uses an ordered io_callback that RAISES on
    Inf/Nan; inside lax.cond/while_loop sub-blocks ordered effects are
    rejected by JAX, so the guard degrades to jax.debug.callback, which
    reports loudly but cannot abort the run. On a trip the full
    provenance (op type, block/op index, offending output, input var
    names) goes into the monitor's flight recorder before the raise, so
    a post-mortem names the op even if the exception text is swallowed
    by a retry loop. Debug mode only."""
    from jax.experimental import io_callback

    op_type = op.type
    block_idx = op.block.idx if getattr(op, "block", None) is not None \
        else 0
    in_names = [n for ns in op.inputs.values() for n in ns if n]
    where = f"block {block_idx}/op {'?' if op_idx is None else op_idx}"
    msg = (f"Operator {op_type!r} at {where} output {name!r} contains "
           f"Inf/Nan; op inputs {in_names} (FLAGS_check_nan_inf)")

    def _trip(arr):
        a = np.asarray(arr)
        if np.isfinite(a).all():
            return False
        from ..monitor import STAT_ADD, flight_record
        STAT_ADD("executor.nan_inf_trips")
        flight_record(
            "nan_inf", op_type=op_type, block=block_idx,
            op=(-1 if op_idx is None else op_idx), output=name,
            inputs=in_names, shape=list(np.shape(a)),
            n_nonfinite=int(np.size(a) - np.isfinite(a).sum()))
        return True

    def cb(arr):
        if _trip(arr):
            raise FloatingPointError(msg)
        return np.zeros((), np.bool_)

    if in_control_flow:
        def report(arr):
            if _trip(arr):
                print(f"FLAGS_check_nan_inf: {msg} (inside control "
                      f"flow; run aborts are only possible at top "
                      f"level)")
        jax.debug.callback(report, val)
    else:
        io_callback(cb, jax.ShapeDtypeStruct((), np.bool_), val,
                    ordered=True)


def _op_scope(op, op_idx):
    """jax.named_scope('{op.type}:{block}/{op_idx}') around one op's
    emission (FLAGS_op_trace_scopes): the scope lands in the jaxpr name
    stack, so HLO op_name metadata, MLIR debug locations, and XPlane
    traces all attribute back to the Program op — the trace-side half
    of the reference's per-op RecordEvent (platform/profiler.cc). Ops
    lowered outside lower_block (shape inference) pass op_idx=None and
    stay unscoped.

    Ops the fusion-scope pass tagged (op._fusion_group, set at
    FLAGS_graph_opt_level=2 by analysis/passes/fusion.py) share a
    'ewfuseN/' scope prefix, so a whole elementwise chain lands under
    one name-stack entry — one fusion candidate for XLA instead of N
    disjoint scopes. The group scope is emitted even with trace scopes
    off: it exists for the compiler, not just the profiler."""
    from .flags import FLAGS
    if op_idx is None:
        return contextlib.nullcontext()
    group = getattr(op, "_fusion_group", None)
    if not FLAGS.op_trace_scopes:
        return (jax.named_scope(group) if group
                else contextlib.nullcontext())
    block_idx = op.block.idx if getattr(op, "block", None) is not None \
        else 0
    prefix = f"{group}/" if group else ""
    return jax.named_scope(f"{prefix}{op.type}:{block_idx}/{op_idx}")


def run_op(op, env, ctx, op_idx=None):
    """Execute one op's lowering against env (name -> array)."""
    from .flags import FLAGS
    blk = op.block.idx if getattr(op, "block", None) is not None else 0
    opdef = REGISTRY.get(
        op.type, where=f"{blk}/{'?' if op_idx is None else op_idx}")
    ins = {}
    for slot, names in op.inputs.items():
        vals = _gather_slot(env, names)
        if vals:
            ins[slot] = vals
    opctx = _OpCtx(ctx, op)
    # live view of already-materialised vars — lets keep-previous-value
    # semantics (conditional_block false branch) read carried state
    opctx.env = env
    with _op_scope(op, op_idx):
        try:
            outs = opdef.lower(opctx, ins, op.attrs)
        except Exception as e:
            # operator attribution on failures (reference op_call_stack.cc:
            # PADDLE_ENFORCE appends the Python-level op that emitted the
            # kernel): name the op, its input slots/shapes, and attrs so
            # users see WHICH Program op died, not just a jnp traceback
            shapes = {s: [getattr(v, "shape", "?") for v in vs]
                      for s, vs in ins.items()}
            note = (f"[operator {op.type!r}] inputs {shapes} -> outputs "
                    f"{dict(op.outputs)}, attrs {op.attrs}")
            e.add_note(note)
            raise
        check = FLAGS.check_nan_inf
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            for name, val in zip(names, vals):
                if name:
                    env[name] = val
                    if check and hasattr(val, "dtype") and \
                            is_floating(val.dtype):
                        _nan_inf_guard(op, name, val,
                                       ctx.in_control_flow, op_idx)


class _OpCtx:
    """View of LowerCtx bound to one op: gives it its deterministic key."""

    def __init__(self, ctx: LowerCtx, op):
        self._ctx = ctx
        self._op = op
        self.is_test = ctx.is_test or bool(op.attrs.get("is_test", False))
        self.mesh = ctx.mesh
        self.block = getattr(op, "block", None)
        self.attrs = op.attrs

    @property
    def rng(self):
        # Stateful ops fold the op's stable id so the generic vjp grad (which
        # re-lowers the fwd op under jax.vjp with the same id) sees identical
        # randomness — the dropout-mask-consistency problem the reference
        # solves by stashing the mask in an output var.
        fwd_id = self._op.attrs.get("fwd_id", self._op.id)
        return self._ctx.rng_for(fwd_id)

    def sub_block(self, idx):
        return self._op.block.program.blocks[idx]

    def lower_sub_block(self, block, env):
        prev = self._ctx.in_control_flow
        self._ctx.in_control_flow = True
        try:
            for i, op in enumerate(block.ops):
                run_op(op, env, self._ctx, op_idx=i)
        finally:
            self._ctx.in_control_flow = prev
        return env


def lower_block(block, env: Dict, ctx: LowerCtx):
    for i, op in enumerate(block.ops):
        run_op(op, env, ctx, op_idx=i)
    return env


# ---------------------------------------------------------------------------
# Build-time shape inference
# ---------------------------------------------------------------------------

def infer_op_shapes(op, block):
    """Fill in output var shapes/dtypes by abstract-evaluating the lowering."""
    opdef = REGISTRY.get(op.type)

    env = {}
    for slot, names in op.inputs.items():
        for n in names:
            if not n or n in env:
                continue
            v = block.var(n)
            if v.shape is None:
                return  # cannot infer yet
            shape = tuple(_DYN_DIM if d == -1 else d for d in v.shape)
            env[n] = jax.ShapeDtypeStruct(shape, as_np_dtype(v.dtype))

    def f(e):
        e = dict(e)
        ctx = LowerCtx(jax.random.PRNGKey(0))
        run_op(op, e, ctx)
        return {n: e[n] for n in op.output_names() if n and n in e}

    out = jax.eval_shape(f, env)
    for name, sds in out.items():
        v = block.var(name)
        v.shape = tuple(-1 if d == _DYN_DIM else int(d) for d in sds.shape)
        v.dtype = jnp.dtype(sds.dtype).name if sds.dtype != jnp.bfloat16 \
            else "bfloat16"


# ---------------------------------------------------------------------------
# Generic grad op: grad::<type> — vjp over the forward lowering
# ---------------------------------------------------------------------------

def _is_diff(arr):
    return is_floating(arr.dtype)


def generic_grad_lower(ctx, ins, attrs):
    fwd_type = attrs["fwd_type"]
    fwd_attrs = attrs["fwd_attrs"]
    fwd_in_slots: Dict[str, int] = attrs["fwd_in_slots"]    # slot -> arity
    fwd_out_slots: List[str] = attrs["fwd_out_slots"]
    # Which positions of each output slot have an incoming cotangent;
    # _gather_slot drops empty-name entries, so this mask restores
    # positional alignment for multi-output slots (e.g. split).
    grad_mask: Dict[str, List[bool]] = attrs.get("fwd_out_grad_mask", {})
    opdef = REGISTRY.get(fwd_type)

    # Split inputs into forward-inputs vs incoming output-cotangents.
    fwd_ins = {s: ins[s] for s in fwd_in_slots if s in ins}
    fake_op = _FakeOp(fwd_type, fwd_attrs, attrs["fwd_id"], ctx)

    if opdef.manual_grad is not None:
        # positionally realign multi-output cotangent lists: _gather_slot
        # drops empty-name entries, so without the mask a manual grad
        # would zip Outputs@GRAD[0] against Ids[1] etc. Missing
        # cotangents become None — manual grads must skip them.
        ins2 = dict(ins)
        for slot in fwd_out_slots:
            gslot = slot + GRAD_SUFFIX
            mask = grad_mask.get(slot)
            if gslot in ins2 and mask is not None and \
                    sum(mask) == len(ins2[gslot]) and \
                    len(mask) != len(ins2[gslot]):
                avail = list(ins2[gslot])
                ins2[gslot] = [avail.pop(0) if present else None
                               for present in mask]
        return opdef.manual_grad(_OpCtx(ctx._ctx, fake_op), ins2,
                                 fwd_attrs)

    diff_slots = [s for s in fwd_ins
                  if s not in opdef.nondiff_inputs
                  and all(_is_diff(a) for a in fwd_ins[s])]
    nondiff = {s: fwd_ins[s] for s in fwd_ins if s not in diff_slots}

    def f(diff):
        full = dict(nondiff)
        full.update(diff)
        outs = opdef.lower(_OpCtx(ctx._ctx, fake_op), full, fwd_attrs)
        return {s: outs[s] for s in fwd_out_slots if s in outs}

    diff_in = {s: fwd_ins[s] for s in diff_slots}
    primal_out, vjp = jax.vjp(f, diff_in)

    cots = {}
    for slot, prims in primal_out.items():
        gslot = slot + GRAD_SUFFIX
        avail = list(ins.get(gslot, []))
        mask = grad_mask.get(slot, [bool(avail)] * len(prims))
        slot_cots = []
        for a, present in zip(prims, mask):
            if present and avail and _is_diff(a):
                slot_cots.append(avail.pop(0).astype(a.dtype))
            else:
                slot_cots.append(jnp.zeros(a.shape, a.dtype))
        cots[slot] = slot_cots
    (gin,) = vjp(cots)
    return {s + GRAD_SUFFIX: gin[s] for s in gin}


class _FakeOp:
    """Stand-in op object so _OpCtx can derive the forward op's PRNG key."""

    def __init__(self, type_, attrs, fwd_id, octx):
        self.type = type_
        self.attrs = dict(attrs)
        self.attrs["fwd_id"] = fwd_id
        self.id = fwd_id
        self.block = octx.block


from .registry import OpDef  # noqa: E402

REGISTRY.register(OpDef(type="grad::generic", lower=generic_grad_lower))
