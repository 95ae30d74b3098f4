"""Whole-program shape & dtype propagation with zero device work.

Reference analogue: the ~500 hand-written InferShape functions the
reference runs over every OpDesc (framework/operator.h:430). Here the
lowering IS the shape function: each op is abstract-evaluated with
`jax.eval_shape` over its registered lowering — the same trick
`lowering.infer_op_shapes` plays at append time, extended to propagate
through a whole Program (including ops appended with infer_shape=False,
e.g. the grad::generic ops backward.py emits) and to CHECK the inferred
specs against the declared Variable.shape/dtype instead of writing them
back.

Ops that cannot abstract-eval are handled two ways:

- `OpDef.abstract_eval` (core/registry.py): a registered shape rule
  `fn(op, in_specs, block) -> {out_name: (shape, dtype)}` — control-flow
  ops (while, conditional_block) register one in ops/controlflow.py.
- `OPAQUE_OPS`: host/RPC/IO/LoD-array/collective ops whose outputs take
  their declared specs unchecked (the spec-band rules simply do not fire
  for them; the dataflow lints in verifier.py still do).

A spec is `(shape, dtype_name)` with -1 marking dynamic dims. Declared
shapes of `None` or `()` are treated as unknown — `Variable.to_dict`
serializes None as [], so a round-tripped unknown is indistinguishable
from a scalar; treating both as unknown forfeits checking on true
scalars but can never produce a false positive.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import numpy as np

from ..core.dtypes import as_np_dtype
from ..core.registry import REGISTRY
from ..core import lowering


class Spec(NamedTuple):
    """(shape, dtype_name) with -1 marking dynamic dims.

    A NamedTuple so the historical plain-tuple protocol still holds —
    `shape, dtype = spec`, equality against `(shape, dtype)`, and plain
    tuples returned by abstract_eval rules all keep working; consumers
    that need methods normalize with `Spec(*spec)`.
    """

    shape: Tuple[int, ...]
    dtype: str

    def nbytes(self, dyn_defaults: int = 1) -> Tuple[int, bool]:
        """Size in bytes -> (nbytes, dynamic).

        Dynamic dims (-1, or the _DYN_DIM placeholder family) are
        substituted with `dyn_defaults` elements each, so with the
        default of 1 the returned byte count is a documented LOWER
        BOUND whenever `dynamic` is True. Callers doing budget math
        (PTV050) must surface the marker instead of presenting the
        bound as exact; resolving real feed shapes first (the memory
        gate's seed path) clears the marker.
        """
        dynamic = False
        n = 1
        for d in self.shape:
            d = int(d)
            if d < 0 or d >= _DYN:
                dynamic = True
                d = int(dyn_defaults)
            n *= max(d, 0)
        itemsize = np.dtype(as_np_dtype(self.dtype)).itemsize
        return n * itemsize, dynamic


# Dynamic-dim placeholder shared with lowering.infer_op_shapes: dims this
# large (or products thereof) read back as dynamic.
_DYN = lowering._DYN_DIM

# Ops whose lowering needs runtime machinery an abstract env cannot
# supply: TensorArray vars hold Python lists (not ShapeDtypeStructs),
# host/RPC/IO ops talk to the outside world, mesh collectives need bound
# axis names. Their outputs take declared specs unchecked.
OPAQUE_OPS = frozenset({
    # executor plumbing
    "feed", "fetch",
    # TensorArray / LoD / decode-loop ops (env values are host lists)
    "write_to_array", "read_from_array", "tensor_array_to_tensor",
    "lod_array_length", "array_to_lod_tensor", "lod_tensor_to_array",
    "merge_lod_tensor", "split_lod_tensor", "lod_rank_table",
    "max_sequence_len", "shrink_rnn_memory", "rnn_memory_helper",
    "reorder_lod_tensor_by_rank", "beam_search", "beam_search_decode",
    "beam_reorder", "gather_tree", "select_input",
    # host-side PS/RPC runtime ops
    "listen_and_serv", "fl_listen_and_serv", "send", "recv", "prefetch",
    "fetch_barrier", "send_barrier", "gen_nccl_id", "c_gen_nccl_id",
    "c_comm_init", "c_comm_init_all", "checkpoint_notify",
    "geo_sgd_send", "ref_by_trainer_id", "distributed_lookup_table",
    "lookup_sparse_table", "split_ids", "merge_ids", "split_byref",
    "delete_var", "distributed_notify", "push_box_sparse",
    # host IO / readers
    "save", "save_combine", "load", "load_combine", "read",
    "create_custom_reader",
    # mesh collectives (axis names unbound outside shard_map)
    "c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
    "c_allreduce_prod", "c_allgather", "c_reducescatter", "c_broadcast",
    "c_sync_calc_stream", "c_sync_comm_stream", "allreduce", "broadcast",
    "shard_hint", "ring_attention", "ulysses_attention", "c_alltoall",
    "moe_ffn", "sync_batch_norm",
    # misc host-side
    "py_func", "get_places", "fake_init", "coalesce_tensor",
    "recurrent", "recompute_segment", "conditional_block_infer",
    "split_selected_rows", "merge_selected_rows",
    "get_tensor_from_selected_rows",
})


def declared_spec(var) -> Optional[Spec]:
    """(shape, dtype) from a Variable's declaration, None if unknown."""
    shp = getattr(var, "shape", None)
    if not shp:  # None or () — see module docstring
        return None
    return Spec(tuple(int(d) for d in shp), str(var.dtype))


def _dtype_name(dt) -> str:
    import jax.numpy as jnp
    return "bfloat16" if dt == jnp.bfloat16 else str(np.dtype(dt))


def _canon(dtype_name: str):
    return np.dtype(jax.dtypes.canonicalize_dtype(as_np_dtype(dtype_name)))


def _dims_match(inferred, declared) -> bool:
    if len(inferred) != len(declared):
        return False
    for a, b in zip(inferred, declared):
        # -1 and _DYN-derived dims are wildcards on either side
        if a < 0 or b < 0 or a >= _DYN or b >= _DYN:
            continue
        if int(a) != int(b):
            return False
    return True


def _eval_op(op, in_specs: Dict[str, Spec]) -> Dict[str, Spec]:
    """Abstract-evaluate one op's lowering: {in name: spec} -> {out
    name: spec}. Raises whatever the lowering raises under eval_shape."""
    env = {}
    for n, (shape, dtype) in in_specs.items():
        shp = tuple(_DYN if d == -1 else int(d) for d in shape)
        env[n] = jax.ShapeDtypeStruct(shp, as_np_dtype(dtype))

    def f(e):
        e = dict(e)
        ctx = lowering.LowerCtx(jax.random.PRNGKey(0))
        lowering.run_op(op, e, ctx)
        return {n: e[n] for n in op.output_names() if n and n in e}

    out = jax.eval_shape(f, env)
    specs = {}
    for name, sds in out.items():
        shape = tuple(-1 if d >= _DYN else int(d) for d in sds.shape)
        specs[name] = Spec(shape, _dtype_name(sds.dtype))
    return specs


def _freeze(value):
    """A hashable stand-in for an attr value made of plain data; raises
    TypeError for anything else (a Block, an array, a callable)."""
    if value is None or isinstance(value, (bool, int, float, str, bytes)):
        return (type(value).__name__, value)
    if isinstance(value, (list, tuple)):
        return (type(value).__name__, tuple(_freeze(v) for v in value))
    if isinstance(value, dict):
        return ("dict", tuple(sorted((str(k), _freeze(v))
                                     for k, v in value.items())))
    if isinstance(value, np.generic):
        return (type(value).__name__, value.item())
    raise TypeError(type(value).__name__)


def _memo_key(op, in_specs):
    """What `_eval_op`'s answer depends on, names left out: the op's
    type and attrs, the spec at each input position, and which output
    positions are named and which input each aliases (an in-place
    output reads back its input's spec where the lowering sets none).
    None where an attr is not plain data: such an op is evaluated
    every time."""
    try:
        attrs = _freeze(op.attrs)
    except TypeError:
        return None
    in_names = op.input_names()
    ins = tuple((slot, tuple(in_specs.get(n) for n in names))
                for slot, names in sorted(op.inputs.items()))
    outs = tuple((slot, tuple(in_names.index(n) if n in in_names
                              else (-1 if n else None) for n in names))
                 for slot, names in sorted(op.outputs.items()))
    return op.type, attrs, ins, outs


def _eval_op_memo(op, in_specs, memo):
    """`_eval_op`, once for the ops of one program that differ in their
    variables' names only (the layers of a model: a 24-layer decoder has
    4,000 ops and some 60 distinct ones). The answer is kept by output
    position and handed back under this op's own names."""
    key = _memo_key(op, in_specs)
    if key is None:
        return _eval_op(op, in_specs)
    if key not in memo:
        out = _eval_op(op, in_specs)
        memo[key] = {(slot, i): out[n]
                     for slot, names in op.outputs.items()
                     for i, n in enumerate(names) if n in out}
    return {op.outputs[slot][i]: spec
            for (slot, i), spec in memo[key].items()}


def infer_program_specs(program, result, check=True,
                        seed: Optional[Dict[str, Spec]] = None
                        ) -> Dict[str, Spec]:
    """Propagate specs through every block; append PTV020/021/022
    findings to `result`. Returns the global block's final spec env.

    seed: {var name: (shape, dtype)} pre-loaded into the global block's
    env before propagation — the memory gate seeds the concrete feed
    shapes here so dynamic (-1/_DYN_DIM) dims resolve downstream
    instead of poisoning size arithmetic (Spec.nbytes)."""
    envs: Dict[int, Dict[str, Spec]] = {}
    memo: Dict[tuple, dict] = {}     # this call's only: _eval_op_memo
    for block in program.blocks:
        parent = envs.get(block.parent_idx, {}) \
            if block.parent_idx >= 0 else {}
        env = dict(parent)
        if block.idx == 0 and seed:
            for name, spec in seed.items():
                env[str(name)] = Spec(tuple(int(d) for d in spec[0]),
                                      str(spec[1]))
        envs[block.idx] = env
        for op_idx, op in enumerate(block.ops):
            _infer_op(op, op_idx, block, env, result, check, memo)
    return envs.get(0, {})


def _seed_outputs_from_decl(op, block, env):
    for name in op.output_names():
        if not name or name in env:
            continue
        var = block._find_var_recursive(name)
        spec = declared_spec(var) if var is not None else None
        if spec is not None:
            env[name] = spec


def _infer_op(op, op_idx, block, env, result, check, memo):
    opdef = REGISTRY._ops.get(op.type)
    if opdef is None or op.type in OPAQUE_OPS:
        # unregistered is the verifier's PTV001; opaque is by design —
        # either way outputs take declared specs so propagation continues
        _seed_outputs_from_decl(op, block, env)
        return

    in_specs: Dict[str, Spec] = {}
    missing = False
    for name in op.input_names():
        if not name or name in in_specs:
            continue
        spec = env.get(name)
        if spec is None:
            var = block._find_var_recursive(name)
            spec = declared_spec(var) if var is not None else None
        if spec is None:
            missing = True
            break
        in_specs[name] = spec

    if getattr(opdef, "abstract_eval", None) is not None:
        try:
            out = opdef.abstract_eval(op, in_specs, block) or {}
        except Exception as e:  # noqa: BLE001 — a broken rule is a finding
            result.add("PTV022",
                       f"abstract-eval rule for {op.type!r} failed: "
                       f"{type(e).__name__}: {e}",
                       op_type=op.type, block=block.idx, op_idx=op_idx)
            out = {}
        for name, spec in out.items():
            env[name] = spec
            if check:
                _check_against_decl(op, op_idx, block, name, spec, result)
        _seed_outputs_from_decl(op, block, env)
        return

    if missing:
        # an input spec is unknowable (same bail as infer_op_shapes'
        # "cannot infer yet") — not a finding, just lost coverage
        _seed_outputs_from_decl(op, block, env)
        return

    try:
        out = _eval_op_memo(op, in_specs, memo)
    except Exception as e:  # noqa: BLE001 — the whole point: any crash
        # inside the lowering under eval_shape means this program cannot
        # lower, reported with op provenance instead of a jnp traceback
        msg = str(e).split("\n", 1)[0][:300]
        result.add("PTV022",
                   f"lowering failed under jax.eval_shape: "
                   f"{type(e).__name__}: {msg}",
                   op_type=op.type, block=block.idx, op_idx=op_idx)
        _seed_outputs_from_decl(op, block, env)
        return

    for name, spec in out.items():
        env[name] = spec
        if check:
            _check_against_decl(op, op_idx, block, name, spec, result)
    _seed_outputs_from_decl(op, block, env)


def _check_against_decl(op, op_idx, block, name, spec, result):
    var = block._find_var_recursive(name)
    decl = declared_spec(var) if var is not None else None
    if decl is None:
        return
    shape, dtype = spec
    dshape, ddtype = decl
    if not _dims_match(shape, dshape):
        result.add("PTV020",
                   f"output {name!r}: inferred shape {list(shape)} vs "
                   f"declared {list(dshape)}",
                   op_type=op.type, block=block.idx, op_idx=op_idx,
                   var=name)
    try:
        same = _canon(dtype) == _canon(ddtype)
    except TypeError:
        same = dtype == ddtype
    if not same:
        result.add("PTV021",
                   f"output {name!r}: inferred dtype {dtype} vs "
                   f"declared {ddtype}",
                   op_type=op.type, block=block.idx, op_idx=op_idx,
                   var=name)
