"""Per-op test specifications for the registry-wide OpTest sweep.

Reference analogue: the ~557 one-file-per-op tests under
python/paddle/fluid/tests/unittests/ driven by op_test.py. Here one spec
entry per op type drives tests/test_op_sweep.py, which checks:

- the op lowers and executes through the full Program-IR -> Executor ->
  XLA path, matching a direct invocation of its registered lowering
  (`exact`), with finite outputs;
- an optional independent numpy reference (`expect`);
- analytic-vs-numeric gradients for the slots in `grad`
  (get_numeric_gradient discipline, reference op_test.py:47).

Ops that cannot run as a single op (host/RPC loops, control flow needing
sub-blocks, mesh collectives) are in SKIPS with a reason; most have
dedicated tests elsewhere (tests/test_parallel.py, test_ops.py, ...).
The committed OP_TEST_MATRIX.json records the whole registry's status.
"""
from __future__ import annotations

import zlib

import numpy as np

rng = np.random.RandomState(1234)

SPECS = {}
SKIPS = {}


def spec(op, ins=None, attrs=None, grad=(), exact=True, expect=None,
         atol=1e-5, grad_tol=8e-3, is_test=False, finite=True):
    assert op not in SPECS, op
    SPECS[op] = dict(ins=ins or {}, attrs=attrs or {}, grad=tuple(grad),
                     exact=exact, expect=expect, atol=atol,
                     grad_tol=grad_tol, is_test=is_test, finite=finite)
    # Reseed from the op name so the NEXT spec's random draws depend
    # only on its predecessor's name, never on how many values earlier
    # specs consumed — editing one spec's shapes must not perturb every
    # later op's inputs (which turns unrelated kink-adjacent draws into
    # phantom grad-check failures).
    rng.seed(zlib.crc32(op.encode()) & 0x7FFFFFFF)


def _rms_norm_ref(i, a):
    """Gated, grouped RMSNorm in numpy: x silu(gate) over each group's
    root mean square, times the scale."""
    x = i["X"] * i["Gate"] / (1.0 + np.exp(-i["Gate"]))
    g = a["groups"]
    xg = x.reshape(x.shape[:-1] + (g, x.shape[-1] // g))
    xg = xg / np.sqrt((xg * xg).mean(-1, keepdims=True) + a["epsilon"])
    return xg.reshape(x.shape) * i["Scale"]


def skip(op, reason):
    assert op not in SKIPS, op
    SKIPS[op] = reason


def f32(*shape, lo=-1.0, hi=1.0):
    return (rng.uniform(lo, hi, shape)).astype(np.float32)


def pos(*shape, lo=0.1, hi=1.5):
    return rng.uniform(lo, hi, shape).astype(np.float32)


def ints(*shape, lo=0, hi=4):
    return rng.randint(lo, hi, shape).astype(np.int32)


# ---------------------------------------------------------------------------
# unary elementwise: X -> Out. Values chosen away from kinks/domain edges.
# ---------------------------------------------------------------------------
_X = np.array([[0.31, -0.77, 1.42], [0.58, -1.23, 0.09]], np.float32)
_XPOS = np.array([[0.31, 0.77, 1.42], [0.58, 1.23, 0.49]], np.float32)
_XUNIT = np.array([[0.31, -0.77, 0.42], [0.58, -0.23, 0.09]], np.float32)

for _op in ["exp", "tanh", "sigmoid", "sin", "cos", "atan", "erf",
            "softplus", "softsign", "gelu", "logsigmoid", "stanh",
            "square", "swish", "hard_sigmoid", "hard_swish", "elu",
            "selu", "soft_relu", "tanh_shrink"]:
    spec(_op, ins={"X": _X}, grad=["X"])
for _op in ["log", "sqrt", "rsqrt", "reciprocal"]:
    spec(_op, ins={"X": _XPOS}, grad=["X"])
for _op in ["asin", "acos"]:
    spec(_op, ins={"X": _XUNIT}, grad=["X"])
for _op in ["abs", "relu", "relu6", "leaky_relu", "brelu", "hard_shrink",
            "softshrink", "thresholded_relu"]:
    spec(_op, ins={"X": _X}, grad=["X"])
for _op in ["ceil", "floor", "round", "sign"]:
    spec(_op, ins={"X": _X})
spec("pow", ins={"X": _XPOS}, attrs={"factor": 2.0}, grad=["X"])
spec("scale", ins={"X": _X}, attrs={"scale": 2.5, "bias": 0.5},
     grad=["X"], expect=lambda i, a: {"Out": [i["X"] * 2.5 + 0.5]})
spec("clip", ins={"X": _X}, attrs={"min": -0.5, "max": 0.5}, grad=["X"],
     expect=lambda i, a: {"Out": [np.clip(i["X"], -0.5, 0.5)]})
spec("prelu", ins={"X": _X, "Alpha": np.array([0.2], np.float32)},
     attrs={"mode": "all"}, grad=["X"])

# ---------------------------------------------------------------------------
# binary elementwise + comparisons + logical
# ---------------------------------------------------------------------------
_Y = np.array([[0.91, 0.27, -0.62], [1.11, 0.53, -0.88]], np.float32)
for _op, _g in [("elementwise_add", True), ("elementwise_sub", True),
                ("elementwise_mul", True), ("elementwise_max", True),
                ("elementwise_min", True)]:
    spec(_op, ins={"X": _X, "Y": _Y}, grad=["X", "Y"] if _g else ())
spec("elementwise_div", ins={"X": _X, "Y": _Y + 2.0}, grad=["X", "Y"])
spec("elementwise_pow", ins={"X": _XPOS, "Y": _Y}, grad=["X"])
spec("elementwise_mod", ins={"X": ints(2, 3, lo=1, hi=9),
                             "Y": ints(2, 3, lo=2, hi=5)})
spec("elementwise_floordiv", ins={"X": ints(2, 3, lo=1, hi=9),
                                  "Y": ints(2, 3, lo=2, hi=5)})
spec("minus", ins={"X": _X, "Y": _Y}, grad=["X", "Y"],
     expect=lambda i, a: {"Out": [i["X"] - i["Y"]]})
for _op in ["equal", "not_equal", "less_than", "less_equal",
            "greater_than", "greater_equal"]:
    spec(_op, ins={"X": ints(2, 3), "Y": ints(2, 3)})
_B1 = rng.rand(2, 3) > 0.5
_B2 = rng.rand(2, 3) > 0.5
for _op in ["logical_and", "logical_or", "logical_xor"]:
    spec(_op, ins={"X": _B1, "Y": _B2})
spec("logical_not", ins={"X": _B1})

# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
for _op, _arr, _g in [("reduce_sum", _X, True), ("reduce_mean", _X, True),
                      ("reduce_max", _X, True), ("reduce_min", _X, True),
                      ("reduce_prod", _XPOS, True)]:
    spec(_op, ins={"X": _arr}, attrs={"dim": [1], "keep_dim": False},
         grad=["X"] if _g else ())
spec("reduce_all", ins={"X": _B1}, attrs={"dim": [0], "keep_dim": False})
spec("reduce_any", ins={"X": _B1}, attrs={"dim": [0], "keep_dim": False})
spec("sum", ins={"X": [("sum_a", _X), ("sum_b", _Y)]}, grad=["X"],
     expect=lambda i, a: {"Out": [i["sum_a"] + i["sum_b"]]})
spec("mean", ins={"X": _X}, grad=["X"],
     expect=lambda i, a: {"Out": [np.mean(i["X"])]})
spec("cumsum", ins={"X": _X}, attrs={"axis": 1}, grad=["X"],
     expect=lambda i, a: {"Out": [np.cumsum(i["X"], axis=1)]})
spec("l1_norm", ins={"X": _X}, grad=["X"],
     expect=lambda i, a: {"Out": [np.abs(i["X"]).sum()]})
spec("squared_l2_norm", ins={"X": _X}, grad=["X"],
     expect=lambda i, a: {"Out": [(i["X"] ** 2).sum()]})
spec("frobenius_norm" if False else "norm", ins={"X": _X},
     attrs={"axis": 1, "epsilon": 1e-10}, grad=["X"])
spec("l2_normalize", ins={"X": _X}, attrs={"axis": 1}, grad=["X"])
spec("clip_by_norm", ins={"X": _X}, attrs={"max_norm": 1.0}, grad=["X"])

# ---------------------------------------------------------------------------
# matmul family
# ---------------------------------------------------------------------------
spec("mul", ins={"X": f32(2, 3), "Y": f32(3, 4)}, grad=["X", "Y"],
     expect=lambda i, a: {"Out": [i["X"] @ i["Y"]]})
spec("matmul", ins={"X": f32(2, 3), "Y": f32(3, 4)}, grad=["X", "Y"],
     expect=lambda i, a: {"Out": [i["X"] @ i["Y"]]})
spec("matmul_v2", ins={"X": f32(2, 3), "Y": f32(3, 4)}, grad=["X", "Y"])
spec("fc", ins={"Input": f32(2, 3), "W": f32(3, 4), "Bias": f32(4)},
     grad=["Input", "W"])
spec("bilinear_tensor_product",
     ins={"X": f32(2, 3), "Y": f32(2, 4), "Weight": f32(5, 3, 4),
          "Bias": f32(1, 5)}, grad=["X", "Y"])
spec("cos_sim", ins={"X": f32(2, 4), "Y": f32(2, 4)}, grad=["X", "Y"])
spec("conv_shift", ins={"X": f32(2, 5), "Y": f32(2, 3)}, grad=["X", "Y"])
spec("fsp", ins={"X": f32(1, 2, 4, 4), "Y": f32(1, 3, 4, 4)},
     grad=["X", "Y"])

# ---------------------------------------------------------------------------
# shape / tensor manipulation
# ---------------------------------------------------------------------------
spec("reshape", ins={"X": _X}, attrs={"shape": [3, 2]}, grad=["X"])
spec("reshape2", ins={"X": _X}, attrs={"shape": [3, 2]}, grad=["X"])
spec("flatten", ins={"X": f32(2, 3, 4)}, attrs={"axis": 1})
spec("flatten2", ins={"X": f32(2, 3, 4)}, attrs={"axis": 1})
spec("squeeze", ins={"X": f32(2, 1, 3)}, attrs={"axes": [1]})
spec("squeeze2", ins={"X": f32(2, 1, 3)}, attrs={"axes": [1]})
spec("unsqueeze", ins={"X": _X}, attrs={"axes": [1]})
spec("unsqueeze2", ins={"X": _X}, attrs={"axes": [1]})
spec("stack", ins={"X": [("stk_a", _X), ("stk_b", _Y)]},
     attrs={"axis": 0}, grad=["X"])
spec("unstack", ins={"X": f32(2, 3)}, attrs={"axis": 0, "num": 2})
spec("concat", ins={"X": [("cc_a", _X), ("cc_b", _Y)]},
     attrs={"axis": 1}, grad=["X"],
     expect=lambda i, a: {"Out": [np.concatenate(
         [i["cc_a"], i["cc_b"]], axis=1)]})
spec("split", ins={"X": f32(2, 6)}, attrs={"num": 2, "axis": 1},
     grad=["X"])
spec("transpose", ins={"X": _X}, attrs={"axis": [1, 0]}, grad=["X"])
spec("transpose2", ins={"X": _X}, attrs={"axis": [1, 0]}, grad=["X"])
spec("slice", ins={"Input": f32(3, 4)},
     attrs={"axes": [0, 1], "starts": [1, 0], "ends": [3, 2]},
     grad=["Input"])
spec("strided_slice", ins={"Input": f32(3, 6)},
     attrs={"axes": [1], "starts": [0], "ends": [6], "strides": [2]},
     grad=["Input"])
spec("expand", ins={"X": f32(1, 3)}, attrs={"expand_times": [2, 1]},
     grad=["X"])
spec("expand_as", ins={"X": f32(1, 3), "target_tensor": f32(2, 3)})
spec("pad", ins={"X": _X}, attrs={"paddings": [1, 1, 0, 2],
                                  "pad_value": 0.0}, grad=["X"])
spec("pad2d", ins={"X": f32(1, 2, 3, 3)},
     attrs={"paddings": [1, 1, 1, 1], "mode": "constant"}, grad=["X"])
spec("pad_constant_like", ins={"X": f32(3, 4), "Y": f32(2, 3)},
     grad=["Y"])
spec("reverse", ins={"X": _X}, attrs={"axis": [1]}, grad=["X"])
spec("gather", ins={"X": f32(4, 3), "Index": ints(2, lo=0, hi=4)},
     grad=["X"])
spec("gather_nd", ins={"X": f32(3, 4),
                       "Index": np.array([[0, 1], [2, 3]], np.int32)},
     grad=["X"])
spec("scatter", ins={"X": f32(4, 3), "Ids": np.array([1, 3], np.int32),
                     "Updates": f32(2, 3)}, attrs={"overwrite": True})
spec("scatter_nd_add",
     ins={"X": f32(4, 3), "Index": np.array([[1], [3]], np.int32),
          "Updates": f32(2, 3)}, grad=["X", "Updates"])
spec("cast", ins={"X": _X}, attrs={"out_dtype": "float32"}, grad=["X"])
spec("assign", ins={"X": _X}, grad=["X"])
spec("shape", ins={"Input": f32(2, 5)})
spec("size", ins={"Input": f32(2, 5)})
spec("diag", ins={"Diagonal": f32(3)})
spec("eye", attrs={"num_rows": 3, "num_columns": 3, "dtype": "float32"})
spec("linspace", ins={"Start": np.array([0.0], np.float32),
                      "Stop": np.array([1.0], np.float32)},
     attrs={"num": 5})   # count must be static under XLA
spec("range", ins={"Start": np.array([0.0], np.float32),
                   "End": np.array([5.0], np.float32),
                   "Step": np.array([1.0], np.float32)},
     attrs={"static_len": 5})  # length must be static under XLA
spec("fill_constant", attrs={"shape": [2, 3], "value": 1.5,
                             "dtype": "float32"},
     expect=lambda i, a: {"Out": [np.full((2, 3), 1.5, np.float32)]})
spec("fill_any_like", ins={"X": _X}, attrs={"value": 2.0})
spec("fill_zeros_like", ins={"X": _X},
     expect=lambda i, a: {"Out": [np.zeros_like(i["X"])]})
spec("fill", attrs={"shape": [2, 2], "value": [3.0, 3.0, 3.0, 3.0],
                    "dtype": "float32"})
spec("fill_constant_batch_size_like", ins={"Input": f32(4, 3)},
     attrs={"shape": [-1, 2], "value": 0.5, "dtype": "float32"})
spec("increment", ins={"X": np.array([1.0], np.float32)},
     attrs={"step": 2.0},
     expect=lambda i, a: {"Out": [np.array([3.0], np.float32)]})
spec("one_hot", ins={"X": np.array([[1], [3]], np.int32)},
     attrs={"depth": 4})
spec("one_hot_v2", ins={"X": np.array([1, 3], np.int32)},
     attrs={"depth": 4})
spec("shard_index", ins={"X": np.array([[1], [5]], np.int64)},
     attrs={"index_num": 8, "nshards": 2, "shard_id": 0,
            "ignore_value": -1})
spec("where", ins={"Condition": _B1})
spec("unique", ins={"X": np.array([3, 1, 3, 2], np.int32)})
spec("unique_with_counts", ins={"X": np.array([3, 1, 3, 2], np.int32)})
spec("top_k", ins={"X": f32(2, 5)}, attrs={"k": 2})
spec("arg_max", ins={"X": f32(2, 5)}, attrs={"axis": 1})
spec("arg_min", ins={"X": f32(2, 5)}, attrs={"axis": 1})
spec("argsort", ins={"X": f32(2, 5)}, attrs={"axis": 1})
spec("is_empty", ins={"X": f32(2)})
spec("isfinite", ins={"X": _X})
spec("has_inf", ins={"X": _X})
spec("has_nan", ins={"X": _X})
spec("multiplex", ins={"X": [("mpx_a", f32(2, 3)), ("mpx_b", f32(2, 3))],
                       "Ids": np.array([[1], [0]], np.int32)})
spec("assign_value", attrs={"shape": [2, 2],
                            "values": [1.0, 2.0, 3.0, 4.0],
                            "dtype": "float32"})
spec("lod_reset", ins={"X": f32(4, 2),
                       "Y": np.array([0, 2, 4], np.int32)})
spec("sequence_mask", ins={"X": np.array([1, 3], np.int64)},
     attrs={"maxlen": 4})
spec("space_to_depth", ins={"X": f32(1, 2, 4, 4)}, attrs={"blocksize": 2},
     grad=["X"])
spec("pixel_shuffle", ins={"X": f32(1, 4, 2, 2)},
     attrs={"upscale_factor": 2}, grad=["X"])
spec("shuffle_channel", ins={"X": f32(1, 4, 2, 2)}, attrs={"group": 2},
     grad=["X"])

# ---------------------------------------------------------------------------
# embedding / lookup
# ---------------------------------------------------------------------------
spec("lookup_table", ins={"W": f32(6, 3),
                          "Ids": np.array([[1], [4]], np.int64)},
     grad=["W"])
spec("lookup_table_v2", ins={"W": f32(6, 3),
                             "Ids": np.array([1, 4], np.int64)},
     grad=["W"])

# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
_PROB = np.array([[0.2, 0.5, 0.3], [0.6, 0.1, 0.3]], np.float32)
_LBL = np.array([[1], [0]], np.int64)
spec("cross_entropy", ins={"X": _PROB, "Label": _LBL}, grad=["X"])
spec("cross_entropy2", ins={"X": _PROB, "Label": _LBL}, grad=["X"])
spec("bpr_loss", ins={"X": _PROB, "Label": _LBL}, grad=["X"])
spec("softmax_with_cross_entropy", ins={"Logits": f32(2, 4),
                                        "Label": _LBL}, grad=["Logits"])
spec("sigmoid_cross_entropy_with_logits",
     ins={"X": f32(2, 3), "Label": rng.rand(2, 3).astype(np.float32)},
     grad=["X"])
spec("hinge_loss", ins={"Logits": np.array([[0.3], [-0.4]], np.float32),
                        "Labels": np.array([[1.0], [0.0]], np.float32)},
     grad=["Logits"])  # values keep 1 -/+ x away from the hinge kink
spec("huber_loss", ins={"X": f32(2, 1), "Y": f32(2, 1)},
     attrs={"delta": 1.0}, grad=["X"])
spec("kldiv_loss", ins={"X": np.log(_PROB), "Target": _PROB},
     attrs={"reduction": "mean"}, grad=["X"])
spec("log_loss", ins={"Predicted": _PROB[:, :1] * 0.8 + 0.1,
                      "Labels": np.array([[1.0], [0.0]], np.float32)},
     attrs={"epsilon": 1e-4}, grad=["Predicted"])
spec("mse_loss", ins={"X": f32(2, 3), "Y": f32(2, 3)}, grad=["X"])
spec("rank_loss", ins={"Label": np.array([[1.0], [0.0]], np.float32),
                       "Left": f32(2, 1), "Right": f32(2, 1)},
     grad=["Left", "Right"])
spec("margin_rank_loss", ins={"Label": np.array([[1.0], [-1.0]],
                                                np.float32),
                              "X1": f32(2, 1), "X2": f32(2, 1)},
     attrs={"margin": 0.1}, grad=["X1", "X2"])
spec("smooth_l1_loss", ins={"X": f32(2, 3), "Y": f32(2, 3)}, grad=["X"])
spec("modified_huber_loss",
     ins={"X": f32(2, 1), "Y": np.array([[1.0], [0.0]], np.float32)},
     grad=["X"])
spec("squared_l2_distance", ins={"X": f32(2, 3), "Y": f32(2, 3)},
     grad=["X"])
spec("dice_loss", ins={"X": _PROB[:, :1],
                       "Label": np.array([[1], [0]], np.int64)})
spec("npair_loss", ins={"Anchor": f32(2, 4), "Positive": f32(2, 4),
                        "Labels": np.array([0, 1], np.int64)},
     attrs={"l2_reg": 0.002}, grad=["Anchor", "Positive"])
spec("center_loss",
     ins={"X": f32(2, 4), "Label": np.array([[0], [1]], np.int64),
          "Centers": f32(3, 4),
          "CenterUpdateRate": np.array([0.1], np.float32)},
     attrs={"cluster_num": 3, "need_update": True}, grad=["X"])
spec("teacher_student_sigmoid_loss",
     ins={"X": f32(2, 1), "Label": np.array([[1.0], [0.0]], np.float32)},
     grad=["X"])
spec("sigmoid_focal_loss",
     ins={"X": f32(2, 3), "Label": np.array([[1], [0]], np.int32),
          "FgNum": np.array([1], np.int32)},
     attrs={"gamma": 2.0, "alpha": 0.25}, grad=["X"])
spec("label_smooth", ins={"X": _PROB}, attrs={"epsilon": 0.1},
     grad=["X"])
spec("log_softmax", ins={"X": f32(2, 4)}, grad=["X"])
spec("softmax", ins={"X": f32(2, 4)}, grad=["X"])

# ---------------------------------------------------------------------------
# optimizer update ops (output check only; inplace semantics)
# ---------------------------------------------------------------------------
_P, _G = f32(3, 2), f32(3, 2)
_LR = np.array([0.1], np.float32)
spec("sgd", ins={"Param": _P, "Grad": _G, "LearningRate": _LR},
     expect=lambda i, a: {"ParamOut": [i["Param"] - 0.1 * i["Grad"]]})
spec("momentum", ins={"Param": _P, "Grad": _G, "Velocity": f32(3, 2),
                      "LearningRate": _LR}, attrs={"mu": 0.9})
spec("adam", ins={"Param": _P, "Grad": _G, "Moment1": f32(3, 2),
                  "Moment2": pos(3, 2), "LearningRate": _LR,
                  "Beta1Pow": np.array([0.9], np.float32),
                  "Beta2Pow": np.array([0.999], np.float32)},
     attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8})
spec("adamw", ins={"Param": _P, "Grad": _G, "Moment1": f32(3, 2),
                   "Moment2": pos(3, 2), "LearningRate": _LR,
                   "Beta1Pow": np.array([0.9], np.float32),
                   "Beta2Pow": np.array([0.999], np.float32)},
     attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
            "coeff": 0.01})
spec("adamax", ins={"Param": _P, "Grad": _G, "Moment": f32(3, 2),
                    "InfNorm": pos(3, 2), "LearningRate": _LR,
                    "Beta1Pow": np.array([0.9], np.float32)},
     attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8})
spec("adagrad", ins={"Param": _P, "Grad": _G, "Moment": pos(3, 2),
                     "LearningRate": _LR}, attrs={"epsilon": 1e-6})
spec("adadelta", ins={"Param": _P, "Grad": _G,
                      "AvgSquaredGrad": pos(3, 2),
                      "AvgSquaredUpdate": pos(3, 2)},
     attrs={"rho": 0.95, "epsilon": 1e-6})
spec("decayed_adagrad", ins={"Param": _P, "Grad": _G,
                             "Moment": pos(3, 2), "LearningRate": _LR},
     attrs={"decay": 0.95, "epsilon": 1e-6})
spec("rmsprop", ins={"Param": _P, "Grad": _G, "MeanSquare": pos(3, 2),
                     "Moment": f32(3, 2), "LearningRate": _LR,
                     "MeanGrad": f32(3, 2)},
     attrs={"decay": 0.9, "epsilon": 1e-6, "momentum": 0.9})
spec("ftrl", ins={"Param": _P, "Grad": _G, "SquaredAccumulator": pos(3, 2),
                  "LinearAccumulator": f32(3, 2), "LearningRate": _LR},
     attrs={"l1": 0.01, "l2": 0.01, "lr_power": -0.5})
spec("lamb", ins={"Param": _P, "Grad": _G, "Moment1": f32(3, 2),
                  "Moment2": pos(3, 2), "LearningRate": _LR,
                  "Beta1Pow": np.array([0.9], np.float32),
                  "Beta2Pow": np.array([0.999], np.float32)},
     attrs={"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6,
            "weight_decay": 0.01})
spec("lars_momentum", ins={"Param": _P, "Grad": _G,
                           "Velocity": f32(3, 2), "LearningRate": _LR},
     attrs={"mu": 0.9, "lars_coeff": 0.001, "lars_weight_decay": 0.0005})
spec("proximal_gd", ins={"Param": _P, "Grad": _G, "LearningRate": _LR},
     attrs={"l1": 0.01, "l2": 0.01})
spec("proximal_adagrad", ins={"Param": _P, "Grad": _G,
                              "Moment": pos(3, 2), "LearningRate": _LR},
     attrs={"l1": 0.01, "l2": 0.01, "epsilon": 1e-6})
spec("dpsgd", ins={"Param": _P, "Grad": _G, "LearningRate": _LR},
     attrs={"batch_size": 2.0, "sigma": 0.0, "clip": 10.0}, exact=False)
spec("dgc_momentum", ins={"Param": _P, "Grad": _G, "Velocity": f32(3, 2),
                          "LearningRate": _LR,
                          "current_step": np.array([0.0], np.float32)},
     attrs={"mu": 0.9, "rampup_begin_step": 100.0})

# ---------------------------------------------------------------------------
# random / init ops (distribution checks only)
# ---------------------------------------------------------------------------
spec("uniform_random", attrs={"shape": [4, 3], "min": -1.0, "max": 1.0,
                              "dtype": "float32"}, exact=False)
spec("gaussian_random", attrs={"shape": [4, 3], "mean": 0.0, "std": 1.0,
                               "dtype": "float32"}, exact=False)
spec("truncated_gaussian_random",
     attrs={"shape": [4, 3], "mean": 0.0, "std": 1.0,
            "dtype": "float32"}, exact=False)
spec("uniform_random_batch_size_like", ins={"Input": f32(4, 3)},
     attrs={"shape": [-1, 2], "min": -1.0, "max": 1.0,
            "dtype": "float32"}, exact=False)
spec("gaussian_random_batch_size_like", ins={"Input": f32(4, 3)},
     attrs={"shape": [-1, 2], "mean": 0.0, "std": 1.0,
            "dtype": "float32"}, exact=False)
spec("randint", attrs={"shape": [4], "low": 0, "high": 5}, exact=False)
spec("sampling_id", ins={"X": _PROB}, exact=False)
spec("random_crop", ins={"X": f32(1, 3, 5, 5), "Seed": np.array([7],
                                                                np.int64)},
     attrs={"shape": [3, 3, 3]}, exact=False)
spec("dropout", ins={"X": f32(2, 3)},
     attrs={"dropout_prob": 0.5, "is_test": True}, is_test=True,
     expect=lambda i, a: {"Out": [i["X"] * 0.5]})

# ---------------------------------------------------------------------------
# skips: ops that cannot run as an isolated single op
# ---------------------------------------------------------------------------
for _op in ["feed", "fetch"]:
    skip(_op, "executor-internal feed/fetch plumbing; exercised by every "
              "exe.run test")
for _op in ["while", "conditional_block", "recurrent",
            "recompute_segment"]:
    skip(_op, "needs a sub-block program; covered in tests/test_ops.py / "
              "test_rnn.py / test_parallel.py")
for _op in ["select_input", "merge_lod_tensor", "split_lod_tensor",
            "array_to_lod_tensor", "lod_tensor_to_array",
            "write_to_array", "read_from_array", "tensor_array_to_tensor",
            "lod_array_length", "lod_rank_table", "max_sequence_len",
            "shrink_rnn_memory", "rnn_memory_helper",
            "reorder_lod_tensor_by_rank", "beam_search",
            "beam_search_decode", "beam_reorder", "gather_tree"]:
    skip(_op, "LoDTensorArray / decode-loop op; covered via "
              "layers.control_flow and rnn decode tests")
for _op in ["listen_and_serv", "send", "recv", "prefetch",
            "fetch_barrier", "send_barrier", "gen_nccl_id",
            "c_gen_nccl_id", "c_comm_init", "c_comm_init_all",
            "checkpoint_notify", "geo_sgd_send", "ref_by_trainer_id",
            "distributed_lookup_table", "lookup_sparse_table",
            "split_ids", "merge_ids", "split_byref",
            "fl_listen_and_serv" if False else "delete_var"]:
    skip(_op, "host-side PS/RPC runtime op; covered in "
              "tests/test_distributed.py")
for _op in ["c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
            "c_allreduce_prod", "c_allgather", "c_reducescatter",
            "c_broadcast", "c_sync_calc_stream", "c_sync_comm_stream",
            "allreduce", "broadcast", "shard_hint", "ring_attention",
            "ulysses_attention", "c_alltoall", "moe_ffn",  # op bodies exercised in
            # tests/test_parallel.py (c_alltoall, seq-parallel op) and
            # tests/test_kernels.py (sharded fns)
            "sync_batch_norm"]:
    skip(_op, "mesh collective; covered in tests/test_parallel.py on the "
              "8-device CPU mesh")
for _op in ["save", "save_combine", "load", "load_combine"]:
    skip(_op, "host IO op; covered by tests/test_models.py save/load and "
              "test_jit_and_extras.py")
skip("paged_attention", "stateful decode op over externally-allocated "
     "KV block pools + block table; the op itself (Pallas read against "
     "a gather reference, scatter write) is covered in "
     "tests/test_paged_attention_op.py, token-exact parity vs the serial "
     "reference in tests/test_generation.py and the allocator in "
     "tests/test_kv_blocks.py")
skip("mamba2_mixer", "stateful serving op over per-slot convolution and "
     "SSM state with start/n_valid feeds; chunks, single steps, muted "
     "and partly valid rows against the plain full-sequence scan in "
     "tests/test_hybrid_model.py")
skip("latent_moe", "top-k routed experts with a held share and an int32 "
     "probe; against the plain reference layer, the four shares adding "
     "up (also over an `ep` mesh axis) and a one-expert router in "
     "tests/test_hybrid_model.py")
skip("print", "host-side debug print (io_callback); side-effect only")
skip("py_func", "wraps arbitrary user Python; covered in "
                "test_jit_and_extras.py")
skip("get_places", "host device-enumeration helper")
skip("fake_init", "PS-mode placeholder init; no computation")
skip("grad::generic", "internal vjp grad dispatcher; exercised by every "
                      "check_grad in this sweep")
skip("fused_elementwise", "emitted only by the level-2 fusion pass; "
                          "bit-exact replay covered by "
                          "tests/test_graph_passes.py parity sweeps")
skip("split_selected_rows", "SelectedRows compat view; covered in "
                            "test_parity_ops.py")
skip("merge_selected_rows", "SelectedRows compat view; covered in "
                            "test_parity_ops.py")
skip("get_tensor_from_selected_rows", "SelectedRows compat view")
skip("coalesce_tensor", "aliasing buffer fusion helper; XLA owns buffer "
                        "layout on TPU (no-op lowering)")

# ===========================================================================
# batch 2: conv/pool/norm, interp, sequence, RNN, detection, quant, metrics
# ===========================================================================

# --- conv / pool -----------------------------------------------------------
_IMG = f32(1, 2, 5, 5)
spec("conv2d", ins={"Input": _IMG, "Filter": f32(3, 2, 3, 3)},
     attrs={"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
            "groups": 1}, grad=["Input", "Filter"])
spec("depthwise_conv2d", ins={"Input": _IMG, "Filter": f32(2, 1, 3, 3)},
     attrs={"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
            "groups": 2}, grad=["Input", "Filter"])
spec("conv2d_transpose", ins={"Input": f32(1, 2, 3, 3),
                              "Filter": f32(2, 3, 3, 3)},
     attrs={"strides": [2, 2], "paddings": [0, 0], "dilations": [1, 1],
            "groups": 1}, grad=["Input", "Filter"])
spec("depthwise_conv2d_transpose",
     ins={"Input": f32(1, 2, 3, 3), "Filter": f32(2, 1, 3, 3)},
     attrs={"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
            "groups": 2}, grad=["Input"])
spec("conv3d", ins={"Input": f32(1, 2, 4, 4, 4),
                    "Filter": f32(3, 2, 3, 3, 3)},
     attrs={"strides": [1, 1, 1], "paddings": [1, 1, 1],
            "dilations": [1, 1, 1], "groups": 1}, grad=["Input"])
spec("conv3d_transpose", ins={"Input": f32(1, 2, 3, 3, 3),
                              "Filter": f32(2, 3, 3, 3, 3)},
     attrs={"strides": [1, 1, 1], "paddings": [0, 0, 0],
            "dilations": [1, 1, 1], "groups": 1}, grad=["Input"])
spec("pool2d", ins={"X": f32(1, 2, 4, 4)},
     attrs={"pooling_type": "avg", "ksize": [2, 2], "strides": [2, 2],
            "paddings": [0, 0]}, grad=["X"])
spec("pool3d", ins={"X": f32(1, 2, 4, 4, 4)},
     attrs={"pooling_type": "max", "ksize": [2, 2, 2],
            "strides": [2, 2, 2], "paddings": [0, 0, 0]}, grad=["X"])
# well-separated values: numeric-grad deltas must not flip a window max
_POOLX = (np.arange(32, dtype=np.float32).reshape(1, 2, 4, 4) * 0.137
          )[:, :, ::-1]
spec("max_pool2d_with_index", ins={"X": _POOLX.copy()},
     attrs={"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]},
     grad=["X"])
spec("max_pool3d_with_index", ins={"X": f32(1, 2, 4, 4, 4)},
     attrs={"ksize": [2, 2, 2], "strides": [2, 2, 2],
            "paddings": [0, 0, 0]})
spec("unpool", ins={"X": f32(1, 2, 2, 2),
                    "Indices": np.array(
                        [[[[0, 3], [8, 11]], [[0, 3], [8, 11]]]],
                        np.int32)},
     attrs={"unpooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
            "paddings": [0, 0]})
spec("spp", ins={"X": f32(1, 2, 4, 4)},
     attrs={"pyramid_height": 2, "pooling_type": "max"})
spec("unfold", ins={"X": f32(1, 2, 4, 4)},
     attrs={"kernel_sizes": [2, 2], "strides": [1, 1],
            "paddings": [0, 0, 0, 0], "dilations": [1, 1]}, grad=["X"])
spec("maxout", ins={"X": f32(1, 4, 3, 3)}, attrs={"groups": 2},
     grad=["X"])

# --- norms -----------------------------------------------------------------
_BN = dict(ins={"X": f32(2, 3, 4, 4), "Scale": pos(3), "Bias": f32(3),
                "Mean": f32(3), "Variance": pos(3)},
           attrs={"is_test": True, "epsilon": 1e-5, "momentum": 0.9})
spec("batch_norm", is_test=True, **_BN)
spec("rms_norm", ins={"X": f32(2, 3, 8), "Scale": pos(8), "Gate": f32(2, 3, 8)},
     attrs={"epsilon": 1e-5, "groups": 2}, grad=["X", "Scale", "Gate"],
     expect=lambda i, a: {"Out": [_rms_norm_ref(i, a)]})
spec("layer_norm", ins={"X": f32(2, 6), "Scale": pos(6), "Bias": f32(6)},
     attrs={"begin_norm_axis": 1, "epsilon": 1e-5},
     grad=["X", "Scale", "Bias"])
spec("instance_norm", ins={"X": f32(2, 3, 4, 4), "Scale": pos(3),
                           "Bias": f32(3)},
     attrs={"epsilon": 1e-5}, grad=["X"])
spec("group_norm", ins={"X": f32(2, 4, 3, 3), "Scale": pos(4),
                        "Bias": f32(4)},
     attrs={"groups": 2, "epsilon": 1e-5}, grad=["X"])
spec("data_norm", ins={"X": f32(2, 3), "BatchSize": pos(3, lo=4, hi=8),
                       "BatchSum": f32(3), "BatchSquareSum": pos(3,
                                                                lo=4,
                                                                hi=8)})
spec("lrn", ins={"X": f32(1, 4, 3, 3)},
     attrs={"n": 4, "k": 1.0, "alpha": 1e-4, "beta": 0.75}, grad=["X"])
spec("spectral_norm", ins={"Weight": f32(6, 4), "U": f32(6),
                           "V": f32(4)},
     attrs={"power_iters": 5, "eps": 1e-12})
spec("affine_channel", ins={"X": f32(1, 3, 2, 2), "Scale": pos(3),
                            "Bias": f32(3)}, grad=["X"])
spec("add_position_encoding", ins={"X": f32(2, 4, 6)},
     attrs={"alpha": 1.0, "beta": 1.0}, grad=["X"])
spec("temporal_shift", ins={"X": f32(4, 4, 2, 2)},
     attrs={"seg_num": 2, "shift_ratio": 0.25}, grad=["X"])

# --- interpolation / warping ----------------------------------------------
spec("bilinear_interp", ins={"X": f32(1, 2, 3, 3)},
     attrs={"out_h": 6, "out_w": 6, "align_corners": False},
     grad=["X"])
spec("nearest_interp", ins={"X": f32(1, 2, 3, 3)},
     attrs={"out_h": 6, "out_w": 6, "align_corners": False})
spec("trilinear_interp", ins={"X": f32(1, 2, 3, 3, 3)},
     attrs={"out_d": 6, "out_h": 6, "out_w": 6,
            "align_corners": False})
spec("affine_grid", ins={"Theta": f32(1, 2, 3)},
     attrs={"output_shape": [1, 1, 4, 4]}, grad=["Theta"])
spec("grid_sampler", ins={"X": f32(1, 2, 4, 4),
                          "Grid": f32(1, 3, 3, 2, lo=-0.9, hi=0.9)},
     grad=["X"])
spec("crop", ins={"X": f32(4, 6)}, attrs={"shape": [2, 3],
                                          "offsets": [1, 2]},
     grad=["X"])
spec("crop_tensor", ins={"X": f32(4, 6)},
     attrs={"shape": [2, 3], "offsets": [1, 2]}, grad=["X"])
spec("square_error_cost", ins={"X": f32(2, 3), "Y": f32(2, 3)},
     grad=["X", "Y"],
     expect=lambda i, a: {"Out": [(i["X__in"] - i["Y__in"]) ** 2]
                          } if False else {
         "Out": [(i["X__in"] - i["Y__in"]) ** 2]})

# --- sequence (padded + lengths design) ------------------------------------
_SEQ = f32(2, 4, 3)
_LENS = np.array([3, 4], np.int64)
spec("sequence_pool", ins={"X": _SEQ, "Lengths": _LENS},
     attrs={"pooltype": "SUM"}, grad=["X"])
spec("sequence_softmax", ins={"X": f32(2, 4), "Lengths": _LENS},
     grad=["X"])
spec("sequence_reverse", ins={"X": _SEQ, "Lengths": _LENS}, grad=["X"])
spec("sequence_pad", ins={"X": _SEQ,
                          "PadValue": np.zeros((1,), np.float32)},
     attrs={"padded_length": 5})
spec("sequence_unpad", ins={"X": _SEQ, "Length": _LENS})
spec("sequence_expand", ins={"X": f32(2, 3), "Y": f32(4, 3)},
     attrs={"ref_level": 0})
spec("sequence_expand_as", ins={"X": f32(2, 3), "Y": f32(2, 3)})
spec("sequence_concat", ins={"X": [("sqc_a", _SEQ), ("sqc_b",
                                                     f32(2, 4, 3))]},
     grad=["X"])  # entries must be distinct buffers for the numeric pass
spec("sequence_conv", ins={"X": _SEQ, "Filter": f32(9, 4)},
     attrs={"contextLength": 3, "contextStart": -1},
     grad=["X", "Filter"])
spec("sequence_enumerate",
     ins={"X": np.array([[1, 2, 3, 4]], np.int64)},
     attrs={"win_size": 2, "pad_value": 0})
spec("sequence_erase", ins={"X": np.array([[1, 2, 0, 3]], np.int64)},
     attrs={"tokens": [0]})
spec("sequence_reshape", ins={"X": f32(2, 4, 6)}, attrs={"new_dim": 8})
spec("sequence_scatter",
     ins={"X": f32(2, 6), "Ids": np.array([[1, 3], [0, 2]], np.int64),
          "Updates": f32(2, 2)})
spec("sequence_slice", ins={"X": _SEQ,
                            "Offset": np.array([[0], [1]], np.int64),
                            "Length": np.array([[2], [2]], np.int64)})
spec("sequence_topk_avg_pooling",
     ins={"X": f32(1, 1, 4, 4), "ROW": f32(1, 4, 1),
          "COLUMN": f32(1, 4, 1)},
     attrs={"topks": [1, 2], "channel_num": 1})
spec("im2sequence", ins={"X": f32(1, 2, 4, 4)},
     attrs={"kernels": [2, 2], "strides": [2, 2],
            "paddings": [0, 0, 0, 0]})
spec("row_conv", ins={"X": f32(2, 5, 3), "Filter": f32(2, 3)},
     grad=["X", "Filter"])
spec("match_matrix_tensor", ins={"X": f32(1, 3, 4), "Y": f32(1, 5, 4),
                                 "W": f32(4, 2, 4)},
     attrs={"dim_t": 2})
spec("var_conv_2d", ins={"X": f32(1, 2, 4, 4), "W": f32(3, 2, 3, 3)},
     attrs={"OutputChannel": 3, "InputChannel": 2, "KernelH": 3,
            "KernelW": 3, "StrideH": 1, "StrideW": 1})
# batch 0: branching tree (1->2,3; 2->4,5) exercises sibling
# index/count weights at depth 2; batch 1: chain whose post-(0,0) edge
# must be IGNORED (construct_tree break semantics)
spec("tree_conv", ins={"NodesVector": f32(2, 6, 3),
                       "EdgeSet": np.array(
                           [[[1, 2], [1, 3], [2, 4], [2, 5], [0, 0]],
                            [[1, 2], [2, 3], [3, 4], [0, 0], [5, 6]]],
                           np.int32),
                       "Filter": f32(3, 3, 2, 2)},
     attrs={"max_depth": 3}, grad=["NodesVector", "Filter"])
spec("filter_by_instag",
     ins={"Ins": f32(3, 2), "Ins_tag": np.array([1, 2, 1], np.int64),
          "Filter_tag": np.array([1], np.int64)},
     attrs={"is_lod": False})
# rectangular A!=B plus two indexes: exercises the greedy
# row/column-retirement order and the cross-index mask union
spec("similarity_focus", ins={"X": f32(2, 3, 4, 5)},
     attrs={"axis": 1, "indexes": [0, 2]})
# no grad check: the reference injects the CVM input as the show/click
# column gradients (cvm_op.h CvmGradComputeKernel) — intentionally NOT
# the numeric derivative of the forward's log transform
spec("cvm", ins={"X": pos(2, 4), "CVM": f32(2, 2)},
     attrs={"use_cvm": True})
# rows of 5 int64 lanes = 40 bytes: exercises BOTH the 32-byte stripe
# accumulator and the 8-byte tail path of XXH64
spec("hash", ins={"X": np.array([[1, 2, 3, 4, 5],
                                 [3, 4, 5, 6, 7]], np.int64)},
     attrs={"num_hash": 2, "mod_by": 1000})

# --- RNN family ------------------------------------------------------------
spec("gru", ins={"Input": f32(2, 4, 9), "Weight": f32(3, 9),
                 "Bias": f32(1, 9)},
     attrs={"activation": "tanh", "gate_activation": "sigmoid"},
     grad=["Input"])
spec("gru_unit", ins={"Input": f32(2, 9), "HiddenPrev": f32(2, 3),
                      "Weight": f32(3, 9), "Bias": f32(1, 9)},
     grad=["Input"])
spec("lstm", ins={"Input": f32(2, 4, 12), "Weight": f32(3, 12),
                  "Bias": f32(1, 12)},
     attrs={"use_peepholes": False}, grad=["Input"])
spec("lstm_unit", ins={"X": f32(2, 12), "C_prev": f32(2, 3)},
     grad=["X"])
spec("lstmp", ins={"Input": f32(2, 4, 12), "Weight": f32(2, 12),
                   "ProjWeight": f32(3, 2), "Bias": f32(1, 12)},
     grad=["Input"])
spec("cudnn_lstm",
     ins={"Input": f32(5, 2, 3), "InitH": np.zeros((1, 2, 4), np.float32),
          "InitC": np.zeros((1, 2, 4), np.float32),
          "W": f32(4 * 4 * 3 + 4 * 4 * 4 + 8 * 4) * 0.1},
     attrs={"hidden_size": 4, "num_layers": 1}, grad=["Input"])
spec("cudnn_gru",
     ins={"Input": f32(5, 2, 3), "InitH": np.zeros((1, 2, 4), np.float32),
          "W": f32(3 * 4 * 3 + 3 * 4 * 4 + 6 * 4) * 0.1},
     attrs={"hidden_size": 4, "num_layers": 1})
spec("attention_lstm",
     ins={"X": f32(2, 4, 6), "C0": f32(2, 3),
          "AttentionWeight": f32(9, 1),
          "LSTMWeight": f32(9, 12), "LSTMBias": f32(1, 12)})
spec("multihead_matmul",
     ins={"Q": f32(2, 4, 6), "K": f32(2, 4, 6), "V": f32(2, 4, 6),
          "BiasQ": f32(6), "BiasK": f32(6), "BiasV": f32(6),
          "BiasQK": f32(2, 2, 4, 4)},
     attrs={"head_number": 2, "alpha": 0.4})
spec("fused_elemwise_activation",
     ins={"X": f32(2, 3), "Y": f32(2, 3)},
     attrs={"functor_list": ["elementwise_add", "relu"]}, grad=["X"])
spec("fused_embedding_seq_pool",
     ins={"W": f32(6, 3), "Ids": np.array([[[1], [4]], [[2], [0]]],
                                          np.int64)},
     attrs={"combiner": "sum"}, grad=["W"])
spec("fused_fc_elementwise_layernorm",
     ins={"X": f32(2, 3), "W": f32(3, 4), "Y": f32(2, 4),
          "Scale": pos(4), "Bias1": f32(4)},
     attrs={"epsilon": 1e-5})
spec("fusion_gru", ins={"X": f32(2, 4, 3), "WeightX": f32(3, 9),
                        "WeightH": f32(3, 9), "Bias": f32(1, 9)},
     attrs={"activation": "tanh", "gate_activation": "sigmoid"})
spec("fusion_lstm", ins={"X": f32(2, 4, 3), "WeightX": f32(3, 12),
                         "WeightH": f32(3, 12), "Bias": f32(1, 12)})
spec("fusion_repeated_fc_relu",
     ins={"X": f32(2, 3), "W": [("frfr_w1", f32(3, 4)),
                                ("frfr_w2", f32(4, 2))],
          "Bias": [("frfr_b1", f32(4)), ("frfr_b2", f32(2))]})
spec("fusion_seqconv_eltadd_relu",
     ins={"X": f32(2, 5, 3), "Filter": f32(9, 4), "Bias": f32(4)},
     attrs={"contextLength": 3, "contextStart": -1})
spec("fusion_seqexpand_concat_fc",
     ins={"X": [("fsecf_a", f32(2, 4, 3)), ("fsecf_b", f32(2, 3))],
          "FCWeight": f32(6, 5)},
     attrs={"fc_activation": "relu"})
spec("fusion_seqpool_concat",
     ins={"X": [("fspc_a", f32(2, 4, 3)), ("fspc_b", f32(2, 4, 3))]},
     attrs={"pooltype": "SUM"})
spec("fusion_squared_mat_sub", ins={"X": f32(2, 3), "Y": f32(3, 4)},
     attrs={"scalar": 1.0})
spec("fusion_transpose_flatten_concat",
     ins={"X": [("ftfc_a", f32(2, 3, 4)), ("ftfc_b", f32(2, 3, 4))]},
     attrs={"trans_axis": [0, 2, 1], "flatten_axis": 1,
            "concat_axis": 1})

# --- CTC / CRF / metrics ---------------------------------------------------
spec("warpctc", ins={"Logits": f32(1, 4, 3),
                     "Label": np.array([[1, 2]], np.int64)},
     attrs={"blank": 0}, grad=["Logits"])
spec("ctc_align", ins={"Input": np.array([[1, 1, 0, 2]], np.int32)},
     attrs={"blank": 0})
spec("edit_distance", ins={"Hyps": np.array([[1, 2, 3, -1]], np.int64),
                           "Refs": np.array([[1, 3, 3, -1]], np.int64)},
     attrs={"normalized": False})
spec("linear_chain_crf",
     ins={"Emission": f32(2, 4, 3), "Transition": f32(5, 3),
          "Label": ints(2, 4, lo=0, hi=3).astype(np.int64)},
     grad=["Emission"])
spec("crf_decoding", ins={"Emission": f32(1, 3, 2),
                          "Transition": np.zeros((4, 2), np.float32)})
spec("accuracy", ins={"Out": _PROB,
                      "Indices": np.array([[1], [0]], np.int64),
                      "Label": _LBL})
spec("mean_iou", ins={"Predictions": ints(2, 3, lo=0, hi=3),
                      "Labels": ints(2, 3, lo=0, hi=3)},
     attrs={"num_classes": 3})
spec("auc", ins={"Predict": _PROB[:, :2],
                 "Label": np.array([[1], [0]], np.int64),
                 "StatPos": np.zeros(201, np.int64),
                 "StatNeg": np.zeros(201, np.int64)},
     attrs={"num_thresholds": 200})
spec("precision_recall",
     ins={"MaxProbs": _PROB[:, :1],
          "Indices": np.array([[1], [0]], np.int64),
          "Labels": np.array([[1], [0]], np.int64),
          "StatesInfo": np.zeros((3, 4), np.int64)},
     attrs={"class_number": 3})
# imperfect IOB inputs: split spans (B where the label has I), merged
# spans (I where the label has B), I-after-O chunk starts, type
# changes mid-chunk, chunks ending at the sequence boundary
# (tags for num_chunk_types=2: B0=0 I0=1 B1=2 I1=3 O=4)
spec("chunk_eval",
     ins={"Inference": np.array([[0, 1, 4, 0, 3, 2, 4, 1],
                                 [2, 3, 3, 0, 4, 4, 0, 1]], np.int64),
          "Label": np.array([[0, 1, 1, 4, 2, 3, 4, 1],
                             [2, 3, 0, 1, 4, 4, 0, 0]], np.int64)},
     attrs={"num_chunk_types": 2, "chunk_scheme": "IOB"})
spec("positive_negative_pair",
     ins={"Score": f32(4, 1), "Label": np.array([[1.], [0.], [1.], [0.]],
                                                np.float32),
          "QueryID": np.array([[1], [1], [1], [1]], np.int64)})
spec("nce", ins={"Input": f32(4, 8), "Weight": f32(20, 8),
                 "Label": ints(4, 1, lo=0, hi=20).astype(np.int64)},
     attrs={"num_neg_samples": 5, "num_total_classes": 20}, exact=False)
spec("sample_logits", ins={"Logits": f32(2, 10),
                           "Labels": ints(2, 1, lo=0,
                                          hi=10).astype(np.int64)},
     attrs={"num_samples": 4}, exact=False)
spec("hierarchical_sigmoid",
     ins={"X": f32(4, 8), "W": f32(7, 8),
          "Label": ints(4, 1, lo=0, hi=8).astype(np.int64)},
     attrs={"num_classes": 8}, grad=["X", "W"])

# --- quantization ----------------------------------------------------------
# no grad checks on fake-quant ops: the registered STE gradient is
# intentionally NOT the numeric derivative of the staircase
spec("fake_quantize_abs_max", ins={"X": _X}, attrs={"bit_length": 8})
spec("fake_channel_wise_quantize_abs_max", ins={"X": f32(3, 4)},
     attrs={"bit_length": 8})
spec("fake_quantize_moving_average_abs_max",
     ins={"X": _X, "InScale": np.array([1.0], np.float32)},
     attrs={"bit_length": 8, "moving_rate": 0.9}, is_test=True)
spec("fake_quantize_dequantize_moving_average_abs_max",
     ins={"X": _X, "InScale": np.array([1.0], np.float32)},
     attrs={"bit_length": 8, "moving_rate": 0.9}, is_test=True)
spec("fake_quantize_range_abs_max",
     ins={"X": _X, "InScale": np.array([1.0], np.float32),
          "Iter": np.array([0], np.int64)},
     attrs={"bit_length": 8, "window_size": 10}, is_test=True)
spec("fake_dequantize_max_abs",
     ins={"X": ints(2, 3, lo=-10, hi=10).astype(np.float32),
          "Scale": np.array([2.0], np.float32)},
     attrs={"max_range": 127.0})
spec("fake_channel_wise_dequantize_max_abs",
     ins={"X": f32(3, 4), "Scales": np.array([2.0, 1.5, 3.0],
                                             np.float32)},
     attrs={"quant_bits": [8]})
spec("moving_average_abs_max_scale",
     ins={"X": _X}, attrs={"moving_rate": 0.9}, is_test=True)
spec("quantize", ins={"Input": _X, "Scale": np.array([2.0], np.float32)})
spec("dequantize", ins={"Input": ints(2, 3, lo=-10, hi=10).astype(
    np.float32), "Scale": np.array([2.0], np.float32)})
spec("requantize", ins={"Input": ints(2, 3, lo=-10, hi=10).astype(
    np.float32)}, attrs={"Scale_in": 2.0, "Scale_out": 4.0})
# attr names are capitalized in the reference (requantize_op.cc:36-37)
spec("dgc", ins={"U": np.zeros(20, np.float32),
                 "V": np.zeros(20, np.float32), "Grad": f32(20)},
     attrs={"m": 0.9, "sparsity": [0.8]})
spec("dgc_clip_by_norm", ins={"X": f32(4),
                              "current_step": np.array([0.0],
                                                       np.float32)},
     attrs={"max_norm": 1.0, "rampup_begin_step": 0.0})
spec("average_accumulates",
     ins={"Param": _P, "InSum1": np.zeros((3, 2), np.float32),
          "InSum2": np.zeros((3, 2), np.float32),
          "InSum3": np.zeros((3, 2), np.float32),
          "InNumAccumulates": np.array([0], np.int64),
          "InOldNumAccumulates": np.array([0], np.int64),
          "InNumUpdates": np.array([0], np.int64)},
     attrs={"average_window": 10, "max_average_window": 20,
            "min_average_window": 5})

# --- detection -------------------------------------------------------------
_BOXES1 = np.array([[0, 0, 10, 10], [20, 20, 30, 30],
                    [5, 5, 15, 15]], np.float32)
spec("iou_similarity", ins={"X": _BOXES1, "Y": _BOXES1[:2]})
spec("box_clip", ins={"Input": _BOXES1,
                      "ImInfo": np.array([[12.0, 12.0, 1.0]],
                                         np.float32)})
spec("box_coder",
     ins={"PriorBox": _BOXES1, "PriorBoxVar": pos(3, 4),
          # distinct buffer: the numeric-grad pass perturbs in place
          "TargetBox": _BOXES1 + np.float32(0.5)},
     attrs={"code_type": "encode_center_size"})
spec("box_decoder_and_assign",
     ins={"PriorBox": _BOXES1, "PriorBoxVar": pos(3, 4),
          "TargetBox": f32(3, 8), "BoxScore": pos(3, 2)},
     attrs={"box_clip": 4.135})
spec("prior_box", ins={"Input": f32(1, 2, 3, 3),
                       "Image": f32(1, 3, 12, 12)},
     attrs={"min_sizes": [2.0], "aspect_ratios": [1.0],
            "variances": [0.1, 0.1, 0.2, 0.2]})
spec("density_prior_box", ins={"Input": f32(1, 2, 3, 3),
                               "Image": f32(1, 3, 12, 12)},
     attrs={"fixed_sizes": [2.0], "fixed_ratios": [1.0],
            "densities": [1], "variances": [0.1, 0.1, 0.2, 0.2]})
spec("anchor_generator", ins={"Input": f32(1, 2, 3, 3)},
     attrs={"anchor_sizes": [16.0], "aspect_ratios": [1.0],
            "stride": [4.0, 4.0], "variances": [0.1, 0.1, 0.2, 0.2]})
spec("yolo_box", ins={"X": f32(1, 3 * 7, 4, 4),
                      "ImgSize": np.array([[128, 128]], np.int32)},
     attrs={"anchors": [10, 13, 16, 30, 33, 23], "class_num": 2,
            "conf_thresh": 0.01, "downsample_ratio": 32})
# three gts: a big box (best-anchor inside the mask -> positive), a
# small box whose best anchor (0) is OUTSIDE the mask -> match -1 with
# only the ignore scan applying, and an all-zero invalid box; GTScore
# exercises the mixup-score weighting; anchor_mask=[1,2] subsets the
# anchor list
spec("yolov3_loss",
     ins={"X": f32(1, 2 * 7, 4, 4),
          "GTBox": np.array([[[0.52, 0.47, 0.4, 0.42],
                              [0.25, 0.75, 0.05, 0.06],
                              [0.0, 0.0, 0.0, 0.0]]], np.float32),
          "GTLabel": np.array([[1, 0, 0]], np.int64),
          "GTScore": np.array([[0.8, 0.6, 1.0]], np.float32)},
     attrs={"anchors": [10, 13, 16, 30, 33, 23],
            "anchor_mask": [1, 2], "class_num": 2,
            "ignore_thresh": 0.5, "downsample_ratio": 32},
     grad=["X"], grad_tol=5e-2)
spec("bipartite_match", ins={"DistMat": np.array([[0.9, 0.1],
                                                  [0.2, 0.8]],
                                                 np.float32)})
spec("target_assign",
     ins={"X": f32(1, 2, 3),
          "MatchIndices": np.array([[0, -1, 1]], np.int32)},
     attrs={"mismatch_value": 0.0})
spec("mine_hard_examples",
     ins={"ClsLoss": pos(1, 3), "MatchIndices": np.array([[0, -1, -1]],
                                                         np.int32),
          "MatchDist": pos(1, 3, lo=0.1, hi=0.9)},
     attrs={"neg_pos_ratio": 2.0, "mining_type": "max_negative"})
spec("polygon_box_transform", ins={"Input": f32(1, 8, 2, 2)})
spec("multiclass_nms",
     ins={"BBoxes": _BOXES1[None], "Scores": pos(1, 2, 3)},
     attrs={"score_threshold": 0.1, "nms_threshold": 0.5,
            "keep_top_k": 4, "background_label": 0})
spec("multiclass_nms2",
     ins={"BBoxes": _BOXES1[None], "Scores": pos(1, 2, 3)},
     attrs={"score_threshold": 0.1, "nms_threshold": 0.5,
            "keep_top_k": 4, "background_label": 0})
spec("collect_fpn_proposals",
     ins={"MultiLevelRois": [("cfp_r1", _BOXES1), ("cfp_r2", _BOXES1)],
          "MultiLevelScores": [("cfp_s1", pos(3)), ("cfp_s2", pos(3))]},
     attrs={"post_nms_topN": 4})
# mixed-scale rois spread across 3 levels, incl. a degenerate box
# (x2<x1 -> area 0 -> clamped to min_level)
spec("distribute_fpn_proposals",
     ins={"FpnRois": np.array(
         [[0, 0, 7, 7], [0, 0, 31, 31], [2, 2, 60, 50],
          [5, 3, 1, 9], [1, 1, 16, 14], [0, 0, 63, 63]], np.float32)},
     attrs={"min_level": 2, "max_level": 4, "refer_level": 3,
            "refer_scale": 16})
# well-formed anchor grid (x1<x2), two images with different sizes and
# scales: exercises variance-scaled decoding, the origin-scale
# min_size filter, center-inside-image rejection, and adaptive-eta NMS
_gp_anchors = np.zeros((3, 3, 2, 4), np.float32)
for _yy in range(3):
    for _xx in range(3):
        for _ai, _sz in enumerate((3.0, 6.0)):
            _gp_anchors[_yy, _xx, _ai] = [8 * _xx + 4 - _sz,
                                          8 * _yy + 4 - _sz,
                                          8 * _xx + 4 + _sz,
                                          8 * _yy + 4 + _sz]
spec("generate_proposals",
     ins={"Scores": pos(2, 2, 3, 3), "BboxDeltas": f32(2, 8, 3, 3),
          "ImInfo": np.array([[24.0, 24.0, 2.0],
                              [20.0, 28.0, 1.0]], np.float32),
          "Anchors": _gp_anchors,
          "Variances": pos(3, 3, 2, 4)},
     attrs={"pre_nms_topN": 12, "post_nms_topN": 6, "nms_thresh": 0.6,
            "min_size": 2.0, "eta": 0.9})
spec("generate_proposal_labels",
     ins={"RpnRois": _BOXES1, "GtClasses": np.array([1], np.int32),
          "IsCrowd": np.array([0], np.int32),
          "GtBoxes": np.array([[0, 0, 10, 10]], np.float32),
          "ImInfo": np.array([[32.0, 32.0, 1.0]], np.float32)},
     attrs={"fg_thresh": 0.5, "class_nums": 3})
spec("generate_mask_labels",
     ins={"ImInfo": np.array([[16.0, 16.0, 1.0]], np.float32),
          "GtClasses": np.array([1, 1], np.int32),
          "IsCrowd": np.array([0, 0], np.int32),
          "GtSegms": (np.arange(128).reshape(2, 8, 8) % 2
                      ).astype(np.float32),
          "Rois": np.array([[0, 0, 7, 15]], np.float32),
          "LabelsInt32": np.array([[1]], np.int32)},
     attrs={"resolution": 8, "num_classes": 2})
spec("rpn_target_assign",
     ins={"Anchor": _BOXES1,
          "GtBoxes": np.array([[0, 0, 10, 10]], np.float32)},
     attrs={"rpn_positive_overlap": 0.7, "rpn_negative_overlap": 0.3})
spec("retinanet_target_assign",
     ins={"Anchor": _BOXES1,
          "GtBoxes": np.array([[0, 0, 10, 10]], np.float32),
          "GtLabels": np.array([[1]], np.int32),
          "IsCrowd": np.array([0], np.int32),
          "ImInfo": np.array([[32.0, 32.0, 1.0]], np.float32)},
     attrs={"positive_overlap": 0.5, "negative_overlap": 0.4})
# two FPN levels, batch of two images with different im_scale, score
# ties (stable-sort order), nms_top_k below the per-level candidate
# count, and adaptive-eta NMS — the full reference pipeline
_RDO_SC0 = pos(2, 4, 3, lo=0.0, hi=1.0)
_RDO_SC0[0, 1, 2] = _RDO_SC0[0, 2, 0] = 0.6   # tie within level 0
_RDO_SC0[1, 0, 1] = 0.01                      # below threshold
_RDO_SC1 = pos(2, 2, 3, lo=0.0, hi=1.0)
_RDO_SC1[0, 0, 1] = 0.6                       # cross-level tie
spec("retinanet_detection_output",
     ins={"BBoxes": [("rdo_box0", f32(2, 4, 4, lo=-0.6, hi=0.6)),
                     ("rdo_box1", f32(2, 2, 4, lo=-0.6, hi=0.6))],
          "Scores": [("rdo_sc0", _RDO_SC0), ("rdo_sc1", _RDO_SC1)],
          "Anchors": [("rdo_an0",
                       np.array([[0, 0, 9, 9], [5, 5, 14, 14],
                                 [20, 20, 29, 29], [0, 20, 9, 29]],
                                np.float32)),
                      ("rdo_an1",
                       np.array([[0, 0, 19, 19], [10, 10, 29, 29]],
                                np.float32))],
          "ImInfo": np.array([[64.0, 64.0, 1.0], [65.0, 65.0, 2.0]],
                             np.float32)},
     # threshold 0.6 > 0.5 so the adaptive-eta decay gate actually
     # fires; image 2's 65/2 = 32.5 frame pins half-away-from-zero
     # rounding (std::round, not banker's)
     attrs={"score_threshold": 0.05, "nms_threshold": 0.6,
            "nms_top_k": 5, "keep_top_k": 6, "nms_eta": 0.9})
spec("roi_align", ins={"X": f32(1, 2, 6, 6),
                       "ROIs": np.array([[0, 0, 4, 4]], np.float32)},
     attrs={"pooled_height": 2, "pooled_width": 2,
            "spatial_scale": 1.0}, grad=["X"])
spec("roi_pool", ins={"X": f32(1, 2, 6, 6),
                      "ROIs": np.array([[0, 0, 4, 4]], np.float32)},
     attrs={"pooled_height": 2, "pooled_width": 2,
            "spatial_scale": 1.0})
# fractional, off-grid roi corners exercise the partial-cell integral
# terms; two images + BatchRoINums exercise the roi->image mapping
spec("prroi_pool", ins={"X": f32(2, 2, 6, 6),
                        "ROIs": np.array([[0.6, 0.4, 4.3, 3.7],
                                          [1.2, 0.7, 5.6, 4.4]],
                                         np.float32),
                        "BatchRoINums": np.array([1, 1], np.int64)},
     attrs={"pooled_height": 2, "pooled_width": 2,
            "spatial_scale": 0.8})
spec("psroi_pool", ins={"X": f32(1, 8, 6, 6),
                        "ROIs": np.array([[0, 0, 4, 4]], np.float32)},
     attrs={"pooled_height": 2, "pooled_width": 2, "output_channels": 2,
            "spatial_scale": 1.0})
spec("roi_perspective_transform",
     ins={"X": f32(1, 2, 8, 8),
          "ROIs": np.array([[1, 1, 6, 1, 6, 6, 1, 6]], np.float32)},
     attrs={"transformed_height": 4, "transformed_width": 4,
            "spatial_scale": 1.0})
# imperfect detections: duplicates on one GT, a near-miss below the
# IoU threshold, a difficult GT, ranked scores crossing class lines —
# the cases where the AP interpolation actually matters
spec("detection_map",
     ins={"DetectRes": np.array(
         [[1.0, 0.90, 0.00, 0.00, 0.40, 0.38],   # tp on gt1
          [1.0, 0.80, 0.02, 0.02, 0.42, 0.40],   # duplicate on gt1 -> fp
          [1.0, 0.70, 0.50, 0.55, 0.90, 0.95],   # tp on gt2
          [1.0, 0.60, 0.10, 0.50, 0.30, 0.70],   # near-miss -> fp
          [2.0, 0.85, 0.21, 0.20, 0.70, 0.71],   # matches difficult gt
          [2.0, 0.75, 0.00, 0.61, 0.30, 0.89]],  # tp on gt4
         np.float32),
          "Label": np.array(
         [[1.0, 0.0, 0.00, 0.00, 0.40, 0.40],
          [1.0, 0.0, 0.50, 0.50, 0.90, 0.90],
          [2.0, 1.0, 0.20, 0.20, 0.70, 0.70],    # difficult
          [2.0, 0.0, 0.00, 0.60, 0.30, 0.90]],
         np.float32)},
     attrs={"overlap_threshold": 0.5, "ap_type": "integral",
            "evaluate_difficult": False})
spec("flash_attention",
     ins={"Q": f32(1, 2, 4, 8), "K": f32(1, 2, 4, 8),
          "V": f32(1, 2, 4, 8)},
     attrs={"causal": False, "block_q": 128, "block_k": 128},
     grad=["Q", "K", "V"], is_test=True)
spec("where_index", ins={"Condition": _B1})

# ===========================================================================
# batch 3: straggler ops (straggler_ops.py)
# ===========================================================================
spec("deformable_conv",
     ins={"Input": f32(1, 2, 5, 5), "Filter": f32(3, 2, 3, 3),
          "Offset": f32(1, 18, 5, 5, lo=-0.5, hi=0.5),
          "Mask": pos(1, 9, 5, 5, lo=0.5, hi=1.0)},
     attrs={"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
            "groups": 1, "deformable_groups": 1},
     grad=["Input", "Filter"], grad_tol=3e-2)
spec("deformable_conv_v1",
     ins={"Input": f32(1, 2, 5, 5), "Filter": f32(3, 2, 3, 3),
          "Offset": f32(1, 18, 5, 5, lo=-0.5, hi=0.5)},
     attrs={"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
            "groups": 1, "deformable_groups": 1})
spec("deformable_psroi_pooling",
     ins={"Input": f32(1, 8, 6, 6),
          "ROIs": np.array([[0, 0, 4, 4]], np.float32),
          "Trans": f32(1, 2, 2, 2, lo=-0.1, hi=0.1)},
     attrs={"pooled_height": 2, "pooled_width": 2, "output_dim": 2,
            "spatial_scale": 1.0, "trans_std": 0.1,
            "sample_per_part": 2})
# positive input/filter/bias keep every relu pre-activation strictly
# positive: central differences disagree with the analytic subgradient
# on draws that land within delta of the kink
spec("conv2d_fusion",
     ins={"Input": f32(1, 2, 4, 4, lo=0.1, hi=1.0),
          "Filter": f32(3, 2, 3, 3, lo=0.05, hi=1.0),
          "Bias": f32(3, lo=0.5, hi=1.5)},
     attrs={"strides": [1, 1], "paddings": [1, 1], "activation": "relu"})
spec("conv2d_inception_fusion",
     ins={"Input": f32(1, 4, 5, 5),
          "Filter": [("inc_f0", f32(2, 4, 1, 1)),
                     ("inc_f1", f32(7, 4, 1, 1)),
                     ("inc_f2", f32(5, 2, 3, 3)),
                     ("inc_f3", f32(4, 3, 3, 3))],
          "Bias": [("inc_b0", f32(2)), ("inc_b1", f32(7)),
                   ("inc_b2", f32(5)), ("inc_b3", f32(4))]},
     attrs={"activation": "relu"})
spec("fused_embedding_fc_lstm",
     ins={"Ids": np.array([[[1], [3], [0]]], np.int64),
          "Embeddings": f32(6, 16), "WeightH": f32(4, 16),
          "Bias": f32(1, 16)})
spec("fusion_seqpool_cvm_concat",
     # positive values: the CVM transform takes log(show/click + 1)
     ins={"X": [("fspcc_a", pos(2, 3, 4)), ("fspcc_b", pos(2, 3, 4))],
          "CVM": f32(2, 2)},
     attrs={"pooltype": "SUM", "use_cvm": True})
spec("pull_box_sparse",
     ins={"Ids": np.array([[1], [5]], np.int64)},
     attrs={"size": 4, "table_id": 7}, exact=False)
spec("fill_zeros_like2", ins={"X": _X}, attrs={"dtype": "float32"},
     expect=lambda i, a: {"Out": [np.zeros_like(i["X"])]})

skip("push_box_sparse", "host-side table update paired with "
                        "pull_box_sparse; covered in "
                        "tests/test_straggler_ops.py")
skip("fl_listen_and_serv", "host-side federated PS loop; routed to "
                           "distributed/ps_server.py by the Executor "
                           "like listen_and_serv")
skip("distributed_notify", "host RPC side effect; covered in "
                           "tests/test_straggler_ops.py")
skip("conditional_block_infer", "needs a sub-block program; delegates "
                                "to the conditional_block lowering")
skip("read", "host reader infeed; covered in "
             "tests/test_straggler_ops.py")
skip("create_custom_reader", "host reader binding; covered in "
                             "tests/test_straggler_ops.py")

# -- the latent-attention decoder's ops (ops/latent_attention.py) ------------

def _gated_ffn_ref(i, a):
    h = i["X"] @ i["W1"]
    f = h.shape[-1] // 2
    return (h[..., :f] / (1.0 + np.exp(-h[..., :f])) * h[..., f:]) @ i["W2"]


def _yarn_rotary_ref(i, a):
    """Pairs (x[2k], x[2k+1]) turned by (start + t) * inv_freq_k, the
    YaRN frequencies written out again."""
    x, dim = i["X"], i["X"].shape[-1]
    k = np.arange(dim // 2, dtype=np.float64)
    f = a["theta"] ** (-2.0 * k / dim)

    def pair(turns):
        return dim * np.log(a["original"] / (2 * np.pi * turns)) \
            / (2 * np.log(a["theta"]))
    low = max(np.floor(pair(a["beta_fast"])), 0)
    high = min(np.ceil(pair(a["beta_slow"])), dim // 2 - 1)
    keep = 1 - np.clip((k - low) / (high - low), 0, 1)
    inv = f / a["factor"] * (1 - keep) + f * keep
    pos = i["StartPos"][:, None] + np.arange(x.shape[1])[None, :]
    ang = (pos[..., None] * inv)[:, :, None, :]
    out = np.empty_like(x)
    out[..., 0::2] = x[..., 0::2] * np.cos(ang) - x[..., 1::2] * np.sin(ang)
    out[..., 1::2] = x[..., 1::2] * np.cos(ang) + x[..., 0::2] * np.sin(ang)
    return out


spec("gated_ffn", ins={"X": f32(2, 3, 8), "W1": f32(8, 12), "W2": f32(6, 8)},
     grad=["X", "W1", "W2"],
     expect=lambda i, a: {"Out": [_gated_ffn_ref(i, a)]})
spec("yarn_rotary", ins={"X": f32(2, 3, 2, 16),
                         "StartPos": ints(2, lo=0, hi=40)},
     attrs={"theta": 50.0, "factor": 4.0, "original": 16, "beta_fast": 2.0,
            "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0},
     grad=["X"], expect=lambda i, a: {"Out": [_yarn_rotary_ref(i, a)]})
skip("mla_project", "latent attention's projections into the absorbed "
     "form (queries in the latent space, the token's cache row); with "
     "`paged_attention` over a latent pool and `mla_output` against the "
     "reference's per-head attention in tests/test_latent_model.py")
skip("mla_output", "the value half of W_kvb and W_o behind the latent "
     "pool's attention; covered with `mla_project` in "
     "tests/test_latent_model.py")
skip("kda_mixer", "stateful serving op over per-slot convolution windows "
     "and a float32 matrix state a head with start/n_valid feeds; chunks, "
     "single steps, muted, fresh and partly valid rows against the "
     "reference's token-by-token recurrence in "
     "tests/test_linear_attention.py")
skip("gated_moe", "top-k routed gated experts with a held share and an "
     "int32 probe; against the plain reference layer, the shares adding "
     "up (also over an `ep` mesh axis) and a one-expert router in "
     "tests/test_latent_model.py")

# ===========================================================================
# independent numpy references + extra grad slots (op_expects.py) —
# merged last so every entry targets an existing spec
# ===========================================================================
from op_expects import EXPECTS, EXTRA_GRADS  # noqa: E402

for _op, _fn in EXPECTS.items():
    assert _op in SPECS, f"expect for unspec'd op {_op}"
    if SPECS[_op]["expect"] is None:
        SPECS[_op]["expect"] = _fn
for _op, _slots in EXTRA_GRADS.items():
    assert _op in SPECS, f"extra grads for unspec'd op {_op}"
    SPECS[_op]["grad"] = tuple(
        dict.fromkeys(list(SPECS[_op]["grad"]) + list(_slots)))
