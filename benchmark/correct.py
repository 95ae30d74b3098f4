"""The comparison that decides `correct`.

Every number compared has a limit of its own in the cell's file
(benchmark/cells/<workload>.json, `limits`), set between two readings
that PERF.md records. `judge` prints each number beside its limit.
"""
from __future__ import annotations

import statistics

# leaves whose reference gradient is under this share of the median
# leaf's are nought to rounding (a key's bias under softmax): Adam moves
# them by round-off alone, so they are left out of the change
NOUGHT = 1e-3


def _worst(prog, ref, floor, leaves):
    worst, where = 0.0, ""
    for k in leaves:
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def train_numbers(prog, ref):
    """Program against reference over the check steps: each step's
    loss, the first gradient's norm and the change's norm by the worst
    leaf. A gap is between the two norms, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["loss"], ref["loss"]))
    g_ref = ref["grad_norm"]
    g_med = statistics.median(g_ref.values())
    grad_gap, grad_leaf = _worst(prog["grad_norm"], g_ref, g_med,
                                 list(g_ref))
    moved = [k for k in g_ref if g_ref[k] >= NOUGHT * g_med]
    d_ref = ref["delta_norm"]
    d_med = statistics.median(d_ref[k] for k in moved)
    delta_gap, delta_leaf = _worst(prog["delta_norm"], d_ref, d_med, moved)
    return ({"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
             "delta_norm_gap": delta_gap},
            {"grad_leaf": grad_leaf, "delta_leaf": delta_leaf,
             "leaves_left_out": len(g_ref) - len(moved)})


def logit_gaps(ref_rows, rows):
    """Per position, the widest and the mean squared gap between two
    [positions, vocab] sets of logits, in units of the reference row's
    spread (its deviation over the vocabulary)."""
    gap = (rows - ref_rows) / ref_rows.std(axis=-1, keepdims=True)
    return abs(gap).max(axis=-1), (gap * gap).mean(axis=-1)


def judge(numbers, limits):
    """(correct, [(name, value, limit), ...]): every number within its
    limit, and every limit has its number."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers.get(name)
        rows.append((name, value, limit))
        if value is None or not value <= limit:
            ok = False
    return ok, rows
