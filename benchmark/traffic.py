"""One general traffic generator, driven by a mix's data file.

A mix is ONE fixed trace: its lengths are the quantile-stratified
multiset of the stated distribution, ordered once by the file's
`trace_seed`; its arrival offsets likewise. `--seed` draws the token
ids and nothing else, so two runs with different seeds do the same
amount of work in the same order.

Length distribution: `{"min": a, "max": b, "mean": m}` is the family
q(u) = a + (b - a) * u**k on u in (0, 1), with k = (b - a)/(m - a) - 1,
whose mean is m (k = 1 is uniform; k > 1 puts most requests near `min`
and leaves a tail towards `max`).
"""
from __future__ import annotations

import numpy as np


def stratified_lengths(spec, n, rng):
    """The n quantiles (i + 0.5)/n of the family, as whole numbers, in
    an order drawn from `rng`."""
    a, b, m = float(spec["min"]), float(spec["max"]), float(spec["mean"])
    if not a < m < b:
        raise ValueError(f"length spec needs min < mean < max: {spec}")
    k = (b - a) / (m - a) - 1.0
    u = (np.arange(n) + 0.5) / n
    vals = np.rint(a + (b - a) * u ** k).astype(np.int64)
    return vals[rng.permutation(n)]


def poisson_offsets(rate_per_s, n, rng):
    """n arrival offsets (seconds from the window's start) with
    exponential gaps of mean 1/rate: the stratified quantiles of the
    exponential, in an order drawn from `rng`, summed."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / float(rate_per_s)
    return np.cumsum(gaps[rng.permutation(n)])


def serve_trace(mix):
    """The fixed trace of a serving mix: a list of
    (due_s, prompt_len, output_len), the same for every seed."""
    n = int(mix["requests"])
    rng = np.random.default_rng(int(mix["trace_seed"]))
    prompts = stratified_lengths(mix["prompt_len"], n, rng)
    outputs = stratified_lengths(mix["output_len"], n, rng)
    limit = mix.get("max_total_len")
    if limit is not None:
        outputs = np.minimum(outputs, int(limit) - prompts)
        if (outputs < 1).any():
            raise ValueError("prompt + output exceeds max_total_len")
    if mix["arrival"] == "backlog":
        due = np.zeros(n)
    elif mix["arrival"] == "poisson":
        due = poisson_offsets(mix["rate_per_s"], n, rng)
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    return [(float(d), int(p), int(o))
            for d, p, o in zip(due, prompts, outputs)]


def prompt_tokens(seed, index, length, vocab_size):
    """Token ids of request `index`, from `--seed`. Each request has a
    stream of its own, so only the requests a run reaches are drawn."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, vocab_size, size=length, dtype=np.int64)


def train_batches(seed, mix, rows, vocab_size):
    """The pool of `batch_pool` token batches [rows, seq_len] that the
    training window cycles through: every row differs."""
    rng = np.random.default_rng(int(seed))
    return [rng.integers(0, vocab_size, size=(rows, int(mix["seq_len"])),
                         dtype=np.int64)
            for _ in range(int(mix["batch_pool"]))]
