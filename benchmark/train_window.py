"""The training window: one loop for every training cell."""
from __future__ import annotations

import collections
import time

import jax
import numpy as np


def run(cell, batches, seconds, in_flight, tracer=None, slice_steps=10):
    """Step `cell` for `seconds`, cycling through `batches`, with at
    most `in_flight` steps enqueued: the loss of step n - in_flight is
    fetched before step n is enqueued, as a trainer that logs its loss
    runs. The window ends in block_until_ready.

    With a `tracer`, a steady slice of `slice_steps` steps in the
    middle is traced, drained at both ends. Returns the counters.
    """
    pending = collections.deque()
    host_s, losses = [], []
    steps = 0
    sliced = None
    slice_at = seconds * 0.4 if tracer is not None else None

    def one_step():
        nonlocal steps
        if len(pending) == in_flight:
            losses.append(float(np.asarray(pending.popleft())))
        pending.append(cell.step(batches[steps % len(batches)]))
        lt = cell.exe.last_step_timings
        host_s.append(lt["total_s"] - lt["fetch_s"])
        steps += 1

    def drain():
        while pending:
            losses.append(float(np.asarray(pending.popleft())))

    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if slice_at is not None and time.perf_counter() - t0 >= slice_at:
            slice_at = None
            drain()
            first = steps
            tracer.start()
            t_s0 = time.perf_counter()
            for _ in range(slice_steps):
                one_step()
            drain()
            t_s1 = time.perf_counter()
            tracer.stop()
            sliced = {"steps": steps - first, "seconds": t_s1 - t_s0,
                      "host_s": host_s[first:steps]}
            continue
        one_step()
    jax.block_until_ready(list(pending))
    t1 = time.perf_counter()
    drain()
    return {"steps": steps, "window_s": t1 - t0,
            "tokens": steps * cell.tokens_per_step,
            "host_s": host_s, "losses": losses, "slice": sliced}
