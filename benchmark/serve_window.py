"""The serving window and its load generator: one loop for every
serving cell, open loop or backlog. Times are taken from when a
request was DUE, on the client's side, by the host's clock.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic

GRACE_S = 5.0   # past `seconds` with no iteration boundary: an error


class Request:
    __slots__ = ("index", "due", "prompt", "n_out", "sent", "stamps",
                 "tokens", "response", "logits")

    def __init__(self, index, due, prompt, n_out):
        self.index, self.due, self.prompt, self.n_out = \
            index, due, prompt, n_out
        self.sent = None
        self.stamps, self.tokens = [], []
        self.response = None
        self.logits = None       # a list where `correct` may sample it

    def on_token(self, tok):
        self.stamps.append(time.perf_counter())
        self.tokens.append(int(tok))

    def finished(self):
        return self.response is not None and self.response.done() \
            and len(self.tokens) == self.n_out

    def failed(self):
        """Done with an error before its first token."""
        return self.response is not None and self.response.done() \
            and not self.tokens


class StepLog:
    """What the traced run records of every executable run, and where
    the traced slice starts and stops: on the engine's own thread,
    between two calls."""

    def __init__(self, tracer, slice_calls, slice_after_s):
        self.calls = []
        self.tracer = tracer
        self.slice_calls = slice_calls
        self.slice_after_s = slice_after_s
        self.t0 = None
        self.slice = None        # (first, last) indices into calls
        self._tracing = False
        self._first = 0

    def annotate(self, kind):
        import jax
        if self.t0 is not None and self.slice is None \
                and not self._tracing \
                and time.perf_counter() - self.t0 >= self.slice_after_s:
            self.tracer.start()
            self._tracing = True
            self._first = len(self.calls)
        return jax.profiler.TraceAnnotation(f"bench.{kind}_step")

    def __call__(self, kind, start, nvalid, host_s, t0, t1):
        self.calls.append((kind, start, nvalid, host_s, t0, t1))
        if self._tracing and \
                len(self.calls) - self._first >= self.slice_calls:
            self.tracer.stop()
            self._tracing = False
            self.slice = (self._first, len(self.calls))

    def close(self):
        if self._tracing:
            self.tracer.stop()
            self._tracing = False
            self.slice = (self._first, len(self.calls))


def make_requests(mix, seed, vocab_size, seconds):
    """The requests a run can reach, with their token ids drawn from
    the seed during set-up."""
    trace = traffic.serve_trace(mix)
    if mix["arrival"] == "poisson":
        trace = [r for r in trace if r[0] < seconds]
        if len(trace) == int(mix["requests"]):
            raise RuntimeError("the trace is shorter than the window: "
                               "raise `requests` in the mix")
    requests = [Request(i, due,
                        traffic.prompt_tokens(seed, i, p,
                                              vocab_size).tolist(), o)
                for i, (due, p, o) in enumerate(trace)]
    for r in check_candidates(mix, seed, requests):
        r.logits = []
    return requests


def _length(r):
    return len(r.prompt) + r.n_out


def check_candidates(mix, seed, requests):
    """The requests whose logits the run keeps for `correct`, in the
    order it will take them: drawn from the seed among the first
    `check_pool` of the trace and no more than its first half (those
    that every run finishes), the longest of them first, and half as
    many again as `check_requests` in case some do not finish."""
    pool = requests[:min(int(mix["check_pool"]), max(1, len(requests) // 2))]
    longest = max(pool, key=_length)
    others = [r for r in pool if r is not longest]
    order = np.random.default_rng(seed).permutation(len(others))
    n = int(mix["check_requests"])
    return [longest] + [others[i] for i in order[:n + n // 2 - 1]]


def check_sample(mix, seed, requests):
    """Of the candidates, the first `check_requests` that finished."""
    done = [r for r in check_candidates(mix, seed, requests)
            if r.finished()]
    return done[:int(mix["check_requests"])]


def iteration_ends():
    """When each turn of the engine's loop that ran a step ended, on
    `perf_counter`'s clock (the stamps' clock), oldest first: the
    program's own iteration records, a ring of `trace.ITERATION_RING`
    in the process. Only the ring's tail is ever asked for here."""
    from paddle_tpu import trace
    return [r["t_end"] for r in trace.iteration_records()]


def window_end(t0, seconds, ends):
    """The first iteration boundary past `seconds`: the `t_end` of the
    first of the engine's records that ends after `t0 + seconds`; None
    while no iteration has ended there yet."""
    return next((float(e) for e in ends if e > t0 + seconds), None)


def run(cell, mix, requests, seconds, log=None, ends=iteration_ends):
    """Offer the trace for `seconds`. Open loop: send each request when
    it is due, then keep the engine stepping until every request sent
    has its first token or has failed. Backlog: keep `queue_depth`
    requests queued beyond the slots until the first iteration has
    ended past `seconds` (`ends()` says when iterations ended); one
    that has not ended `GRACE_S` later is an error. Returns the
    window's start on the host's clock, the requests sent and, for a
    backlog, the boundary the window closed on."""
    backlog = mix["arrival"] == "backlog"
    t0 = time.perf_counter()
    if log is not None:
        log.t0 = t0
    sent = 0

    def send(r):
        r.sent = time.perf_counter()
        r.response = cell.request(r.prompt, r.n_out, r.on_token, r.logits)

    if backlog:
        want = int(mix["queue_depth"]) + cell.max_slots
        while True:
            while cell.load() < want:
                if sent == len(requests):
                    raise RuntimeError(
                        "the backlog ran empty inside the window: raise "
                        "`requests` in the mix")
                send(requests[sent])
                sent += 1
            if time.perf_counter() - t0 > seconds:
                t_end = window_end(t0, seconds, ends())
                if t_end is not None:
                    return t0, requests[:sent], t_end
                if time.perf_counter() - t0 > seconds + GRACE_S:
                    raise RuntimeError(
                        f"no iteration ended within {GRACE_S} s past the "
                        "window: the engine stalled or keeps no records")
            time.sleep(0.005)
    else:
        for r in requests:
            delay = t0 + r.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            send(r)
            sent += 1
        limit = t0 + seconds + mix["timeout_ms"] / 1e3 + 5.0
        while time.perf_counter() < limit:
            if all(r.tokens or r.failed() for r in requests):
                break
            time.sleep(0.005)
    return t0, requests[:sent], None


# -- what the client saw -----------------------------------------------------

def _all_stamps(sent):
    return np.sort(np.concatenate(
        [np.asarray(r.stamps) for r in sent if r.stamps] or [np.zeros(0)]))


def tokens_until(t_end, sent):
    """Output tokens stamped up to `t_end`: an iteration stamps its
    tokens before its record ends."""
    return int(np.searchsorted(_all_stamps(sent), t_end, side="right"))


def longest_pause_ms(sent):
    """The longest time in which no request got a token: a stalled
    host shows here, on an earlier line of the output, and in no
    metric."""
    stamps = _all_stamps(sent)
    return float(np.diff(stamps).max() * 1e3) if len(stamps) > 1 else 0.0


def percentile(values, q):
    """A percentile that is one of the samples (no interpolation, so a
    request without a first token, at infinity, stays a number)."""
    return float(np.percentile(np.asarray(values, np.float64), q,
                               method="higher"))


def ttft_ms(t0, sent):
    """First token minus due time; a request without one lies beyond
    every percentile."""
    return [((r.stamps[0] - (t0 + r.due)) * 1e3) if r.stamps
            else float("inf") for r in sent]


def ttft_thirds(ttft):
    """Mean TTFT of the first and of the last third of the requests,
    in the order they were due: a queue that grows through the window
    (a rate past the knee) shows as the last over the first."""
    third = max(1, len(ttft) // 3)
    return {"ttft_first_third_mean_ms": sum(ttft[:third]) / third,
            "ttft_last_third_mean_ms": sum(ttft[-third:]) / third}


def late_ms(t0, sent, q):
    """How late the load generator ran: sent minus due, a percentile
    over every request sent."""
    late = [(r.sent - (t0 + r.due)) * 1e3 for r in sent
            if r.sent is not None]
    return float(np.percentile(late, q)) if late else None


def gaps_ms(sent):
    out = []
    for r in sent:
        out += list(np.diff(np.asarray(r.stamps)) * 1e3)
    return out
