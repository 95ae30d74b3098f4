"""The serving window and its load generator: one loop for every
serving cell, open loop or backlog. Times are taken from when a
request was DUE, on the client's side, by the host's clock.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import traffic

BURST_GAP_S = 0.02   # tokens of one iteration arrive within this


class Request:
    __slots__ = ("index", "due", "prompt", "n_out", "sent", "stamps",
                 "tokens", "response", "latest", "logits")

    def __init__(self, index, due, prompt, n_out, latest):
        self.index, self.due, self.prompt, self.n_out = \
            index, due, prompt, n_out
        self.sent = None
        self.stamps, self.tokens = [], []
        self.response = None
        self.latest = latest     # one cell shared by the run's requests
        self.logits = None       # a list where `correct` may sample it

    def on_token(self, tok):
        now = time.perf_counter()
        self.stamps.append(now)
        self.tokens.append(int(tok))
        self.latest[0] = now

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
    latest = [0.0]
    requests = [Request(i, due,
                        traffic.prompt_tokens(seed, i, p,
                                              vocab_size).tolist(),
                        o, latest)
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


def run(cell, mix, requests, seconds, log=None):
    """Offer the trace for `seconds`. Open loop: send each request when
    it is due, then keep the engine stepping until every request sent
    has its first token or has failed. Backlog: keep `queue_depth`
    requests queued beyond the slots until an iteration has ended past
    `seconds`. Returns the window's start on the host's clock."""
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
            now = time.perf_counter()
            last = requests[0].latest[0]
            if last - t0 > seconds and (now - last > 2 * BURST_GAP_S
                                        or now - t0 > seconds + 5.0):
                break
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
    return t0, requests[:sent]


# -- what the client saw -----------------------------------------------------

def _all_stamps(sent):
    return np.sort(np.concatenate(
        [np.asarray(r.stamps) for r in sent if r.stamps] or [np.zeros(0)]))


def window_end(t0, seconds, sent):
    """The first iteration boundary after `seconds`: the last stamp of
    the first burst of tokens that ends past it."""
    stamps = _all_stamps(sent)
    after = stamps[stamps > t0 + seconds]
    if not len(after):
        raise RuntimeError("no iteration ended after the window")
    end = after[0]
    for s in after[1:]:
        if s - end > BURST_GAP_S:
            break
        end = s
    return float(end), stamps


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


def gaps_ms(sent):
    out = []
    for r in sent:
        out += list(np.diff(np.asarray(r.stamps)) * 1e3)
    return out
