"""The serving window on a simulated engine and host clock: a backlog
closes on the first iteration boundary past `seconds`, taken from the
engine's own records, whatever an iteration takes; the rule it
replaced (bursts of tokens 20 ms apart) is kept here as the failing
case."""
import types

import pytest

from benchmark import serve_window

SLOTS, SECONDS, T_START = 4, 2.0, 100.0
MIX = {"arrival": "backlog", "queue_depth": 4}


class Host:
    """`time` as `serve_window` sees it: `sleep` is when the engine's
    thread runs."""

    def __init__(self):
        self.now, self.engine = T_START, None

    def perf_counter(self):
        return self.now

    def sleep(self, dt):
        self.engine.run_until(self.now + dt)
        self.now += dt


class Engine:
    """Admits into free slots, emits one token a live request a turn,
    keeps each turn's end: what `trace.iteration_records()` says."""

    def __init__(self, host, step_s, stall_after=None):
        self.host, self.step_s, self.stall_after = host, step_s, stall_after
        self.max_slots, self.queue, self.live, self.ends = SLOTS, [], [], []
        self.next_end = T_START + step_s
        host.engine = self

    def request(self, prompt, n_out, on_token, logits=None):
        response = types.SimpleNamespace(finished=False)
        response.done = lambda: response.finished
        self.queue.append([n_out, on_token, response])
        return response

    def load(self):
        return len(self.queue) + len(self.live)

    def run_until(self, t):
        back = self.host.now
        while self.next_end <= t:
            if self.stall_after is not None \
                    and self.next_end > T_START + self.stall_after:
                break
            while self.queue and len(self.live) < self.max_slots:
                self.live.append(self.queue.pop(0))
            self.host.now = self.next_end - 1e-4   # tokens, then the end
            for r in self.live:
                r[1](7)
                r[0] -= 1
                r[2].finished = r[0] == 0
            self.live = [r for r in self.live if r[0]]
            self.ends.append(self.next_end)
            self.next_end += self.step_s
        self.host.now = back


def backlog(monkeypatch, step_ms, n=4000, **engine):
    host = Host()
    monkeypatch.setattr(serve_window, "time", host)
    eng = Engine(host, step_ms / 1e3, **engine)
    requests = [serve_window.Request(i, 0.0, [1, 2], 5 + i % 7)
                for i in range(n)]
    return host, eng, requests


def old_rule(host, eng, requests, seconds, burst_gap_s=0.02):
    """The loop's exit as it was before: leave once tokens have come
    past `seconds` and none for two burst gaps, or 5 s past it."""
    t0, sent = host.perf_counter(), 0
    while True:
        while eng.load() < MIX["queue_depth"] + SLOTS:
            r = requests[sent]
            r.response = eng.request(r.prompt, r.n_out, r.on_token)
            sent += 1
        now = host.perf_counter()
        last = max((r.stamps[-1] for r in requests[:sent] if r.stamps),
                   default=0.0)
        if last - t0 > seconds and (now - last > 2 * burst_gap_s
                                    or now - t0 > seconds + 5.0):
            return now - t0
        host.sleep(0.005)


@pytest.mark.parametrize("step_ms", [8, 12, 23])
def test_the_backlog_closes_on_the_first_boundary_past_seconds(
        monkeypatch, step_ms):
    host, eng, requests = backlog(monkeypatch, step_ms)
    t0, sent, t_end = serve_window.run(eng, MIX, requests, SECONDS,
                                       ends=lambda: eng.ends)
    step = step_ms / 1e3
    assert t0 == T_START
    assert t_end == next(e for e in eng.ends if e > t0 + SECONDS)
    assert SECONDS < t_end - t0 <= SECONDS + step + 1e-9
    # the loop itself left within a turn and a poll of the boundary
    assert host.now - t0 <= SECONDS + step + 0.0051
    # every token of the turns up to the boundary, none of a later one
    turns = sum(e <= t_end for e in eng.ends)
    assert serve_window.tokens_until(t_end, sent) == SLOTS * turns
    assert len(sent) < len(requests) // 2


@pytest.mark.parametrize("step_ms", [8, 12, 23])
def test_the_rule_it_replaced_ran_on_to_five_seconds_past(
        monkeypatch, step_ms):
    host, eng, requests = backlog(monkeypatch, step_ms)
    assert old_rule(host, eng, requests, SECONDS) > SECONDS + 5.0
    # and left in time only where turns lay further apart than 40 ms
    host, eng, requests = backlog(monkeypatch, 60)
    assert old_rule(host, eng, requests, SECONDS) < SECONDS + 0.1


def test_window_end_is_the_first_record_that_ends_past_seconds():
    ends = [10.5, 11.0, 11.9, 12.012, 12.024]
    assert serve_window.window_end(10.0, 2.0, ends) == 12.012
    assert serve_window.window_end(10.0, 2.0, ends[:3]) is None
    assert serve_window.window_end(10.0, 2.0, []) is None
    # a boundary ON `seconds` is not past it
    assert serve_window.window_end(10.0, 2.0, [12.0, 12.3]) == 12.3


def test_a_stalled_engine_is_an_error_not_a_longer_window(monkeypatch):
    host, eng, requests = backlog(monkeypatch, 12, stall_after=1.0)
    with pytest.raises(RuntimeError, match="no iteration ended"):
        serve_window.run(eng, MIX, requests, SECONDS, ends=lambda: eng.ends)
    assert host.now - T_START <= SECONDS + serve_window.GRACE_S + 0.011


def test_a_backlog_that_runs_out_fails_the_run(monkeypatch):
    host, eng, requests = backlog(monkeypatch, 12, n=60)
    with pytest.raises(RuntimeError, match="ran empty"):
        serve_window.run(eng, MIX, requests, SECONDS, ends=lambda: eng.ends)


def test_iteration_ends_reads_the_programs_ring(monkeypatch):
    from paddle_tpu import trace
    monkeypatch.setattr(trace, "iteration_records", lambda: [
        {"t_start": 1.0, "t_end": 1.5}, {"t_start": 1.5, "t_end": 2.25}])
    assert serve_window.iteration_ends() == [1.5, 2.25]


def test_thirds_and_lateness():
    ttft = [10.0] * 4 + [20.0] * 4 + [40.0] * 5
    assert serve_window.ttft_thirds(ttft) == {
        "ttft_first_third_mean_ms": 10.0, "ttft_last_third_mean_ms": 40.0}
    reqs = []
    for i in range(20):
        r = serve_window.Request(i, 0.1 * i, [1], 1)
        r.sent = 50.0 + 0.1 * i + 0.001 * i
        reqs.append(r)
    assert serve_window.late_ms(50.0, reqs, 100) == pytest.approx(19.0)
    assert serve_window.late_ms(50.0, [], 95) is None
