"""Every mix is one fixed trace: `--seed` draws token ids and nothing
else."""
import os

import numpy as np
import pytest

from benchmark import manifest, traffic

MIXES = sorted(f[:-5] for f in os.listdir(
    os.path.join(manifest.HERE, "traffic")))
SERVE = [m for m in MIXES if manifest.traffic(m)["kind"] == "serve"]
LEDGER_TOK_S = 1780.6   # serve_out_tok_s of the engine PR 26 left (ledger)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_same_trace_other_tokens(name):
    mix = manifest.traffic(name)
    if mix["kind"] == "serve":
        a, b = traffic.serve_trace(mix), traffic.serve_trace(mix)
        assert a == b
        for i, (_, p, _) in enumerate(a[:8]):
            t1 = traffic.prompt_tokens(2**31 + 5, i, p, 50257)
            t2 = traffic.prompt_tokens(7, i, p, 50257)
            assert len(t1) == len(t2) == p and (t1 != t2).any()
            assert (t1 == traffic.prompt_tokens(2**31 + 5, i, p, 50257)).all()
    else:
        b1 = traffic.train_batches(2**31 + 5, mix, 4, 30522)
        b2 = traffic.train_batches(7, mix, 4, 30522)
        assert [x.shape for x in b1] == [x.shape for x in b2]
        assert len(b1) == mix["batch_pool"] > mix["check_steps"]
        assert all((x != y).any() for x, y in zip(b1, b2))
        rows = np.concatenate(b1)
        assert len({r.tobytes() for r in rows}) == len(rows)


@pytest.mark.parametrize("name", SERVE)
def test_lengths_are_the_stated_distribution(name):
    mix = manifest.traffic(name)
    trace = traffic.serve_trace(mix)
    assert len(trace) == mix["requests"]
    for col, key in ((1, "prompt_len"), (2, "output_len")):
        vals = np.array([r[col] for r in trace])
        spec = mix[key]
        assert spec["min"] <= vals.min() and vals.max() <= spec["max"]
        if "max_total_len" not in mix or key == "prompt_len":
            assert abs(vals.mean() - spec["mean"]) < 0.02 * spec["mean"]
    if "max_total_len" in mix:
        assert max(p + o for _, p, o in trace) <= mix["max_total_len"]
    due = [r[0] for r in trace]
    assert due == sorted(due)
    if mix["arrival"] == "poisson":
        assert abs(len(trace) / due[-1] - mix["rate_per_s"]) \
            < 0.05 * mix["rate_per_s"]
        # the trace outlasts the longest window there can be
        assert due[-1] > 51


def test_backlog_outlasts_any_window_at_ten_times_the_ledgers_rate():
    mix = manifest.traffic("backlog_short_in_mid_out")
    trace = traffic.serve_trace(mix)
    assert sum(o for _, _, o in trace) > 10 * LEDGER_TOK_S * 51
    # every request fits its slot with room to spare, and a slot turns
    # over several times in a window even at a fifth of that rate
    assert max(p + o for _, p, o in trace) <= 576
    assert mix["output_len"]["mean"] * 32 * 3 < LEDGER_TOK_S * 40


def test_the_open_loop_fills_its_percentiles():
    """A 40 s window sends some hundreds of requests, so that tens lie
    beyond the 90th percentile of TTFT, and the trace is at least
    twice the window."""
    mix = manifest.traffic("open_long_in_short_out")
    due = [d for d, _, _ in traffic.serve_trace(mix)]
    in_window = sum(d < 40 for d in due)
    assert in_window >= 500 and len(due) >= 2 * in_window
