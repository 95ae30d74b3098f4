"""Host milliseconds the Executor spends a step outside the wait for
the device: `exe.last_step_timings` total minus fetch, mean over the
window's steps."""
from benchmark.readers import _serve_calls


def read(ctx, kind=None):
    if ctx["kind"] == "train":
        host = ctx["window"]["host_s"]
    else:
        host = [c[3] for c in _serve_calls.calls(ctx)
                if kind is None or c[0] == kind]
    return 1e3 * sum(host) / len(host) if host else None
