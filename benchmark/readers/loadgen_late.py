"""How late the load generator ran: sent minus due, a percentile over
every request sent (host clock)."""
from benchmark import serve_window


def read(ctx, q=95):
    return serve_window.late_ms(ctx["t0"], ctx["sent"], q)
