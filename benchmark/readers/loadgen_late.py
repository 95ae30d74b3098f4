"""How late the load generator ran: sent minus due, a percentile over
every request sent (host clock)."""
import numpy as np


def read(ctx, q=95):
    late = [(r.sent - (ctx["t0"] + r.due)) * 1e3 for r in ctx["sent"]
            if r.sent is not None]
    return float(np.percentile(late, q)) if late else None
