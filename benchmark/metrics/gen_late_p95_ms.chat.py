"""How late the load generator ran: 95th percentile of sent minus due over
the requests due in the window. A starved generator must not read as a fast
server."""
from benchmark.readers import percentile


def read(ctx):
    late = [(r.sent - r.due) * 1e3 for r in ctx["facts"]["due"]
            if r.sent is not None and r.due is not None]
    return percentile(late, 95)
