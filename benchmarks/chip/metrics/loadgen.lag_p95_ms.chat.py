"""loadgen.lag_p95_ms.chat: how late the load generator sent requests —
the 95th percentile of send time minus due time over every request due in
the window, in milliseconds.  A request falls late when the loop is inside
a long admission or round at its due time.  Host clock."""

from chipbench.stats import percentile


def read(run):
    due = run.due_in_window()
    return percentile([(r.sent - r.due) * 1e3 for r in due], 95) \
        if due else None
