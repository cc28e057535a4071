"""sched.queue_wait_p95_s.chat: the 95th percentile, over the requests due
in the window that were admitted, of the seconds from due time to the
start of the ``sched_admit`` call that placed them.  Host clock."""

from chipbench.stats import percentile


def read(run):
    waits = [r.admit_start - r.due for r in run.due_in_window()
             if r.admit_start is not None]
    return percentile(waits, 95) if waits else None
