"""ttft_p95_s: the 95th percentile, over every request due in the window,
of the seconds from its due time to its first token.  A request that
never got one counts the whole wait.  Host clock."""

from chipbench.stats import percentile


def read(run):
    due = run.due_in_window()
    return percentile([run.ttft(r) for r in due], 95) if due else None
