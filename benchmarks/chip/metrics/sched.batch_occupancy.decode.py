"""sched.batch_occupancy.decode: the mean number of live slots per
``serve_step`` round in the window (the engine decodes every slot of its
``max_batch``; this counts the ones that carry a request)."""


def read(run):
    rounds = run.window_spans("serve_step")
    return (sum(s.info["live"] for s in rounds) / len(rounds)
            if rounds else None)
