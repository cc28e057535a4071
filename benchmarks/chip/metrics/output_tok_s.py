"""output_tok_s: every output token emitted in the window over the
window's seconds.  Host clock."""


def read(run):
    n = sum(run.t_open <= t < run.t_close
            for r in run.records for t in r.token_times)
    return n / run.seconds
