"""engine.decode_round_ms.tp4: host seconds of every ``serve_step`` call
in the window over the decode iterations they ran, in milliseconds — a
round of the tensor-parallel engine, all-reduces included."""


def read(run):
    rounds = [s for s in run.window_spans("serve_step")
              if s.info["iterations"]]
    its = sum(s.info["iterations"] for s in rounds)
    return sum(s.end - s.start for s in rounds) / its * 1e3 if its else None
