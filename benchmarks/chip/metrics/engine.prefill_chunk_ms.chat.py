"""engine.prefill_chunk_ms.chat: host seconds of every ``sched_admit``
call in the window over the prefill chunks those calls computed, in
milliseconds (an admission is its chunks plus the first token's
sampling)."""


def read(run):
    adm = [s for s in run.window_spans("sched_admit") if s.info["chunks"]]
    chunks = sum(s.info["chunks"] for s in adm)
    return (sum(s.end - s.start for s in adm) / chunks * 1e3
            if chunks else None)
