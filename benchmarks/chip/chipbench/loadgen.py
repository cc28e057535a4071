"""Offering a cell's traffic to the server on the host's clock.

The window drives ``serving.server.Server`` over one ``ServeEngine``
through ``AsyncScheduler.step(more_arrivals=True)``.  The scheduler's
injected clock is the host clock (``advance`` does nothing: time passes by
itself), and every request is submitted when it is due with
``arrival=<due time>``, so its time to first token counts from then.

``TimedEngine`` stands between scheduler and engine: it times every
``sched_admit`` and ``serve_step`` call on the host clock inside a
``jax.profiler.TraceAnnotation`` (``bench.sched_admit``,
``bench.serve_step``), so host spans and device trace share one clock.
Both calls return host values, so each span holds its device work.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

now = time.perf_counter


class WallClock:
    """The scheduler's clock: the host's, which moves by itself."""

    def now(self) -> float:
        return now()

    def advance(self, dt: float) -> None:
        pass


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    info: dict


class TimedEngine:
    """The engine, with a host span around each admission and round."""

    def __init__(self, engine):
        object.__setattr__(self, "_engine", engine)
        object.__setattr__(self, "spans", [])

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        setattr(self._engine, name, value)

    def sched_admit(self, st, slot, prompt, stop):
        t0 = now()
        with jax.profiler.TraceAnnotation("bench.sched_admit"):
            first = self._engine.sched_admit(st, slot, prompt, stop)
        t1 = now()
        adm = st.adm[slot] if first is not None and st.adm else None
        self.spans.append(Span("sched_admit", t0, t1, {
            "prompt": id(prompt), "prompt_len": len(prompt),
            "admitted": first is not None,
            "chunks": adm.n_chunks - adm.compute_from if adm else 0}))
        return first

    def serve_step(self, st, quantum=1):
        act = st.live & (st.n_gen < st.stops)
        pos = (np.array(st.pos) if st.pos is not None
               else np.asarray(st.cache["pos"]))
        t0 = now()
        with jax.profiler.TraceAnnotation("bench.serve_step"):
            toks, done = self._engine.serve_step(st, quantum)
        t1 = now()
        # attention context of every token decoded: a slot at position p
        # attends p + 1 keys for its first token, p + 2 for its second, ...
        ctx = sum(sum(int(pos[b]) + i + 1 for i in range(len(t)))
                  for b, t in toks.items())
        self.spans.append(Span("serve_step", t0, t1, {
            "live": int(act.sum()), "batch": int(len(st.live)),
            "tokens": sum(len(t) for t in toks.values()),
            "iterations": max((len(t) for t in toks.values()), default=0),
            "ctx_sum": ctx}))
        return toks, done


@dataclasses.dataclass
class Record:
    """One request as the load generator saw it (host-clock seconds)."""

    rid: int
    due: float
    sent: float
    prompt_len: int
    max_new: int
    handle: object
    token_times: list = dataclasses.field(default_factory=list)
    admit_start: float | None = None

    @property
    def first_token(self):
        return self.token_times[0] if self.token_times else None

    @property
    def finished(self) -> bool:
        return self.handle.finished_at is not None


class Session:
    """One server over one engine, and the requests offered to it."""

    def __init__(self, engine, quantum: int):
        from repro.serving.server import Server

        self.engine = TimedEngine(engine)
        self.server = Server(self.engine, clock=WallClock(), quantum=quantum)
        self.sched = self.server.sched
        self.records: dict[int, Record] = {}

    def _on_token(self, h, tok, t) -> None:
        self.records[h.rid].token_times.append(t)

    def submit(self, req, due: float) -> Record:
        h = self.server.submit(list(req.prompt), req.max_new, arrival=due,
                               allow_past_arrival=True,
                               on_token=self._on_token)
        rec = Record(h.rid, due, now(), len(req.prompt), req.max_new, h)
        self.records[h.rid] = rec
        return rec

    def step(self) -> bool:
        return self.sched.step(more_arrivals=True)

    def run_until(self, done, deadline: float) -> bool:
        """Step until ``done()`` or the deadline; False if cut."""
        while not done():
            if now() >= deadline:
                return False
            if not self.step():
                time.sleep(0.001)
        return True

    def admit_starts(self) -> None:
        """Give each record the start of the admission span that placed
        it (spans name their prompt list, which the handle holds)."""
        by_prompt = {id(r.handle.prompt): r for r in self.records.values()}
        for s in self.engine.spans:
            if s.name == "sched_admit" and s.info["admitted"]:
                r = by_prompt.get(s.info["prompt"])
                if r is not None and r.admit_start is None:
                    r.admit_start = s.start


def run_open(session: Session, requests: list, seconds: float,
             on_open=None, on_close=None):
    """Offer ``requests`` at their due times for ``seconds``; returns
    (t_open, t_close).  Requests due in the window but not yet sent when
    the last round ends are sent then, late."""
    t_open = now()
    if on_open is not None:
        on_open()
    t_close = t_open + seconds
    i, n = 0, len(requests)
    while now() < t_close:
        t = now()
        while i < n and t_open + requests[i].due <= t:
            session.submit(requests[i], t_open + requests[i].due)
            i += 1
        if not session.step():
            nxt = t_open + requests[i].due if i < n else t_close
            time.sleep(max(0.0, min(nxt, t_close) - now()))
    if on_close is not None:
        on_close()
    while i < n and requests[i].due < seconds:
        session.submit(requests[i], t_open + requests[i].due)
        i += 1
    return t_open, t_close


def run_closed(session: Session, next_request, clients: list, seconds: float,
               on_open=None, on_close=None):
    """Closed loop: ``clients[c]`` is client c's request in flight; when it
    finishes the client sends ``next_request(c)`` at once.  Returns
    (t_open, t_close)."""
    t_open = now()
    if on_open is not None:
        on_open()
    t_close = t_open + seconds
    while now() < t_close:
        session.step()
        for c, rec in enumerate(clients):
            if rec.finished:
                clients[c] = session.submit(next_request(c), now())
    if on_close is not None:
        on_close()
    return t_open, t_close
