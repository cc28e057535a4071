"""Host regions and device scopes (DESIGN.md §13).

``Telemetry.region`` puts the scheduler's and engine's host work on the
profiler's clock as ``TraceAnnotation``s; the paged model path names its
parts with ``jax.named_scope``.  Neither enters the replay record:

* one scheduler round under ``jax.profiler`` writes ``sched.step``,
  ``engine.decode_round`` and its five ``engine.decode.*`` phases, nested,
  into the ``.xplane.pb`` (and an admission's ``engine.*`` regions);
* the disabled registry's region is one shared no-op object;
* regions leave ``event_log()`` and ``snapshot()`` as they were;
* the ``queued`` span runs from the request's arrival to the start of
  the placement that admitted it;
* the compiled paged decode program's ops carry the scope names.
"""

import glob

import jax
import jax.numpy as jnp
import pytest

import repro.configs as C
from repro.models.model_zoo import build
from repro.serving import ServeEngine, Server
from repro.serving.server import CONTENDED_ENGINE_KW, contended_trace
from repro.serving.telemetry import NULL_TELEMETRY, Telemetry

DECODE_PHASES = ("engine.decode.stage", "engine.decode.launch",
                 "engine.decode.wait", "engine.decode.fetch",
                 "engine.decode.collect")


@pytest.fixture(scope="module")
def tiny():
    cfg = C.get("qwen3-1.7b").reduced().replace(n_layers=2, dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _paged_engine(model, params):
    return ServeEngine(model, params, max_len=32, max_batch=2, paged=True,
                       page_size=8, kv_dtype="int8")


def _host_regions(path) -> list:
    """(name, start_ns, end_ns) of every program region on the host."""
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for p in pd.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(("sched.", "engine."))),
                  key=lambda r: r[1])


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_round_regions_nest_in_the_profile(tiny, tmp_path):
    model, params = tiny
    srv = Server(_paged_engine(model, params), telemetry=Telemetry())
    srv.submit([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 4)
    srv.sched.step()                       # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    srv.submit([2, 7, 1, 8, 2, 8, 1, 8, 2, 8], 4)
    srv.sched.step()                       # admits the second, decodes both
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    regions = _host_regions(path)
    by = {}
    for r in regions:
        by.setdefault(r[0], []).append(r)
    step, = by["sched.step"]
    rnd, = by["engine.decode_round"]
    assert _inside(rnd, step)
    phases = [by[name][0] for name in DECODE_PHASES]
    assert all(len(by[name]) == 1 for name in DECODE_PHASES)
    assert all(_inside(ph, rnd) for ph in phases)
    # the phases run in order and do not overlap
    assert all(a[2] <= b[1] for a, b in zip(phases, phases[1:]))
    admit, = by["sched.admit"]
    eng_admit, = by["engine.admit"]
    first, = by["engine.first_token"]
    assert _inside(admit, step) and _inside(eng_admit, admit)
    assert _inside(first, eng_admit)
    assert len(by["engine.prefill_chunk"]) == 2      # 10 tokens, 8-token pages
    assert all(_inside(c, eng_admit) for c in by["engine.prefill_chunk"])
    emit, = by["sched.emit"]
    assert _inside(emit, step) and rnd[2] <= emit[1]


def test_null_region_is_one_shared_noop():
    a = NULL_TELEMETRY.region("engine.decode_round")
    b = NULL_TELEMETRY.region("sched.step", slot=3)
    assert a is b
    with a:
        with b:
            pass


def test_scoped_region_prefixes_its_name(tmp_path):
    tel = Telemetry()
    jax.profiler.start_trace(str(tmp_path))
    with tel.scoped("r0").region("sched.step"):
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for p in pd.planes for line in p.lines
             for e in line.events}
    assert "r0.sched.step" in names


def test_regions_leave_the_replay_record_alone():
    class Clock:
        def now(self):
            return 1.5

    tel = Telemetry(Clock())
    tel.count("sched.rounds")
    tel.instant("sched", 0, "tick")
    before = (tel.snapshot_json(), tel.event_log_json())
    with tel.region("sched.step"):
        with tel.scoped("r0").region("engine.decode_round", tokens=4):
            pass
    assert (tel.snapshot_json(), tel.event_log_json()) == before


def test_queued_span_runs_from_arrival_to_placement(tiny):
    """A request is queued from its own arrival — also when the round
    that harvests it starts later — until the placement that admits it
    begins; its prefill belongs to the slot's ``prefill`` span."""
    model, params = tiny
    eng = ServeEngine(model, params, **CONTENDED_ENGINE_KW)
    tel = Telemetry()
    srv = Server(eng, telemetry=tel)
    trace = contended_trace(1, model.cfg.vocab)
    srv.replay(trace)
    queued = {e["tid"]: e for e in tel.event_log()
              if e.get("name") == "queued"}
    assert len(queued) == len(trace)
    late = 0
    cost = srv.sched.costs.prefill_token
    for rid, h in srv.sched.handles.items():
        q = queued[rid]
        assert q["t0"] == round(h.arrival, 9)
        assert q["t1"] == pytest.approx(h.admitted_at
                                        - cost * len(h.prompt), abs=1e-9)
        harvest = next(t for t, kind, r in srv.sched.events
                       if kind == "arrive" and r == rid)
        late += harvest > h.arrival
    assert late, "no request was harvested after its arrival — weak test"


def test_paged_decode_program_names_its_parts(tiny):
    """The compiled decode loop's ops carry the model's scope names under
    ``decode_step`` (``sample`` sits beside it, in the loop body)."""
    import re

    model, params = tiny
    eng = _paged_engine(model, params)
    st = eng.sched_state()
    cache = {**eng.pool.cache, "page_table": jnp.asarray(st.pt_np),
             "pos": jnp.asarray(st.pos)}
    text = eng._decode_loop.lower(
        eng.params, cache, st.last, st.live, st.n_gen, st.stops, st.out,
        st.key, stop_on_event=False).compile().as_text()
    stacks = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("kv_read", "attention", "mlp", "head"):
        assert any("/decode_step/" in s and f"/{scope}/" in s
                   for s in stacks), scope
    assert any("/sample/" in s for s in stacks)
