"""Observability layer (repro.obs): metrics registry (log-bucket
histogram percentiles vs the numpy reference, reset semantics, Prometheus
export), Chrome-trace tracer (schema-valid export, disabled-tracer cost
model), engine request-lifecycle events surviving preemption +
re-prefill, runtime kernel-dispatch telemetry, and run metadata."""
import json

import numpy as np
import pytest

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               default_registry)
from repro.obs.runmeta import run_metadata
from repro.obs.trace import NULL_TRACER, Tracer, validate_chrome_trace


# --------------------------------------------------------------------------- #
# histograms
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("dist,lo", [("uniform", 1.0), ("lognormal", None)])
def test_histogram_percentiles_match_numpy(dist, lo):
    """Log buckets at base 1.05 bound the relative error vs the exact
    sorted-sample percentile by roughly one bucket width (~5%)."""
    rng = np.random.default_rng(0)
    xs = (rng.uniform(1.0, 100.0, 5000) if dist == "uniform"
          else rng.lognormal(mean=2.0, sigma=1.0, size=5000))
    h = Histogram("t")
    for x in xs:
        h.record(float(x))
    for p in (50, 90, 99):
        ref = float(np.percentile(xs, p))
        got = h.percentile(p)
        assert abs(got - ref) / ref < 0.08, (dist, p, got, ref)
    assert h.count == len(xs)
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.mean == pytest.approx(xs.mean())


def test_histogram_edge_cases():
    h = Histogram("t")
    assert h.percentile(50) == 0.0 and h.mean == 0.0       # empty
    s = h.summary()
    assert s["count"] == 0 and s["min"] == 0.0 and s["max"] == 0.0
    h.record(0.0)                                           # underflow bucket
    h.record(-3.0)
    assert h.percentile(50) <= 0.0
    h2 = Histogram("u")
    h2.record(7.0)                                          # single sample:
    assert h2.percentile(50) == pytest.approx(7.0)          # clamped to
    assert h2.percentile(99) == pytest.approx(7.0)          # exact extrema


def test_histogram_percentile_extremes_match_numpy():
    """p=0 must return the recorded MINIMUM exactly (the old rank-0 walk
    stopped at the first bucket and returned its midpoint — badly wrong
    for skewed data) and p=100 the maximum; both interact correctly with
    the underflow bucket that absorbs every non-positive sample."""
    rng = np.random.default_rng(3)
    xs = rng.lognormal(mean=1.0, sigma=2.0, size=500)
    h = Histogram("t")
    for x in xs:
        h.record(float(x))
    assert h.percentile(0) == float(xs.min())       # exact, not a midpoint
    assert h.percentile(100) == float(xs.max())
    assert h.percentile(-5) == float(xs.min())      # clamped below 0
    assert h.percentile(101) == float(xs.max())     # clamped above 100
    # rank-1 inside the underflow bucket is the recorded min, not 0
    h2 = Histogram("u")
    for v in (-3.0, 0.0, 5.0, 40.0):
        h2.record(v)
    assert h2.percentile(0) == -3.0
    assert h2.percentile(25) == -3.0
    assert h2.percentile(100) == 40.0


def test_counter_gauge_and_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("ticks", unit="ticks")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert reg.counter("ticks") is c                # get-or-create identity
    g = reg.gauge("occ")
    g.set(0.75)
    assert reg.gauge("occ").value == 0.75
    with pytest.raises(TypeError, match="already registered"):
        reg.histogram("ticks")                      # type mismatch is loud


def test_registry_reset_zeroes_every_series():
    reg = MetricsRegistry()
    reg.counter("c").inc(9)
    reg.gauge("g").set(1.5)
    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0):
        h.record(v)
    reg.reset()
    # registration survives; every value is zeroed
    assert reg.names() == ["c", "g", "h"]
    assert reg.counter("c").value == 0
    assert reg.gauge("g").value == 0.0
    assert h.count == 0 and h.total == 0.0 and h._buckets == {}
    d = reg.to_dict()
    assert d["c"]["value"] == 0 and d["h"]["count"] == 0


def test_registry_json_and_prometheus_export():
    reg = MetricsRegistry()
    reg.counter("ticks", unit="ticks").inc(3)
    reg.gauge("occ").set(0.5)
    reg.histogram("lat_ms", unit="ms").record(10.0)
    d = reg.to_dict()
    assert d["ticks"] == {"type": "counter", "unit": "ticks", "value": 3}
    assert d["lat_ms"]["type"] == "histogram" and d["lat_ms"]["count"] == 1
    json.dumps(d)                                   # JSON-serializable
    text = reg.prometheus_text()
    assert "# TYPE repro_ticks counter\nrepro_ticks 3" in text
    assert "# TYPE repro_occ gauge\nrepro_occ 0.5" in text
    assert 'repro_lat_ms{quantile="0.5"}' in text
    assert "repro_lat_ms_sum 10.0" in text and "repro_lat_ms_count 1" in text


# --------------------------------------------------------------------------- #
# tracer
# --------------------------------------------------------------------------- #
def test_tracer_exports_valid_chrome_trace(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("tick", tick=1):
        with tr.span("dispatch", cat="kernel", lanes=2):
            pass
    tr.instant("ADMITTED", rid=0, slot=1)
    tr.begin_async("req", 7, prompt_len=5)
    tr.counter("occupancy", 0.5)
    tr.end_async("req", 7, outcome="finished")
    obj = tr.export()
    n = validate_chrome_trace(obj)
    assert n == 1 + 6                               # process_name meta + events
    by_ph = {}
    for ev in obj["traceEvents"]:
        by_ph.setdefault(ev["ph"], []).append(ev)
    assert len(by_ph["X"]) == 2 and all("dur" in e for e in by_ph["X"])
    # inner span closed first -> recorded first; nesting visible via ts/dur
    outer = next(e for e in by_ph["X"] if e["name"] == "tick")
    inner = next(e for e in by_ph["X"] if e["name"] == "dispatch")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert by_ph["b"][0]["id"] == by_ph["e"][0]["id"] == 7
    assert by_ph["i"][0]["args"] == {"rid": 0, "slot": 1}
    # round-trips through the file writer
    p = tmp_path / "trace.json"
    tr.write(str(p))
    assert validate_chrome_trace(json.load(open(p))) == n


def test_tracer_disabled_records_nothing():
    tr = Tracer(enabled=False)
    ctx = tr.span("tick")
    with ctx:
        tr.instant("X")
        tr.begin_async("req", 1)
        tr.counter("c", 1.0)
    assert tr.events == []
    assert tr.span("other") is ctx                  # shared no-op context
    assert NULL_TRACER.events == []
    assert validate_chrome_trace(NULL_TRACER.export()) == 1   # meta only


def test_tracer_clear_resets_epoch():
    tr = Tracer(enabled=True)
    with tr.span("a"):
        pass
    tr.clear()
    assert tr.events == []
    with tr.span("b"):
        pass
    assert tr.events[0]["ts"] >= 0                  # new epoch, ts stays valid
    validate_chrome_trace(tr.export())


def test_tracer_export_lays_over_a_profiler_trace(tmp_path):
    """The export's epoch on the wall clock the profiler stamps its events
    with: an annotated span lands where the device trace has it."""
    import glob
    import time

    import jax
    from jax.profiler import ProfileData
    before = time.time_ns()
    tr = Tracer(enabled=True)
    assert before <= tr.epoch_wall_ns <= time.time_ns()
    jax.profiler.start_trace(str(tmp_path))
    with tr.span("engine.probe", annotate=True):
        time.sleep(0.01)
    jax.profiler.stop_trace()
    obj = tr.export()
    validate_chrome_trace(obj)
    assert obj["otherData"] == {"epoch_perf_counter_ns": tr.epoch_ns,
                                "epoch_wall_ns": tr.epoch_wall_ns}
    ev = next(e for e in obj["traceEvents"] if e["name"] == "engine.probe")
    pd = ProfileData.from_file(glob.glob(
        str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    start = dict(next(p for p in pd.planes
                      if p.name == "Task Environment").stats)[
        "profile_start_time"]
    xev = next(e for p in pd.planes for line in p.lines for e in line.events
               if e.name == "engine.probe")
    at = tr.epoch_wall_ns + ev["ts"] * 1e3 - start    # on the trace's clock
    assert abs(at - xev.start_ns) < 1e6
    assert abs(ev["dur"] * 1e3 - xev.duration_ns) < 1e6


def test_validate_chrome_trace_rejects_malformed():
    ok = {"name": "a", "ph": "i", "pid": 0, "tid": 0, "ts": 1.0, "s": "t"}
    validate_chrome_trace({"traceEvents": [ok]})
    bad = [
        {"traceEvents": [{**ok, "ph": "Z"}]},                 # unknown phase
        {"traceEvents": [{k: v for k, v in ok.items() if k != "ts"}]},
        {"traceEvents": [{**ok, "ph": "X"}]},                 # X without dur
        {"traceEvents": [{**ok, "ph": "b"}]},                 # async sans id
        {"traceEvents": [{**ok, "ts": -1.0}]},
        {"traceEvents": "nope"},
        {"events": []},
    ]
    for obj in bad:
        with pytest.raises(ValueError):
            validate_chrome_trace(obj)


# --------------------------------------------------------------------------- #
# engine lifecycle + dispatch telemetry (slow half: real engine runs)
# --------------------------------------------------------------------------- #
def _engine(tracer=None, num_pages=48, slots=4, **ecfg):
    import jax
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.scheduler import EngineConfig, PagedEngine
    cfg = get_config("llama3.2-3b").reduced().replace(connection="fal")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, PagedEngine(cfg, params, EngineConfig(
        page_size=8, num_pages=num_pages, slots=slots, prefill_chunk=8,
        max_seq=64, **ecfg), tracer=tracer)


def _events(tracer, rid):
    return [e["name"] for e in tracer.events
            if e.get("args", {}).get("rid") == rid
            or (e["ph"] in ("b", "e") and e.get("id") == rid)]


def test_engine_lifecycle_events_survive_preemption():
    """Tight page pool: a preempted request's trace must show the full
    QUEUED -> ADMITTED -> PREFILL -> ... -> PREEMPTED -> ADMITTED ->
    PREFILL -> DECODE -> FINISHED arc, with its async req span closed
    exactly once."""
    import numpy as np_
    from repro.serve.scheduler import ServeRequest
    tracer = Tracer(enabled=True)
    cfg, eng = _engine(tracer=tracer, num_pages=9)
    rng = np_.random.default_rng(1)
    for i in range(10):
        eng.submit(ServeRequest(rid=i, prompt=rng.integers(0, cfg.vocab,
                                                           4 + i % 7),
                                max_new=6 + 3 * (i % 3)))
    done = eng.run()
    assert len(done) == 10
    st = eng.stats()
    assert st["preemptions"] > 0
    victim = next(r.rid for r in done if r.preemptions > 0)
    seq = _events(tracer, victim)
    i_pre = seq.index("PREEMPTED")
    assert seq[:3] == ["req", "QUEUED", "ADMITTED"]   # b-event then instants
    assert "PREFILL" in seq[:i_pre]                   # first residency
    after = seq[i_pre:]
    assert "ADMITTED" in after and "PREFILL" in after # re-admitted+re-prefill
    assert "DECODE" in after and after[-2:] == ["FINISHED", "req"]
    assert seq.count("req") == 2                      # one b + one e
    # every request's async span opens and closes exactly once
    for r in done:
        s = _events(tracer, r.rid)
        assert s.count("req") == 2 and s.count("FINISHED") == 1
    validate_chrome_trace(tracer.export())
    # engine-measured latency summaries populated
    assert st["ttft_ms"]["count"] == 10 and st["ttft_ms"]["p50"] > 0
    assert st["inter_token_ms"]["count"] > 0
    assert st["queue_wait_ticks"]["count"] >= 10      # re-admissions count too


def test_engine_stats_reset_zeroes_registry_and_trace():
    from repro.serve.scheduler import ServeRequest
    tracer = Tracer(enabled=True)
    cfg, eng = _engine(tracer=tracer)
    eng.submit(ServeRequest(rid=0, prompt=np.arange(6) % cfg.vocab,
                            max_new=4))
    eng.run()
    assert eng.metrics.counter("engine_ticks_total").value > 0
    assert tracer.events
    eng.reset_stats()
    assert tracer.events == []
    for name in eng.metrics.names():
        s = eng.metrics.get(name)
        assert getattr(s, "count", getattr(s, "value", 0)) in (0, 0.0), name
    st = eng.stats()
    assert st["ticks"] == 0 and st["ttft_ms"]["count"] == 0


TICK_PHASES = ("engine.admit", "engine.plan", "engine.upload",
               "engine.dispatch", "engine.sync", "engine.consume")


@pytest.mark.parametrize("spec", [0, 4], ids=["plain", "speculative"])
def test_engine_tick_phases_nest_in_each_tick(spec):
    """Each tick's host phases, in order and inside its ``engine.tick``
    span, on the plain and the speculative path alike."""
    from repro.serve.scheduler import ServeRequest
    tracer = Tracer(enabled=True)
    extra = dict(spec_tokens=spec, draft_blocks=1) if spec else {}
    cfg, eng = _engine(tracer=tracer, **extra)
    for i in range(3):
        eng.submit(ServeRequest(rid=i, prompt=np.arange(5 + 4 * i) % cfg.vocab,
                                max_new=5))
    eng.run()
    xs = [e for e in tracer.events if e["ph"] == "X"]
    ticks = [e for e in xs if e["name"] == "engine.tick"]
    assert len(ticks) == eng.ticks
    dispatched = 0
    for t in ticks:
        inside = [e for e in xs if e["name"] in TICK_PHASES
                  and t["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= t["ts"] + t["dur"] + 1e-3]
        names = [e["name"] for e in sorted(inside, key=lambda e: e["ts"])]
        assert names in (list(TICK_PHASES[:2]), list(TICK_PHASES)), names
        dispatched += names == list(TICK_PHASES)
    assert dispatched == eng.dispatches > 0
    assert sum(e["name"] == "engine.dispatch" for e in xs) == eng.dispatches
    # the dispatch histogram closes after the sync: one sample a dispatch
    assert eng.stats()["dispatch_ms"]["count"] == eng.dispatches


def test_engine_null_tracer_costs_no_annotation(monkeypatch):
    """The default tracer records nothing and enters no
    ``TraceAnnotation``; an enabled one annotates every phase."""
    import jax
    from repro.serve.scheduler import ServeRequest
    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    for tracer in (None, Tracer(enabled=True)):
        cfg, eng = _engine(tracer=tracer)
        eng.submit(ServeRequest(rid=0, prompt=np.arange(6) % cfg.vocab,
                                max_new=3))
        eng.run()
        if tracer is None:
            assert made == [] and NULL_TRACER.events == []
    assert set(TICK_PHASES) | {"engine.tick"} <= set(made)


def test_kernel_dispatch_paths_runtime_measured():
    """The engine run above traced the packed paged-attention dispatcher;
    on the CPU backend the registry must report cpu-fallback for it, and
    the trace-count counter must live in the default registry."""
    import jax
    from repro.kernels import ops
    from repro.serve.scheduler import ServeRequest
    tracer_cfg, eng = _engine()
    eng.submit(ServeRequest(rid=0, prompt=np.arange(6) % tracer_cfg.vocab,
                            max_new=3))
    eng.run()
    paths = ops.dispatch_paths()
    assert "paged_packed_attention" in paths
    if jax.default_backend() == "cpu":
        assert paths["paged_packed_attention"] == "cpu-fallback"
    name = f"kernel_dispatch_total.paged_packed_attention." \
           f"{paths['paged_packed_attention']}"
    assert default_registry().counter(name).value >= 1
    # engine stats' dispatch telemetry and BENCH stamping both read this map
    assert set(paths.values()) <= {"fused-tpu", "interpret", "cpu-fallback"}


def test_run_metadata_shape():
    meta = run_metadata(timestamp=123.0, repo_dir=".",
                        dispatch_paths={"x": "cpu-fallback"})
    for k in ("git_sha", "jax_version", "backend", "device_kind",
              "device_count", "python", "platform"):
        assert k in meta, k
    assert meta["timestamp"] == 123.0
    assert meta["dispatch_paths"] == {"x": "cpu-fallback"}
    assert isinstance(meta["device_count"], int) and meta["device_count"] >= 1
    json.dumps(meta)                                  # stampable into JSON
    # omitted optionals stay absent (BENCH files stay minimal)
    assert "timestamp" not in run_metadata()
