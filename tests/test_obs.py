"""Observability spine (`repro.obs`): tracer schema + Perfetto export,
disabled-tracing zero-overhead contract, metrics registry, flight
recorder + postmortem artifacts, REPRO_LOG gating, and the
measured-vs-simulated skew helpers.

No jax imports here — the obs layer is dependency-free by design and
these tests must stay cheap enough for any tier-1 run.
"""
from __future__ import annotations

import json
import threading

import pytest

from repro.obs import (CONTROL_TRACK, NULL_SPAN, PLANNER_TRACK, STAGE_CAT,
                       FlightRecorder, Metrics, Tracer, device_track,
                       diff_traces, dump_postmortem, get_flight,
                       get_metrics, get_tracer, link_track, load_trace,
                       postmortem_dir, set_metrics, set_postmortem_dir,
                       set_tracer, span, span_events, stage_skew,
                       write_trace)
import importlib

from repro.obs import metrics as obsmetrics
from repro.obs import trace as obstrace

# ``from .log import log`` in the package shadows the submodule
# attribute with the function — go through importlib for the module
obslog = importlib.import_module("repro.obs.log")


@pytest.fixture(autouse=True)
def _clean_obs_globals():
    """Every test starts and ends with observability uninstalled."""
    set_tracer(None)
    set_metrics(None)
    set_postmortem_dir(None)
    get_flight().clear()
    yield
    set_tracer(None)
    set_metrics(None)
    set_postmortem_dir(None)
    get_flight().clear()


# ---------------------------------------------------------------------------
# disabled-tracing contract
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_null_singleton():
    """With no tracer installed, span() returns THE module singleton —
    no per-call allocation — and the null span absorbs the full API."""
    assert get_tracer() is None
    a = span(CONTROL_TRACK, "stage-a", cat=STAGE_CAT)
    b = span(PLANNER_TRACK, "anything-else")
    assert a is NULL_SPAN and b is NULL_SPAN
    with a as sp:
        assert sp is NULL_SPAN
        sp.set(answer=42)
        sp.event("marker", detail="ignored")
    # instants are equally inert
    obstrace.instant(CONTROL_TRACK, "nothing")


def test_null_span_has_no_instance_dict():
    """__slots__ = () — the singleton cannot accumulate per-call state,
    which is what makes sharing it safe."""
    assert not hasattr(NULL_SPAN, "__dict__")
    with pytest.raises(AttributeError):
        NULL_SPAN.leak = 1


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs what the
    profiler sink does with it."""

    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)
        self.log.append(("new", name))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False

    def set_metadata(self, **args):
        self.args.update(args)
        self.log.append(("set", self.name))


@pytest.fixture
def fake_profiler(monkeypatch):
    """A stand-in ``jax.profiler`` module, so the sink is tested without
    importing jax."""
    import sys
    import types
    mod = types.ModuleType("jax.profiler")
    mod.TraceAnnotation = _FakeAnnotation
    monkeypatch.setitem(sys.modules, "jax.profiler", mod)
    _FakeAnnotation.log = []
    return _FakeAnnotation


def test_profiler_sink_opens_one_annotation_per_span(fake_profiler):
    """Each span of the profiler sink enters and exits one
    TraceAnnotation of its name, nested as the spans are; ``set`` adds
    stats; instants are empty annotations; nothing is recorded, and an
    externally timed span is dropped."""
    tr = Tracer(sink="profiler")
    assert tr.sink == "profiler"
    with tr.span(CONTROL_TRACK, "mesh.request", cat="exec", seq=1) as req:
        with tr.span(CONTROL_TRACK, "mesh.launch", cat="exec",
                     kind="compute"):
            pass
        req.set(launches=1)
        req.event("marker")
    tr.add_complete(CONTROL_TRACK, "seg[a..b]", 0.0, 1.0, cat=STAGE_CAT)
    assert fake_profiler.log == [
        ("new", "mesh.request"), ("enter", "mesh.request"),
        ("new", "mesh.launch"), ("enter", "mesh.launch"),
        ("exit", "mesh.launch"), ("set", "mesh.request"),
        ("new", "marker"), ("enter", "marker"), ("exit", "marker"),
        ("exit", "mesh.request")]
    assert req.args == {"seq": 1, "launches": 1}
    assert len(tr) == 0 and tr.spans() == []


def test_profiler_sink_leaves_the_null_path_alone(fake_profiler):
    """Built but not installed, a profiler-sink tracer changes nothing:
    ``span`` still returns the shared NULL_SPAN and opens nothing."""
    Tracer(sink="profiler")
    assert span(CONTROL_TRACK, "mesh.launch") is NULL_SPAN
    assert fake_profiler.log == []
    set_tracer(Tracer(sink="profiler"))
    with span(CONTROL_TRACK, "mesh.launch"):
        pass
    assert fake_profiler.log == [("new", "mesh.launch"),
                                 ("enter", "mesh.launch"),
                                 ("exit", "mesh.launch")]


def test_unknown_sink_is_refused():
    with pytest.raises(ValueError, match="sink"):
        Tracer(sink="stdout")


def test_set_tracer_roundtrip():
    tr = Tracer()
    assert set_tracer(tr) is tr
    assert get_tracer() is tr
    assert set_tracer(None) is None
    assert get_tracer() is None


# ---------------------------------------------------------------------------
# recording + nesting invariants
# ---------------------------------------------------------------------------

def test_span_records_complete_event():
    tr = Tracer()
    with tr.span(CONTROL_TRACK, "work", cat="phase", graph="g"):
        pass
    (rec,) = tr.spans()
    assert rec["ph"] == "X" and rec["name"] == "work"
    assert rec["cat"] == "phase" and rec["track"] == CONTROL_TRACK
    assert rec["dur"] >= 0.0 and rec["ts"] >= 0.0
    assert rec["args"] == {"graph": "g"}


def test_nesting_depth_and_ordering():
    tr = Tracer()
    with tr.span(PLANNER_TRACK, "outer") as outer:
        with tr.span(PLANNER_TRACK, "inner") as inner:
            assert outer.depth == 0 and inner.depth == 1
        with tr.span(PLANNER_TRACK, "inner2") as inner2:
            assert inner2.depth == 1
    recs = tr.spans()
    # spans() sorts by start time: outer opened first
    assert [r["name"] for r in recs] == ["outer", "inner", "inner2"]
    assert [r["depth"] for r in recs] == [0, 1, 1]
    # children nest inside the parent interval
    t0, t1 = recs[0]["ts"], recs[0]["ts"] + recs[0]["dur"]
    for child in recs[1:]:
        assert t0 <= child["ts"]
        assert child["ts"] + child["dur"] <= t1


def test_nesting_is_per_thread():
    tr = Tracer()
    depths = []

    def worker():
        with tr.span("dev0", "t") as sp:
            depths.append(sp.depth)

    with tr.span("dev0", "main-open"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    # the worker thread starts its own stack: depth 0, not 1
    assert depths == [0]


def test_span_exit_on_exception_marks_error():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span(CONTROL_TRACK, "boom"):
            raise RuntimeError("x")
    (rec,) = tr.spans()
    assert rec["args"].get("error") is True


def test_add_complete_and_filtering():
    tr = Tracer()
    tr.add_complete(CONTROL_TRACK, "seg[a..b]", 10.0, 5.0, cat=STAGE_CAT)
    tr.add_complete(device_track(0), "seg[a..b]", 10.0, 4.0, cat="device")
    tr.add_complete(link_track(1), "xfer", 15.0, 1.0, cat="link")
    assert len(tr.spans(cat=STAGE_CAT)) == 1
    assert len(tr.spans(track=device_track(0))) == 1
    assert len(tr.spans()) == 3


def test_track_tids_assigned_in_first_use_order():
    tr = Tracer()
    assert tr.ensure_track("dev1") == 1
    assert tr.ensure_track("dev0") == 2
    assert tr.ensure_track("dev1") == 1


# ---------------------------------------------------------------------------
# Perfetto export schema
# ---------------------------------------------------------------------------

def _sample_tracer():
    tr = Tracer()
    with tr.span(CONTROL_TRACK, "stage-a", cat=STAGE_CAT):
        pass
    tr.instant(PLANNER_TRACK, "detect", cat="planner")
    tr.add_complete(device_track(0), "stage-a", 1.0, 2.0, cat="device")
    return tr


def test_perfetto_event_fields():
    doc = _sample_tracer().to_perfetto()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert events, "empty export"
    metas = [e for e in events if e["ph"] == "M"]
    assert {"process_name"} | {"thread_name"} == {m["name"] for m in metas}
    for ev in events:
        assert ev["ph"] in ("M", "X", "i")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], float) and ev["ts"] >= 0.0
            assert isinstance(ev["dur"], float) and ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    # non-meta events sorted by ts
    ts = [e["ts"] for e in events if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_write_trace_roundtrip(tmp_path):
    path = str(tmp_path / "t.trace.json")
    tr = _sample_tracer()
    assert write_trace(path, tr) == path
    loaded = load_trace(path)
    assert loaded == tr.to_perfetto()
    # valid JSON on disk, not just via load_trace
    with open(path) as f:
        json.load(f)


def test_write_trace_merges_distinct_pids(tmp_path):
    measured = _sample_tracer()
    sim = Tracer(process="simulated", pid=2)
    sim.add_complete(device_track(0), "stage-a", 0.0, 3.0, cat=STAGE_CAT)
    path = str(tmp_path / "merged.trace.json")
    write_trace(path, measured, sim)
    loaded = load_trace(path)
    pids = {e["pid"] for e in loaded["traceEvents"]}
    assert pids == {1, 2}
    names = {e["args"]["name"] for e in loaded["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert names == {"measured", "simulated"}


def test_span_events_resolves_tracks(tmp_path):
    path = str(tmp_path / "t.trace.json")
    write_trace(path, _sample_tracer())
    loaded = load_trace(path)
    evs = span_events(loaded, cat=STAGE_CAT, pid=1)
    assert [e["name"] for e in evs] == ["stage-a"]
    assert evs[0]["track"] == CONTROL_TRACK
    assert span_events(loaded, track=device_track(0))[0]["cat"] == "device"


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_metrics_counters_and_labels():
    m = Metrics()
    m.inc("hits")
    m.inc("hits", 2.0)
    m.inc("hits", table="i")
    assert m.counter_value("hits") == 3.0
    assert m.counter_value("hits", table="i") == 1.0
    snap = m.snapshot()
    assert snap["counters"]["hits"] == 3.0
    assert snap["counters"]['hits{table="i"}'] == 1.0


def test_metrics_gauge_overwrites():
    m = Metrics()
    m.gauge("beta", 0.5, graph="g")
    m.gauge("beta", 0.7, graph="g")
    assert m.gauge_value("beta", graph="g") == 0.7
    assert m.gauge_value("beta") is None


def test_metrics_histogram_buckets():
    m = Metrics()
    for v in (0.5, 1.0, 3.0, 3.0):
        m.observe("lat", v)
    h = m.snapshot()["histograms"]["lat"]
    assert h["count"] == 4
    assert h["sum"] == pytest.approx(7.5)
    assert h["min"] == 0.5 and h["max"] == 3.0
    # 0.5 -> le_2^-1, 1.0 -> le_2^0, 3.0 -> le_2^2 (twice)
    assert h["buckets"] == {"le_2^-1": 1, "le_2^0": 1, "le_2^2": 2}


def test_metrics_export(tmp_path):
    m = Metrics()
    m.inc("n", 5.0)
    path = str(tmp_path / "metrics.json")
    assert m.export(path) == path
    with open(path) as f:
        assert json.load(f)["counters"]["n"] == 5.0


def test_free_functions_noop_until_installed():
    assert get_metrics() is None
    obsmetrics.inc("ghost")
    obsmetrics.gauge("ghost", 1.0)
    obsmetrics.observe("ghost", 1.0)
    m = set_metrics(Metrics())
    obsmetrics.inc("real")
    assert m.counter_value("real") == 1.0
    assert m.counter_value("ghost") == 0.0


# ---------------------------------------------------------------------------
# flight recorder + postmortems
# ---------------------------------------------------------------------------

def test_flight_ring_bounds_and_eviction():
    fr = FlightRecorder(capacity=3)
    for i in range(5):
        fr.record("tick", i=i)
    assert len(fr) == 3
    assert fr.total_recorded == 5
    assert [e["i"] for e in fr.events()] == [2, 3, 4]
    assert all(e["kind"] == "tick" and e["t_us"] >= 0.0
               for e in fr.events())
    fr.clear()
    assert len(fr) == 0 and fr.total_recorded == 5


def test_flight_capacity_validated():
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_postmortem_noop_without_directory(monkeypatch):
    monkeypatch.delenv("REPRO_POSTMORTEM_DIR", raising=False)
    assert postmortem_dir() is None
    assert dump_postmortem("unit_test") is None


def test_postmortem_dump_contents(tmp_path):
    set_postmortem_dir(str(tmp_path))
    get_flight().record("stage_dispatch", label="seg[a..b]", attempt=0)
    tr = set_tracer(Tracer())
    with tr.span(CONTROL_TRACK, "seg[a..b]", cat=STAGE_CAT):
        pass
    path = dump_postmortem("stage_timeout",
                           context={"label": "seg[a..b]", "timeout_s": 1.0})
    assert path is not None and path.startswith(str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "stage_timeout"
    assert doc["context"]["label"] == "seg[a..b]"
    assert any(e["kind"] == "stage_dispatch" for e in doc["events"])
    assert [s["name"] for s in doc["spans"]] == ["seg[a..b]"]


def test_postmortem_env_var(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_POSTMORTEM_DIR", str(tmp_path))
    assert postmortem_dir() == str(tmp_path)
    path = dump_postmortem("refine_oscillation", context={"cycle": [1, 2]})
    assert path is not None
    with open(path) as f:
        assert json.load(f)["context"]["cycle"] == [1, 2]
    # explicit dir overrides env; None defers back
    set_postmortem_dir(str(tmp_path / "sub"))
    assert postmortem_dir() == str(tmp_path / "sub")
    set_postmortem_dir(None)
    assert postmortem_dir() == str(tmp_path)


# ---------------------------------------------------------------------------
# REPRO_LOG gating
# ---------------------------------------------------------------------------

def test_log_quiet_by_default(monkeypatch, capsys):
    for off in ("", "0", "off", "false", "OFF"):
        monkeypatch.setenv("REPRO_LOG", off)
        assert not obslog.enabled()
        obslog.log("train.step", step=1, loss=0.5)
    monkeypatch.delenv("REPRO_LOG")
    obslog.log("train.step", step=1)
    out = capsys.readouterr()
    assert out.out == "" and out.err == ""


def test_log_human_mode(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_LOG", "1")
    assert obslog.enabled()
    obslog.log("train.step", step=3, loss=0.25)
    out = capsys.readouterr()
    assert out.out == ""
    assert out.err == "[train.step] step=3 loss=0.25\n"


def test_log_json_mode(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_LOG", "json")
    obslog.log("serve.timing", batch=4, prefill_ms=1.5)
    line = capsys.readouterr().err.strip()
    assert json.loads(line) == {"event": "serve.timing", "batch": 4,
                                "prefill_ms": 1.5}


# ---------------------------------------------------------------------------
# skew helpers
# ---------------------------------------------------------------------------

def test_stage_skew_ratios_and_summary():
    stages = [
        {"kind": "compute", "label": "seg[a..b]",
         "sim_s": 1.0, "measured_s": 2.0},
        {"kind": "sync", "label": "bound@b",
         "sim_s": 0.5, "measured_s": 0.25},
        {"kind": "sync", "label": "gather",
         "sim_s": 0.0, "measured_s": 0.1},      # unpaired: sim zero
        {"kind": "compute", "label": "seg[c..c]",
         "sim_s": 1.0, "measured_s": None},     # unpaired: missing
    ]
    skew = stage_skew(stages)
    assert skew["n_stages"] == 4 and skew["n_paired"] == 2
    ratios = [p["ratio"] for p in skew["per_stage"]]
    assert ratios == [2.0, 0.5, None, None]
    assert skew["median_ratio"] == pytest.approx(1.25)
    assert skew["min_ratio"] == 0.5 and skew["max_ratio"] == 2.0
    assert skew["max_abs_log2"] == pytest.approx(1.0)


def test_stage_skew_empty():
    skew = stage_skew([])
    assert skew["n_stages"] == 0 and skew["n_paired"] == 0
    assert skew["median_ratio"] is None
    assert skew["max_abs_log2"] is None


def _stage_trace(pid, names_durs, process):
    tr = Tracer(process=process, pid=pid)
    t = 0.0
    for name, dur in names_durs:
        tr.add_complete(CONTROL_TRACK, name, t, dur, cat=STAGE_CAT)
        t += dur
    return tr.to_perfetto()


def test_diff_traces_match():
    m = _stage_trace(1, [("a", 2.0), ("b", 1.0)], "measured")
    s = _stage_trace(2, [("a", 1.0), ("b", 1.0)], "simulated")
    d = diff_traces(m, s)
    assert d["structure_match"]
    assert d["only_measured"] == [] and d["only_simulated"] == []
    assert [(p["name"], p["ratio"]) for p in d["pairs"]] == \
        [("a", 2.0), ("b", 1.0)]


def test_diff_traces_mismatch():
    m = _stage_trace(1, [("a", 1.0), ("x", 1.0)], "measured")
    s = _stage_trace(2, [("a", 1.0), ("b", 1.0)], "simulated")
    d = diff_traces(m, s)
    assert not d["structure_match"]
    assert d["only_measured"] == ["x"]
    assert d["only_simulated"] == ["b"]
    assert [p["name"] for p in d["pairs"]] == ["a"]
