"""Mesh executor: multi-device `Session(..., ExecConfig(executor="mesh"))`.

Two tiers, following the repo's multi-device convention
(``test_multidevice.py``): the main test process keeps jax at 1 device,
so everything that needs a real device mesh runs in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

In-process (1 device):
  * degenerate 1-node plans bypass collectives — the mesh path must run
    (and match the local executor bit-exactly) with a single device and
    no mesh;
  * argument validation, ``to_occupancy`` arithmetic and the
    stage-decomposition validator as pure functions;
  * ``refine_with_simulator(occupancy_fn=...)`` consumes measured
    occupancy in place of the simulator.

Subprocess (8 fake devices, ``slow``):
  * equivalence vs the single-process path on every ``EDGE_MODELS`` entry
    (chains and branched DAGs) at node counts 2/4/8 with searched plans,
    scale-normalized tolerance as in PR 5, plus exact ``ExecStats``
    geometry equality;
  * ``backend="pallas"`` slots into the per-device programs unchanged;
  * measured stage structure (``instrument=True, overlap=False``)
    matches ``simsched.build_stages`` 1:1 and compute stages carry
    per-device completion times;
  * the overlapped (double-buffered) halo path on an NT plan matches;
  * the refine loop closes against *measured* mesh occupancy.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.edge_models import EDGE_MODELS
from repro.core import AnalyticEstimator, Testbed
from repro.core.dpp import plan_search
from repro.core.partition import Mode, Scheme
from repro.core.plan import Plan
from repro.runtime.engine import (EXECUTORS, ExecStats, MeasuredOccupancy,
                                  StageTime, init_weights)
from repro.runtime.mesh_exec import validate_stage_decomposition
from repro.runtime.session import ExecConfig, Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EST = AnalyticEstimator()


def run_partitioned(g, w, x, plan, nodes, **cfg):
    """Session-API positional sugar for this module's config sweeps."""
    return Session(g, w, plan, nodes, ExecConfig(**cfg)).run(x)

MODEL_TEST_KW = {
    "mobilenet": dict(width=32),
    "resnet18": dict(width=32),
    "resnet101": dict(width=32),
    "inception": dict(width=32),
    "bert": dict(seq=16, d=32, n_layers=1, d_ff=64),
}


#: hard wall limit for one mesh subprocess — generous for compile-heavy
#: 8-device runs, small enough that a wedged collective fails the test
#: instead of hanging the whole suite until the CI job limit
SUBPROC_TIMEOUT_S = 1200

_STARVATION_MSG = (
    "mesh subprocess exceeded {limit}s — on the CPU host platform this "
    "is the known thread-pool starvation: all fake devices share one "
    "dispatch pool, so threads parked in one stage module's collective "
    "rendezvous can starve another module's participants (XLA logs "
    "'collective_ops_utils ... may be stuck'). Reduce "
    "XLA_FLAGS=--xla_force_host_platform_device_count, keep the "
    "executor's serialized CPU dispatch enabled (_MeshRun.serialize), "
    "or arm run_partitioned_mesh(stage_timeout_s=...) to fail the "
    "single wedged stage instead of the whole process.")


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    try:
        return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                              capture_output=True, text=True, env=env,
                              timeout=SUBPROC_TIMEOUT_S)
    except subprocess.TimeoutExpired as exc:
        pytest.fail(_STARVATION_MSG.format(limit=SUBPROC_TIMEOUT_S)
                    + f"\npartial stdout: {exc.stdout!r}"
                    + f"\npartial stderr: {exc.stderr!r}")


def _model_io(name, seed=0):
    g = EDGE_MODELS[name](**MODEL_TEST_KW[name])
    w = init_weights(g, jax.random.PRNGKey(seed))
    l0 = g.layers[0]
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (l0.in_h, l0.in_w, l0.in_c))
    return g, w, x


# ---------------------------------------------------------------------------
# in-process: degenerate 1-node path + validation
# ---------------------------------------------------------------------------

def test_executors_constant():
    assert EXECUTORS == ("local", "mesh")


@pytest.mark.parametrize("name", ["mobilenet", "resnet18"])
def test_one_node_plan_bypasses_collectives(name):
    """nodes=1 must work in a 1-device process: no mesh is built and no
    collective is traced — output and stats are bit-identical to the
    local executor."""
    g, w, x = _model_io(name)
    plan = plan_search(g, EST, Testbed(nodes=1, bandwidth_gbps=0.5)).plan
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=1)
    out, s = run_partitioned(g, w, x, plan, nodes=1, executor="mesh")
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s == s_ref


def test_one_node_instrumented_stats():
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    _, s = run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                           instrument=True)
    assert s.stage_times and s.wall_s > 0.0
    kinds = {st.kind for st in s.stage_times}
    assert kinds == {"compute", "sync"}
    occ = s.to_occupancy()
    assert occ.period_s == max(occ.dev_occupancy_s, occ.link_occupancy_s)
    assert occ.latency_s >= 0.0


def test_executor_validation():
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    with pytest.raises(ValueError, match="executor"):
        run_partitioned(g, w, x, plan, nodes=1, executor="bogus")
    with pytest.raises(ValueError, match="backend"):
        run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                        backend="bogus")
    with pytest.raises(ValueError, match="nodes"):
        run_partitioned(g, w, x, plan, nodes=0, executor="mesh")


def test_mesh_needs_devices():
    """Asking for more nodes than devices raises the actionable
    XLA_FLAGS hint (this process has 1 device)."""
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        run_partitioned(g, w, x, plan, nodes=4, executor="mesh")


# ---------------------------------------------------------------------------
# in-process: fault handling (1-node plans need no mesh; the shrink
# precheck *wants* a device-starved process)
# ---------------------------------------------------------------------------

def test_fault_knob_validation():
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    with pytest.raises(ValueError, match="fallback"):
        run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                        fallback="shrug")
    with pytest.raises(ValueError, match="stage_retries"):
        run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                        stage_retries=-1)
    with pytest.raises(ValueError, match="stage_timeout_s"):
        run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                        stage_timeout_s=0.0)


def test_transient_fault_is_retried():
    """Every stage dispatch fails once: with stage_retries=1 the run
    completes, matches the local executor, and counts every re-attempt
    (failure_count > 0 marks the occupancy sample untrusted for
    refine)."""
    from repro.runtime.mesh_exec import run_partitioned_mesh

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=1)
    failed = set()

    def hook(kind, label, attempt):
        if (kind, label) not in failed:
            failed.add((kind, label))
            raise OSError(f"injected transient fault at {label}")

    out, s = run_partitioned_mesh(g, w, x, plan, nodes=1,
                                  stage_retries=1, fault_hook=hook)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s.retries == len(failed) > 0
    assert s.timeouts == 0 and s.fallbacks == 0
    assert s.failure_count == s.retries
    # retries are advisory: stats still equal the clean run's geometry
    assert s == s_ref


def test_persistent_fault_exhausts_retries():
    from repro.runtime.mesh_exec import (StageDispatchError,
                                         run_partitioned_mesh)

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))

    def hook(kind, label, attempt):
        raise OSError("injected persistent fault")

    with pytest.raises(StageDispatchError,
                       match=r"failed after 3 attempt\(s\)"):
        run_partitioned_mesh(g, w, x, plan, nodes=1, stage_retries=2,
                             fault_hook=hook)


def test_persistent_fault_degrades_to_local():
    from repro.runtime.mesh_exec import run_partitioned_mesh

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    ref, _ = run_partitioned(g, w, x, plan, nodes=1)

    def hook(kind, label, attempt):
        raise OSError("injected persistent fault")

    out, s = run_partitioned_mesh(g, w, x, plan, nodes=1, stage_retries=1,
                                  fallback="local", fault_hook=hook)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s.fallbacks == 1 and s.retries >= 1
    assert s.failure_count >= 2


def test_timeout_is_never_retried():
    """An injected StageTimeoutError must go straight to the fallback —
    re-dispatching a wedged collective just stacks another stuck module
    on the thread pool (see _timeout_message)."""
    from repro.runtime.mesh_exec import (StageTimeoutError,
                                         run_partitioned_mesh)

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    ref, _ = run_partitioned(g, w, x, plan, nodes=1)

    def hook(kind, label, attempt):
        raise StageTimeoutError(f"injected timeout at {label}")

    out, s = run_partitioned_mesh(g, w, x, plan, nodes=1,
                                  stage_retries=5, fallback="local",
                                  fault_hook=hook)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s.timeouts == 1
    assert s.retries == 0          # stage_retries never applied
    assert s.fallbacks == 1
    # and without a fallback the timeout propagates
    with pytest.raises(StageTimeoutError, match="injected timeout"):
        run_partitioned_mesh(g, w, x, plan, nodes=1, stage_retries=5,
                             fault_hook=hook)


def test_real_watchdog_fires_with_actionable_message():
    """An unmeetable stage_timeout_s trips the watchdog on the first
    (compiling) stage; the message names the known CPU thread-pool
    starvation and its remedies."""
    from repro.runtime.mesh_exec import StageTimeoutError

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    with pytest.raises(StageTimeoutError, match="starvation"):
        run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                        stage_timeout_s=1e-4)


def test_generous_timeout_counts_nothing():
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=1)
    out, s = run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                             stage_timeout_s=300.0, stage_retries=2)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s == s_ref
    assert s.failure_count == 0


def test_mesh_shrink_degrades_to_local():
    """A 4-node plan in this 1-device process: with fallback='local' the
    precheck degrades to the single-process engine instead of raising
    the XLA_FLAGS hint (cf. test_mesh_needs_devices)."""
    g, w, x = _model_io("mobilenet")
    plan = plan_search(g, EST, Testbed(nodes=4, bandwidth_gbps=0.5)).plan
    ref, _ = run_partitioned(g, w, x, plan, nodes=4)
    out, s = run_partitioned(g, w, x, plan, nodes=4, executor="mesh",
                             fallback="local")
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s.fallbacks == 1 and s.failure_count == 1


def test_failure_counters_break_stats_trust_not_equality():
    """ExecStats equality compares geometry only — failure counters are
    excluded (a retried run still validates against the clean baseline)
    but failure_count drives refine's trusted-sample logic."""
    a, b = ExecStats(), ExecStats()
    a.retries, a.timeouts, a.fallbacks = 2, 1, 1
    assert a == b
    assert a.failure_count == 4 and b.failure_count == 0


def test_to_occupancy_arithmetic():
    s = ExecStats()
    with pytest.raises(ValueError, match="instrument"):
        s.to_occupancy()
    s.stage_times = [
        StageTime("compute", "seg[a..b]", 0.5, (0.2, 0.5)),
        StageTime("compute", "seg[c..c]", 0.3, (0.3, 0.1)),
        StageTime("sync", "bound@b", 0.05),
        StageTime("sync", "gather", 0.1),
    ]
    s.wall_s = 0.95
    occ = s.to_occupancy()
    assert isinstance(occ, MeasuredOccupancy)
    # per-device sums: dev0 = 0.5, dev1 = 0.6 -> straggler 0.6
    assert occ.dev_occupancy_s == pytest.approx(0.6)
    assert occ.link_occupancy_s == pytest.approx(0.15)
    assert occ.period_s == pytest.approx(0.6)
    assert occ.latency_s == pytest.approx(0.95)


def test_to_occupancy_error_names_mesh_executor():
    """The empty-stats message must tell the caller exactly which
    executor/flag combination produces measured stages."""
    with pytest.raises(ValueError, match=r'executor="mesh"'):
        ExecStats().to_occupancy()


# ---------------------------------------------------------------------------
# observability: stage spans, postmortems, disabled-tracing contract
# ---------------------------------------------------------------------------

def test_stage_spans_match_stage_times_one_to_one():
    """With a tracer installed, the control-track ``cat="stage"`` spans
    are the observability mirror of ``ExecStats.stage_times``: same
    count, same labels, same order, same kinds, same wall times."""
    from repro.obs import CONTROL_TRACK, STAGE_CAT, Tracer, set_tracer

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    tr = Tracer()
    set_tracer(tr)
    try:
        _, s = run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                               instrument=True)
    finally:
        set_tracer(None)
    spans = tr.spans(cat=STAGE_CAT, track=CONTROL_TRACK)
    assert len(spans) == len(s.stage_times) > 0
    assert [sp["name"] for sp in spans] == \
        [st.label for st in s.stage_times]
    assert [sp["args"]["kind"] for sp in spans] == \
        [st.kind for st in s.stage_times]
    for sp, st in zip(spans, s.stage_times):
        assert sp["dur"] == pytest.approx(st.wall_s * 1e6)
    # per-device spans mirror the compute stages' completion tuples
    # (empty here: the 1-node path measures no per-shard times)
    n_dev_expected = sum(len(st.device_done_s) for st in s.stage_times)
    assert len(tr.spans(cat="device")) == n_dev_expected


def test_tracing_disabled_is_bit_identical(monkeypatch):
    """The default (no tracer) and traced runs agree bit-exactly on
    outputs and on the ExecStats geometry contract — instrumentation
    must never perturb the numerics.  That holds for the profiler sink
    on the uninstrumented path too, with the same dispatch counters; and
    with no tracer installed nothing is recorded or annotated."""
    from repro.obs import Tracer, get_tracer, set_tracer

    assert get_tracer() is None        # tier-1 default: tracing off
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                                 instrument=True)
    set_tracer(Tracer())
    try:
        out, s = run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                                 instrument=True)
    finally:
        set_tracer(None)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s == s_ref
    assert [st.label for st in s.stage_times] == \
        [st.label for st in s_ref.stage_times]

    # profiler sink, uninstrumented: same answer, stats and counters
    annotated = []

    class Annotation(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            annotated.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    unused = Tracer()
    # build the plan program first, so that both compared runs are warm
    run_partitioned(g, w, x, plan, nodes=1, executor="mesh")
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=1, executor="mesh")
    assert annotated == [] and len(unused) == 0
    set_tracer(Tracer(sink="profiler"))
    try:
        out, s = run_partitioned(g, w, x, plan, nodes=1, executor="mesh")
    finally:
        set_tracer(None)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s == s_ref
    assert (s.launches, s.cache_misses) == (s_ref.launches,
                                            s_ref.cache_misses)
    assert annotated.count("mesh.launch") == s.launches > 0


def test_watchdog_timeout_dumps_postmortem(tmp_path):
    """A tripped stage watchdog leaves a postmortem artifact carrying
    the failing stage's span context (kind/label/timeout) and the
    recent flight-ring events, including that stage's dispatch."""
    from repro.obs import get_flight, set_postmortem_dir
    from repro.runtime.mesh_exec import StageTimeoutError

    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    get_flight().clear()
    set_postmortem_dir(str(tmp_path))
    try:
        with pytest.raises(StageTimeoutError):
            run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                            stage_timeout_s=1e-4)
    finally:
        set_postmortem_dir(None)
    dumps = sorted(tmp_path.glob("postmortem-*-stage_timeout.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["reason"] == "stage_timeout"
    ctx = doc["context"]
    assert ctx["timeout_s"] == pytest.approx(1e-4)
    assert ctx["kind"] in ("compute", "sync") and ctx["label"]
    # the ring shows the failing stage being dispatched, then timing out
    kinds = [(e["kind"], e.get("label")) for e in doc["events"]]
    assert ("stage_dispatch", ctx["label"]) in kinds
    assert ("stage_timeout", ctx["label"]) in kinds


def test_no_postmortem_dir_means_no_artifact(tmp_path, monkeypatch):
    """Without a configured directory the watchdog failure raises
    exactly as before — no artifact side effects anywhere."""
    from repro.obs import postmortem_dir
    from repro.runtime.mesh_exec import StageTimeoutError

    monkeypatch.delenv("REPRO_POSTMORTEM_DIR", raising=False)
    assert postmortem_dir() is None
    g, w, x = _model_io("mobilenet")
    plan = Plan([(Scheme.INH, Mode.T)] * len(g))
    with pytest.raises(StageTimeoutError):
        run_partitioned(g, w, x, plan, nodes=1, executor="mesh",
                        stage_timeout_s=1e-4)
    assert list(tmp_path.glob("postmortem-*")) == []


def test_validate_stage_decomposition_pure():
    from repro.cluster.simsched import Stage

    def sim(kind, label):
        return Stage(kind, (1.0,), (), label)

    stats = ExecStats()
    stats.stage_times = [
        StageTime("compute", "seg[a..b]", 0.1, (0.1,)),
        StageTime("sync", "bound@b", 0.01),
        StageTime("compute", "seg[c..d]", 0.2, (0.2,)),
        StageTime("sync", "reshard", 0.0),
        StageTime("sync", "gather", 0.02),
    ]
    stages = [sim("compute", "seg[a..b]"), sim("sync", "bound@b"),
              sim("compute", "seg[c..d]"), sim("sync", "gather")]
    v = validate_stage_decomposition(stats, stages)
    assert v["structure_match"] and not v["missing"] and not v["extra"]
    assert len(v["stages"]) == 4
    assert all(r["measured_s"] is not None for r in v["stages"])
    # a sim-only stage is missing; a measured-only stage is extra
    v2 = validate_stage_decomposition(
        stats, stages + [sim("sync", "fork->x")])
    assert not v2["structure_match"]
    assert v2["missing"] == [("sync", "fork->x")]
    # post-merge bound@ subsumed by the measured merge-> gather
    stats3 = ExecStats()
    stats3.stage_times = [StageTime("sync", "merge->m", 0.01),
                          StageTime("compute", "seg[m..m]", 0.1, (0.1,))]
    stages3 = [sim("sync", "merge->m"), sim("compute", "seg[m..m]"),
               sim("sync", "bound@m")]
    v3 = validate_stage_decomposition(stats3, stages3)
    assert v3["structure_match"]
    assert v3["subsumed"] == [("sync", "bound@m")]


def test_refine_accepts_measured_occupancy():
    """occupancy_fn replaces the simulator as the occupancy source: the
    fixed-point loop runs on measured numbers and report is None."""
    from repro.cluster import homogeneous, refine_with_simulator

    g = EDGE_MODELS["mobilenet"](**MODEL_TEST_KW["mobilenet"])
    cl = homogeneous(2, bandwidth_gbps=1.0)
    calls = []

    def occupancy_fn(plan):
        calls.append(plan)
        return MeasuredOccupancy(dev_occupancy_s=2e-3,
                                 link_occupancy_s=1e-3,
                                 period_s=2e-3, latency_s=3e-3)

    rr = refine_with_simulator(g, cl, max_iters=3,
                               occupancy_fn=occupancy_fn)
    assert calls and rr.report is None
    assert rr.throughput_rps == pytest.approx(500.0)
    assert all(s.dev_occupancy_s == pytest.approx(2e-3) for s in rr.steps)
    # constant measurements -> constant reweighting -> fixed point
    assert rr.converged


# ---------------------------------------------------------------------------
# subprocess: real 8-device mesh
# ---------------------------------------------------------------------------

_PRELUDE = """
    import numpy as np, jax, jax.numpy as jnp
    assert len(jax.devices()) == 8, jax.devices()
    from repro.configs.edge_models import EDGE_MODELS
    from repro.core import AnalyticEstimator, Testbed
    from repro.core.dpp import plan_search
    from repro.runtime.engine import init_weights
    from repro.runtime.session import ExecConfig, Session
    EST = AnalyticEstimator()
    KW = %r

    def run_partitioned(g, w, x, plan, nodes, **cfg):
        return Session(g, w, plan, nodes, ExecConfig(**cfg)).run(x)

    def model_io(name, seed=0):
        g = EDGE_MODELS[name](**KW[name])
        w = init_weights(g, jax.random.PRNGKey(seed))
        l0 = g.layers[0]
        x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (l0.in_h, l0.in_w, l0.in_c))
        return g, w, x

    def rel_err(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(
            1.0, jnp.max(jnp.abs(b))))
""" % (MODEL_TEST_KW,)


@pytest.mark.slow
@pytest.mark.parametrize("nodes", [2, 4, 8])
def test_mesh_equivalence_all_models(nodes):
    """Mesh vs single-process equivalence, searched plans, xla backend."""
    r = _run(_PRELUDE + f"""
    nodes = {nodes}
    for name in KW:
        g, w, x = model_io(name)
        plan = plan_search(g, EST,
                           Testbed(nodes=nodes, bandwidth_gbps=0.5)).plan
        ref, s_ref = run_partitioned(g, w, x, plan, nodes=nodes)
        out, s = run_partitioned(g, w, x, plan, nodes=nodes,
                                 executor='mesh')
        e = rel_err(out, ref)
        assert e < 1e-4, (name, e)
        assert s == s_ref, (name, s, s_ref)
        print('EQ_OK', name)
    print('ALL_EQ_OK')
    """)
    assert "ALL_EQ_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_mesh_equivalence_pallas():
    """The Pallas shard kernels run unchanged inside the per-device
    programs (the collective assembles the halo-extended slice the
    kernel consumes)."""
    r = _run(_PRELUDE + """
    for name in ('mobilenet', 'resnet18', 'bert'):
        g, w, x = model_io(name)
        plan = plan_search(g, EST,
                           Testbed(nodes=4, bandwidth_gbps=0.5)).plan
        ref, s_ref = run_partitioned(g, w, x, plan, nodes=4,
                                     backend='pallas')
        out, s = run_partitioned(g, w, x, plan, nodes=4,
                                 backend='pallas', executor='mesh')
        e = rel_err(out, ref)
        assert e < 1e-4, (name, e)
        assert s == s_ref, (name,)
        print('PALLAS_OK', name)
    print('ALL_PALLAS_OK')
    """)
    assert "ALL_PALLAS_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_mesh_stage_structure_matches_simulator():
    """instrument=True, overlap=False: the measured stage multiset equals
    simsched.build_stages 1:1 and every multi-node compute stage carries
    per-device completion times."""
    r = _run(_PRELUDE + """
    from repro.cluster import build_stages, homogeneous
    from repro.runtime.mesh_exec import validate_stage_decomposition
    cl = homogeneous(4, bandwidth_gbps=0.5)
    for name in KW:
        g, w, x = model_io(name)
        plan = plan_search(g, EST,
                           Testbed(nodes=4, bandwidth_gbps=0.5)).plan
        out, s = run_partitioned(g, w, x, plan, nodes=4, executor='mesh',
                                 instrument=True, overlap=False)
        v = validate_stage_decomposition(s, build_stages(g, plan, cl))
        assert v['structure_match'], (name, v['missing'], v['extra'])
        n_dev = [len(st.device_done_s) for st in s.stage_times
                 if st.kind == 'compute'
                 and len(st.device_done_s) > 0]
        assert n_dev and all(k == 4 for k in n_dev), (name, n_dev)
        print('STRUCT_OK', name)
    print('ALL_STRUCT_OK')
    """)
    assert "ALL_STRUCT_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_mesh_overlapped_halo_exchange():
    """Same-scheme boundaries take the double-buffered ppermute path:
    on a constant-resolution conv chain (every boundary is
    permute-eligible) overlap=True fuses all exchanges into the
    producing compute stages, overlap=False dispatches each as its own
    sync stage.  On mobilenet at test scale the deep tail shrinks to
    <1 row per node, so ineligible boundaries must *fall back* to the
    gather path and still match."""
    r = _run(_PRELUDE + """
    from repro.core.graph import ConvT, LayerSpec, ModelGraph, chain
    from repro.core.partition import Mode, Scheme
    from repro.core.plan import Plan
    # constant-resolution chain: 6x conv3x3 s1 p1 over 24x24 rows ->
    # 6 rows/node at 4 nodes, 1-2 halo rows per 2-layer segment
    convs = [LayerSpec(f'c{i}', ConvT.CONV, 24, 24, 8, 8, 3, 1, 1)
             for i in range(6)]
    g = chain('flatchain', convs)
    w = init_weights(g, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 24, 8))
    steps = [(Scheme.INH, Mode.T if i % 2 == 1 else Mode.NT)
             for i in range(len(g))]
    plan = Plan(steps)
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=4)
    for overlap in (True, False):
        out, s = run_partitioned(g, w, x, plan, nodes=4, executor='mesh',
                                 instrument=True, overlap=overlap)
        e = rel_err(out, ref)
        assert e < 1e-4, (overlap, e)
        assert s == s_ref
        syncs = [st.label for st in s.stage_times if st.kind == 'sync']
        bounds = [l for l in syncs if l.startswith('bound@')]
        if overlap:
            # every exchange fused into the producing compute stage
            assert not bounds, syncs
        else:
            assert bounds == ['bound@c1', 'bound@c3'], syncs
    # mobilenet, T every 3rd layer: the high-res boundaries fuse, the
    # deep ineligible ones fall back to gather (labelled bound@) —
    # overlap=True must still strictly reduce the sync-stage count
    g, w, x = model_io('mobilenet')
    steps = [(Scheme.INH, Mode.T if (i % 3 == 2) else Mode.NT)
             for i in range(len(g))]
    steps[-1] = (Scheme.INH, Mode.T)
    plan = Plan(steps)
    ref, s_ref = run_partitioned(g, w, x, plan, nodes=4)
    n_bounds = {}
    for overlap in (True, False):
        out, s = run_partitioned(g, w, x, plan, nodes=4, executor='mesh',
                                 instrument=True, overlap=overlap)
        assert rel_err(out, ref) < 1e-4
        assert s == s_ref
        n_bounds[overlap] = sum(
            1 for st in s.stage_times
            if st.kind == 'sync' and st.label.startswith('bound@'))
    assert n_bounds[True] < n_bounds[False], n_bounds
    print('OVERLAP_OK')
    """)
    assert "OVERLAP_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.slow
def test_refine_on_measured_mesh_occupancy():
    """Close the planner loop against the machine: refine re-selects on
    occupancy measured by warm instrumented mesh runs."""
    r = _run(_PRELUDE + """
    from repro.cluster import homogeneous, refine_with_simulator
    g, w, x = model_io('mobilenet')
    cl = homogeneous(2, bandwidth_gbps=1.0)

    def occupancy_fn(plan):
        run = lambda: run_partitioned(g, w, x, plan, nodes=2,
                                      executor='mesh', instrument=True)
        run()                       # warm-up: compile
        _, s = run()
        return s.to_occupancy()

    rr = refine_with_simulator(g, cl, max_iters=2,
                               occupancy_fn=occupancy_fn)
    assert rr.report is None
    assert rr.steps and rr.throughput_rps > 0.0
    assert all(s.dev_occupancy_s > 0.0 for s in rr.steps)
    print('REFINE_MEASURED_OK')
    """)
    assert "REFINE_MEASURED_OK" in r.stdout, r.stdout + r.stderr
