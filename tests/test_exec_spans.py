"""The mesh executor's spans on the ``jax.profiler`` trace.

A ``Tracer(sink="profiler")`` is installed around two runs of a searched
plan inside ``jax.profiler.trace``: the first with an empty program
cache, the second warm.  The executor's spans are read back from the
profiler's ``.xplane.pb``, where they share the clock of the device's
ops.  Each run is one ``mesh.request`` (arg ``path``) holding every
other executor span.

On the staged path (a per-stage policy armed: ``stage_retries=1``):
one ``mesh.launch`` and one ``mesh.lookup`` per launched program, one
``mesh.geometry`` per segment and per merge, one ``mesh.wait``, and a
``mesh.build`` per cache miss, so none on the warm run.

On the plan path (the default policy): one ``mesh.lookup``, one
``mesh.launch`` of kind ``"plan"`` and one ``mesh.wait`` a request.  The
cold run traces the program inside the ``mesh.build`` labelled
``"plan"``, so its ``mesh.geometry`` spans, one per segment and per
merge, lie inside that build; the warm run has no geometry and no build.

One node runs in this process; four run in a child with four virtual
CPU devices (this module run as a script), as the main test process
keeps one device.
"""
import glob
import json
import os
import subprocess
import sys
import tempfile
from collections import Counter

import jax
import pytest

from repro.configs.edge_models import EDGE_MODELS
from repro.core import AnalyticEstimator, Testbed
from repro.core.dpp import plan_search
from repro.obs import Tracer, set_tracer
from repro.runtime.engine import init_weights
from repro.runtime.mesh_exec import clear_mesh_program_cache
from repro.runtime.session import ExecConfig, Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXEC_SPANS = ("mesh.request", "mesh.geometry", "mesh.lookup",
              "mesh.build", "mesh.launch", "mesh.wait")
#: chain (mobilenet) and branched graph with merges (resnet18)
MODELS = ("mobilenet", "resnet18")
#: the policy that selects each path
PATHS = {"staged": ExecConfig(executor="mesh", stage_retries=1),
         "plan": ExecConfig(executor="mesh")}
#: the span args a check reads
ARGS = ("seq", "launches", "cache_misses", "path", "kind", "label")
#: hard wall limit of the four-device child
CHILD_TIMEOUT_S = 900


def exec_events(xplane: str):
    """``(name, start_ns, end_ns, stats)`` of the executor spans on the
    trace's host planes, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in EXEC_SPANS:
                    a = int(e.start_ns)
                    out.append((e.name, a, a + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda r: r[1])


def capture(name: str, nodes: int, path: str = "staged") -> dict:
    """Two profiled runs of ``name``'s searched ``nodes``-node plan on
    ``path``, cold then warm: per run its counters, the executor spans
    that lie in its ``mesh.request`` with their args, and those of them
    that lie in a ``mesh.build`` labelled ``"plan"``."""
    clear_mesh_program_cache()
    g = EDGE_MODELS[name](width=32)
    w = init_weights(g, jax.random.PRNGKey(0))
    l0 = g.layers[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (l0.in_h, l0.in_w, l0.in_c))
    plan = plan_search(g, AnalyticEstimator(),
                       Testbed(nodes=nodes, bandwidth_gbps=0.5)).plan
    sess = Session(g, w, plan, nodes, PATHS[path])
    with tempfile.TemporaryDirectory() as d:
        set_tracer(Tracer(sink="profiler"))
        try:
            with jax.profiler.trace(d):
                stats = [sess.run(x)[1] for _ in range(2)]
        finally:
            set_tracer(None)
        (xplane,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                              recursive=True)
        events = exec_events(xplane)
    reqs = [e for e in events if e[0] == "mesh.request"]
    children = [e for e in events if e[0] != "mesh.request"]
    builds = [e for e in children
              if e[0] == "mesh.build" and e[3].get("label") == "plan"]
    runs = []
    for (_, a, b, args), st in zip(reqs, stats):
        inside = [e for e in children if a <= e[1] and e[2] <= b]
        in_build = Counter(n for n, s, t, _ in inside
                           if any(c <= s and t <= d
                                  for _, c, d, _ in builds))
        runs.append({"launches": st.launches,
                     "cache_misses": st.cache_misses,
                     "compute_stages": st.compute_stages,
                     "args": {k: args.get(k) for k in ARGS},
                     "spans": dict(Counter(n for n, *_ in inside)),
                     "in_plan_build": dict(in_build),
                     "launch_args": [{k: e[3][k] for k in ("kind", "label")}
                                     for e in inside
                                     if e[0] == "mesh.launch"]})
    return {"requests": len(reqs), "children": len(children),
            "runs": runs}


def check(got: dict) -> None:
    """The staged path's spans."""
    assert got["requests"] == 2
    cold, warm = got["runs"]
    # every child span lies inside its request
    assert got["children"] == sum(sum(r["spans"].values())
                                  for r in got["runs"])
    for run in (cold, warm):
        sp = run["spans"]
        assert run["args"]["path"] == "staged"
        assert run["launches"] > 0
        assert sp["mesh.launch"] == sp["mesh.lookup"] == run["launches"]
        # compute_stages counts the segments and the merges
        assert sp["mesh.geometry"] == run["compute_stages"]
        assert sp["mesh.wait"] == 1
        assert run["args"]["launches"] == run["launches"]
        assert run["args"]["cache_misses"] == run["cache_misses"]
    assert cold["cache_misses"] > 0
    assert cold["spans"]["mesh.build"] == cold["cache_misses"]
    assert warm["cache_misses"] == 0 and "mesh.build" not in warm["spans"]
    assert warm["args"]["seq"] == cold["args"]["seq"] + 1


def check_plan(got: dict) -> None:
    """The plan path's spans."""
    assert got["requests"] == 2
    cold, warm = got["runs"]
    assert got["children"] == sum(sum(r["spans"].values())
                                  for r in got["runs"])
    for run in (cold, warm):
        sp = run["spans"]
        assert run["args"]["path"] == "plan"
        assert run["launches"] == run["args"]["launches"] == 1
        assert run["args"]["cache_misses"] == run["cache_misses"]
        assert sp["mesh.launch"] == sp["mesh.wait"] == 1
        assert run["launch_args"] == [{"kind": "plan", "label": "plan"}]
    # cold: the plan program is built, and its trace holds the geometry
    # and the stage programs' lookups and builds
    assert cold["cache_misses"] > 0
    assert cold["spans"]["mesh.build"] == cold["cache_misses"]
    assert cold["spans"]["mesh.geometry"] == cold["compute_stages"]
    assert cold["in_plan_build"]["mesh.geometry"] == cold["compute_stages"]
    assert cold["spans"]["mesh.lookup"] == \
        cold["in_plan_build"].get("mesh.lookup", 0) + 1
    # warm: one lookup, one launch, one wait, and nothing else
    assert warm["cache_misses"] == 0
    assert warm["spans"] == {"mesh.lookup": 1, "mesh.launch": 1,
                             "mesh.wait": 1}
    assert warm["args"]["seq"] == cold["args"]["seq"] + 1


CHECKS = {"staged": check, "plan": check_plan}


@pytest.mark.parametrize("name,path", [
    pytest.param(name, path, id=name if path == "staged"
                 else f"{path}-{name}")
    for path in PATHS for name in MODELS])
def test_executor_spans_one_node(name, path):
    CHECKS[path](capture(name, 1, path))


@pytest.fixture(scope="module")
def four_nodes() -> dict:
    """Both paths' captures on four virtual devices, from one child."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env,
                       timeout=CHILD_TIMEOUT_S)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert sorted(got) == sorted(PATHS)
    return got


def test_executor_spans_four_nodes(four_nodes):
    got = four_nodes["staged"]
    assert sorted(got) == sorted(MODELS)
    for name in MODELS:
        check(got[name])


def test_plan_spans_four_nodes(four_nodes):
    got = four_nodes["plan"]
    assert sorted(got) == sorted(MODELS)
    for name in MODELS:
        check_plan(got[name])


if __name__ == "__main__":
    print(json.dumps({path: {name: capture(name, 4, path)
                             for name in MODELS} for path in PATHS}))
