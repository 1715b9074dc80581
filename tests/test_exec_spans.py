"""The mesh executor's spans on the ``jax.profiler`` trace.

A ``Tracer(sink="profiler")`` is installed around two runs of a searched
plan inside ``jax.profiler.trace``: the first with an empty program
cache, the second warm.  The executor's spans are read back from the
profiler's ``.xplane.pb``, where they share the clock of the device's
ops.  Each run is one ``mesh.request`` holding every other executor
span: one ``mesh.launch`` and one ``mesh.lookup`` per launched program,
one ``mesh.geometry`` per segment and per merge, one ``mesh.wait``, and
a ``mesh.build`` per cache miss, so none on the warm run.

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


def capture(name: str, nodes: int) -> dict:
    """Two profiled runs of ``name``'s searched ``nodes``-node plan, cold
    then warm: per run its counters and the executor spans that lie in
    its ``mesh.request``."""
    clear_mesh_program_cache()
    g = EDGE_MODELS[name](width=32)
    w = init_weights(g, jax.random.PRNGKey(0))
    l0 = g.layers[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (l0.in_h, l0.in_w, l0.in_c))
    plan = plan_search(g, AnalyticEstimator(),
                       Testbed(nodes=nodes, bandwidth_gbps=0.5)).plan
    sess = Session(g, w, plan, nodes, ExecConfig(executor="mesh"))
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
    runs = []
    for (_, a, b, args), st in zip(reqs, stats):
        inside = Counter(n for n, s, t, _ in children if a <= s and t <= b)
        runs.append({"launches": st.launches,
                     "cache_misses": st.cache_misses,
                     "compute_stages": st.compute_stages,
                     "args": {k: args.get(k) for k in
                              ("seq", "launches", "cache_misses")},
                     "spans": dict(inside)})
    return {"requests": len(reqs), "children": len(children),
            "runs": runs}


def check(got: dict) -> None:
    assert got["requests"] == 2
    cold, warm = got["runs"]
    # every child span lies inside its request
    assert got["children"] == sum(sum(r["spans"].values())
                                  for r in got["runs"])
    for run in (cold, warm):
        sp = run["spans"]
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


@pytest.mark.parametrize("name", MODELS)
def test_executor_spans_one_node(name):
    check(capture(name, 1))


def test_executor_spans_four_nodes():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env,
                       timeout=CHILD_TIMEOUT_S)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert sorted(got) == sorted(MODELS)
    for name in MODELS:
        check(got[name])


if __name__ == "__main__":
    print(json.dumps({name: capture(name, 4) for name in MODELS}))
