"""The mesh executor's plan path: one jitted program per request.

A policy with no per-stage semantics (no ``instrument``, no
``stage_timeout_s``, no ``stage_retries``, no ``fault_hook``) runs a
request as one program traced from the staged body.  It must answer as
the staged path does (exactly on the xla backend, within 1e-4 on pallas)
with the same ``ExecStats`` geometry, launch once when warm, and build
one program per (graph, plan, input shape).  Any per-stage policy keeps
the staged path.  A plan launch that raises is a ``StageDispatchError``
labelled ``"plan"``, so ``fallback="local"`` still degrades.

A chain (mobilenet) and a branched graph with merges (resnet18) run on
one node in this process and on four virtual devices in a child.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.edge_models import EDGE_MODELS
from repro.core import AnalyticEstimator, Testbed
from repro.core.dpp import plan_search
from repro.runtime import mesh_exec
from repro.runtime.engine import init_weights
from repro.runtime.session import ExecConfig, Session

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = ("mobilenet", "resnet18")
#: largest scale-normalized difference, plan against staged, by backend
TOL = {"xla": 0.0, "pallas": 1e-4}
#: policies with per-stage semantics, each of which keeps the staged path
STAGED_POLICIES = {
    "instrument": dict(instrument=True),
    "stage_timeout_s": dict(stage_timeout_s=300.0),
    "stage_retries": dict(stage_retries=1),
}
#: hard wall limit of the four-device child
CHILD_TIMEOUT_S = 900


def model_io(name, width=32, nodes=1):
    g = EDGE_MODELS[name](width=width)
    w = init_weights(g, jax.random.PRNGKey(0))
    l0 = g.layers[0]
    x = jax.random.normal(jax.random.PRNGKey(1), (l0.in_h, l0.in_w, l0.in_c))
    plan = plan_search(g, AnalyticEstimator(),
                       Testbed(nodes=nodes, bandwidth_gbps=0.5)).plan
    return g, w, x, plan


def rel_err(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b))
                 / jnp.maximum(1.0, jnp.max(jnp.abs(b))))


def plan_programs() -> int:
    return sum(isinstance(v, mesh_exec._PlanProgram)
               for v in mesh_exec._PROG_CACHE.values())


def compare(name, backend, nodes):
    """Plan path against staged path, cold then warm: the largest
    difference, whether the stats agree, and the warm dispatch counters."""
    g, w, x, plan = model_io(name, nodes=nodes)
    staged, s_staged = Session(
        g, w, plan, nodes, ExecConfig(executor="mesh", backend=backend,
                                      instrument=True)).run(x)
    sess = Session(g, w, plan, nodes,
                   ExecConfig(executor="mesh", backend=backend))
    cold, s_cold = sess.run(x)
    warm, s_warm = sess.run(x)
    return {"err": max(rel_err(cold, staged), rel_err(warm, staged)),
            "stats_equal": s_cold == s_staged and s_warm == s_staged,
            "cold": [s_cold.launches, s_cold.cache_misses],
            "warm": [s_warm.launches, s_warm.cache_misses],
            "staged_launches": s_staged.launches}


def check(got, backend):
    assert got["err"] <= TOL[backend], got
    assert got["stats_equal"], got
    assert got["cold"][0] == 1 and got["cold"][1] >= 1, got
    assert got["warm"] == [1, 0], got
    assert got["staged_launches"] > 1, got


@pytest.mark.parametrize("backend", sorted(TOL))
@pytest.mark.parametrize("name", MODELS)
def test_plan_path_matches_staged_one_node(name, backend):
    check(compare(name, backend, 1), backend)


def test_plan_path_matches_staged_four_nodes():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                       capture_output=True, text=True, env=env,
                       timeout=CHILD_TIMEOUT_S)
    assert r.returncode == 0, r.stdout + r.stderr
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert sorted(got) == sorted(f"{n}/{b}" for n in MODELS for b in TOL)
    for key, res in got.items():
        check(res, key.split("/")[1])


def test_new_input_shape_builds_a_second_program():
    """One program per (graph, plan, input shape): a second shape adds
    one, and the first stays warm."""
    mesh_exec.clear_mesh_program_cache()
    g, w, x, plan = model_io("mobilenet")
    sess = Session(g, w, plan, 1, ExecConfig(executor="mesh"))
    sess.run(x)
    assert plan_programs() == 1
    g2, w2, x2, plan2 = model_io("mobilenet", width=48)
    _, s2 = Session(g2, w2, plan2, 1, ExecConfig(executor="mesh")).run(x2)
    assert s2.launches == 1 and s2.cache_misses >= 1
    assert plan_programs() == 2
    _, s = sess.run(x)
    assert (s.launches, s.cache_misses) == (1, 0)
    assert plan_programs() == 2


@pytest.mark.parametrize("policy", sorted(STAGED_POLICIES) + ["fault_hook"])
def test_per_stage_policy_takes_the_staged_path(policy):
    g, w, x, plan = model_io("mobilenet")
    ref, s_ref = Session(g, w, plan, 1, ExecConfig(executor="mesh")).run(x)
    mesh_exec.clear_mesh_program_cache()
    hooked = []
    kw = {"fault_hook": lambda *a: hooked.append(a)} \
        if policy == "fault_hook" else {}
    cfg = ExecConfig(executor="mesh", **STAGED_POLICIES.get(policy, {}))
    out, s = Session(g, w, plan, 1, cfg, **kw).run(x)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0
    assert s == s_ref
    assert s.launches > 1 and plan_programs() == 0
    if policy == "fault_hook":
        assert len(hooked) == s.launches


def _break_plan_program():
    """Make every cached plan program's launch raise."""
    def fail(*args):
        raise OSError("injected plan launch fault")
    for k, v in list(mesh_exec._PROG_CACHE.items()):
        if isinstance(v, mesh_exec._PlanProgram):
            mesh_exec._PROG_CACHE[k] = dataclasses.replace(v, fn=fail)


def test_failing_plan_launch_degrades_or_raises():
    g, w, x, plan = model_io("mobilenet")
    ref, _ = Session(g, w, plan, 1).run(x)
    Session(g, w, plan, 1, ExecConfig(executor="mesh")).run(x)
    _break_plan_program()
    try:
        out, s = Session(g, w, plan, 1, ExecConfig(
            executor="mesh", fallback="local")).run(x)
        assert float(jnp.max(jnp.abs(out - ref))) == 0.0
        assert s.fallbacks == 1 and s.failure_count == 1
        with pytest.raises(mesh_exec.StageDispatchError,
                           match=r"'plan' failed after 1 attempt"):
            Session(g, w, plan, 1, ExecConfig(executor="mesh")).run(x)
    finally:
        mesh_exec.clear_mesh_program_cache()


if __name__ == "__main__":
    print(json.dumps({f"{n}/{b}": compare(n, b, 4)
                      for n in MODELS for b in TOL}))
