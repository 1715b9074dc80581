"""Mesh-executor benchmark: sequential vs mesh wall time, measured vs
simulated stage times -> ``BENCH_mesh.json``.

For each edge model (test scale, searched 4-node plan) this runs the
single-process engine and the mesh executor (4 fake host devices) and
records:

* ``agree`` / ``rel_err`` — mesh output vs the single-process path
  (PR 5 scale-normalized tolerance);
* ``stats_equal`` — ``ExecStats`` geometry accounting identical;
* ``structure_match`` — the measured stage multiset
  (``instrument=True, overlap=False``) equals
  ``simsched.build_stages`` 1:1 (post-merge boundaries subsumed by the
  merge gather — see ``runtime.mesh_exec.validate_stage_decomposition``);
* ``local_us`` / ``mesh_wall_us`` / ``dev_occupancy_us`` /
  ``link_occupancy_us`` — warm wall times and measured occupancy;
* ``stages`` — per-stage ``{kind, label, sim_s, measured_s}`` pairs;
* ``skew`` — per-stage measured/simulated ratios plus a
  ``median_ratio`` / ``max_abs_log2`` summary (``obs.skew.stage_skew``);
  advisory, surfaced by ``check_regression --kind mesh`` as a note.

With ``--trace-dir PATH`` (or ``run(trace_dir=...)``) each model's warm
staged run is captured by ``repro.obs.Tracer`` and written together with
the simulator's timeline (same Perfetto schema, pid 2) to
``PATH/mesh_<model>.trace.json``, plus a ``mesh_metrics.json`` counter
snapshot — open the trace files at https://ui.perfetto.dev.

``check_regression.py --kind mesh`` gates the flags **hard**; every
timing field is **advisory**: the "devices" are XLA host-platform fakes
sharing one CPU's cores, so per-stage durations carry scheduling noise
far above any regression signal (the per-device completion times are an
upper envelope — shards are blocked on in mesh order) and sim-vs-measured
ratios reflect the analytic Testbed's modeled edge silicon, not this
host.  The flags are the contract; the times are the trajectory record
(see ``noise_note`` in the JSON).

The bench needs >= 4 devices.  It runs in-process on the devices it is
given; only on the CPU platform, before this process has initialised JAX,
does it respawn itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` when the flags ask
for fewer (``common.respawn_for_host_devices``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import (emit, json_arg, respawn_for_host_devices,
                     trace_dir_arg)

NODES = 4

#: test-scale constructor kwargs (interpret-mode full scale is minutes)
MODEL_KW = {
    "mobilenet": dict(width=32),
    "resnet18": dict(width=32),
    "resnet101": dict(width=32),
    "inception": dict(width=32),
    "bert": dict(seq=16, d=32, n_layers=1, d_ff=64),
}

SMOKE_MODELS = ("mobilenet", "resnet18")

NOISE_NOTE = (
    "All *_us / *_s fields are advisory on CPU CI: the mesh 'devices' are "
    "XLA host-platform fakes time-sharing one CPU, so stage durations "
    "include scheduler noise well above 2x and sim_s comes from the "
    "analytic edge-silicon Testbed, not this host. Only the boolean "
    "flags (agree/stats_equal/structure_match) are gated.")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_model(name: str, trace_dir: str | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.cluster import build_stages, homogeneous, simulate_trace
    from repro.configs.edge_models import EDGE_MODELS
    from repro.core import Testbed
    from repro.core.dpp import plan_search
    from repro.obs import Tracer, set_tracer, write_trace
    from repro.obs.skew import stage_skew
    from repro.runtime.engine import init_weights
    from repro.runtime.session import ExecConfig, Session
    from repro.runtime.mesh_exec import validate_stage_decomposition

    from .common import EST, time_call

    g = EDGE_MODELS[name](**MODEL_KW[name])
    w = init_weights(g, jax.random.PRNGKey(0))
    l0 = g.layers[0]
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (l0.in_h, l0.in_w, l0.in_c))
    plan = plan_search(g, EST,
                       Testbed(nodes=NODES, bandwidth_gbps=0.5)).plan

    local_sess = Session(g, w, plan, NODES)
    local_us, (ref, s_ref) = time_call(
        lambda: local_sess.run(x), repeats=2)

    mesh_sess = Session(g, w, plan, NODES,
                        ExecConfig(executor="mesh", instrument=True))
    def mesh_run():
        return mesh_sess.run(x)
    mesh_run()                                   # warm-up: compile
    mesh_us, (out, s_mesh) = time_call(mesh_run, repeats=2)
    occ = s_mesh.to_occupancy()

    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    rel_err = float(jnp.max(jnp.abs(out - ref))) / scale

    # staged (overlap=False) run against the simulator's stage DAG;
    # two runs so the measured one is warm (only the warm run is traced)
    staged_sess = Session(g, w, plan, NODES,
                          ExecConfig(executor="mesh", instrument=True,
                                     overlap=False))
    _, s_staged = staged_sess.run(x)
    tr = Tracer() if trace_dir else None
    set_tracer(tr)
    try:
        _, s_staged = staged_sess.run(x)
    finally:
        set_tracer(None)
    cl = homogeneous(NODES, bandwidth_gbps=0.5)
    v = validate_stage_decomposition(s_staged, build_stages(g, plan, cl))

    if trace_dir:
        _, sim_tr = simulate_trace(g, plan, cl)
        write_trace(os.path.join(trace_dir, f"mesh_{name}.trace.json"),
                    tr, sim_tr)

    return {
        "skew": stage_skew(v["stages"]),
        "rel_err": rel_err,
        "agree": rel_err < 1e-4,
        "stats_equal": s_ref == s_mesh,
        "structure_match": v["structure_match"],
        "missing": [list(m) for m in v["missing"]],
        "extra": [list(m) for m in v["extra"]],
        "subsumed": [list(m) for m in v["subsumed"]],
        "local_us": local_us,
        "mesh_wall_us": mesh_us,
        "dev_occupancy_us": occ.dev_occupancy_s * 1e6,
        "link_occupancy_us": occ.link_occupancy_s * 1e6,
        "stages": v["stages"],
    }


def _run_inner(json_path: str | None, smoke: bool,
               trace_dir: str | None = None) -> dict:
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if len(jax.devices()) < NODES:
        raise RuntimeError(
            f"mesh_bench needs {NODES} devices, found {len(jax.devices())} "
            f"(on the CPU set XLA_FLAGS=--xla_force_host_platform_device_"
            f"count={NODES} before JAX starts)")
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        from repro.obs import Metrics, set_metrics
        set_metrics(Metrics())
    models = SMOKE_MODELS if smoke else tuple(MODEL_KW)
    record = {"nodes": NODES, "devices": len(jax.devices()),
              "noise_note": NOISE_NOTE, "models": {}}
    for name in models:
        rec = _bench_model(name, trace_dir=trace_dir)
        record["models"][name] = rec
        flags = "ok" if (rec["agree"] and rec["stats_equal"]
                         and rec["structure_match"]) else "FLAG"
        emit(f"mesh_{name}", rec["mesh_wall_us"],
             f"local={rec['local_us']:.0f}us rel_err={rec['rel_err']:.1e} "
             f"{flags}")
    if trace_dir:
        from repro.obs import get_metrics, set_metrics
        get_metrics().export(os.path.join(trace_dir, "mesh_metrics.json"))
        set_metrics(None)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    return record


def run(json_path: str | None = None, smoke: bool = False,
        trace_dir: str | None = None) -> dict:
    """Entry point used by ``benchmarks.run``: runs in-process, or
    respawns with forced host devices where
    :func:`~benchmarks.common.respawn_for_host_devices` allows."""
    if not respawn_for_host_devices(NODES):
        return _run_inner(json_path, smoke, trace_dir=trace_dir)
    out_path = os.path.abspath(json_path) if json_path else \
        os.path.join(_ROOT, "BENCH_mesh.json")
    cmd = [sys.executable, "-m", "benchmarks.mesh_bench",
           "--json", out_path]
    if smoke:
        cmd.append("--smoke")
    if trace_dir:
        cmd += ["--trace-dir", os.path.abspath(trace_dir)]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"),
                    env.get("PYTHONPATH", "")) if p)
    try:
        r = subprocess.run(cmd, env=env, cwd=_ROOT, capture_output=True,
                           text=True, timeout=3600)
    except subprocess.TimeoutExpired as exc:
        raise RuntimeError(
            "mesh_bench subprocess exceeded 3600s — on the CPU host "
            "platform this is the known thread-pool starvation: all fake "
            "devices share one dispatch pool, so threads parked in one "
            "stage module's collective rendezvous can starve another "
            "module's participants (XLA logs 'collective_ops_utils ... "
            "may be stuck'). Reduce "
            "XLA_FLAGS=--xla_force_host_platform_device_count, run with "
            "--smoke, or arm run_partitioned_mesh(stage_timeout_s=...) "
            "to fail the single wedged stage instead of the whole "
            f"sweep.\npartial stdout: {exc.stdout!r}") from exc
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        raise RuntimeError("mesh_bench subprocess failed")
    with open(out_path) as f:
        return json.load(f)


if __name__ == "__main__":
    argv = sys.argv[1:]
    run(json_path=json_arg(argv, default="BENCH_mesh.json"),
        smoke="--smoke" in argv, trace_dir=trace_dir_arg(argv))
