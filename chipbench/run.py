"""Chip benchmark of FlexPie's served path, one cell per run.

Run from the root of a checkout, on a machine with the cell's chips:

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell needs is found by name.  ``BENCHMARK.json`` names the
cell's configuration, traffic mix and chips.  The configuration file
holds the sizes and the plan's node count, one node per chip, and names
the program's model and the plain reference in
``chipbench/models/<reference>.py``.  The mix is
``chipbench/traffic/<mix>.json``, read by ``chipbench/traffic.py``.  The
limits of the correctness check are ``chipbench/cells/<cell>.json``.
Each per-layer metric is read by ``chipbench/metrics/<metric>.py``.

A run plans the model for one node per chip with the program's own
planner, makes the weights on the device from the seed, builds a
``Session`` on the mesh executor with the Pallas kernels, and warms it
up; that is set-up.  It then serves the mix for ``--seconds`` seconds.
With ``--trace 1`` the first seconds of that window are profiled and the
last line carries the per-layer metrics; with ``--trace 0`` it carries
the end-to-end ones.  After the window every answer is compared with the
plain reference, and the numbers compared are printed beside their
limits, last on standard error and last in the result line.

The run fails, with no result, where JAX finds no TPU or fewer than the
cell's chips.
"""
from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import traffic, work  # noqa: E402
from chipbench.trace import Trace, reduce_xspace  # noqa: E402

BENCH = ROOT / "chipbench"
#: seconds of a ``--trace 1`` window that the profiler records
TRACE_SECONDS = 4.0
#: untimed requests after the first, which compiles or loads every program
WARMUP_REQUESTS = 2
#: images per call of the jitted reference
REF_BLOCK = 8
#: the program's layer types, as the reference tables name them
OPS = {"CONV": "conv", "POINTWISE": "conv", "DWCONV": "dwconv",
       "POOL": "pool", "ADD": "add", "FC": "fc"}
SHAPE_KEYS = ("in_h", "in_w", "in_c", "out_c", "k", "s", "p")
#: jax.monitoring events counted inside the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class BenchError(RuntimeError):
    """The benchmark's own files or the machine do not fit the cell."""


def _json(path: Path) -> Dict:
    return json.loads(path.read_text())


def load_cell(name: str) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with everything it names."""
    spec = _json(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = _json(ROOT / cfg_entry["file"])
    if config["nodes"] != cell["chips"]:
        raise BenchError(f"{name}: configuration {cell['config']!r} plans "
                         f"{config['nodes']} nodes, the cell has "
                         f"{cell['chips']} chips")
    return SimpleNamespace(
        spec=spec, cell=cell, config=config,
        mix=traffic.load(cell["traffic"]),
        limits=_json(BENCH / "cells" / f"{name}.json"))


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def per_layer_metrics(spec: Dict, cell: str) -> List[Dict]:
    """The per-layer metrics a cell reports: those listed for it, or
    listed for none and moving an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in spec["end_to_end"] if _applies(m, cell)}
    return [m for m in spec["per_layer"]
            if _applies(m, cell) and m["moves"] in e2e]


def seed_key(seed: int):
    """A PRNG key for any whole-number seed, 64 bits and more."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_weights(table: List[Dict], seed: int, dtype: str) -> List:
    """Seeded normal weights, scaled by 1/sqrt(fan-in), in one jitted call
    on the device; ``None`` for layers without weights."""
    import jax
    import jax.numpy as jnp
    shapes = [work.weight_shape(l) for l in table]

    def init(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.normal(k, s, jnp.dtype(dtype))
                / math.sqrt(math.prod(s[:-1]))
                for k, s in zip(keys, shapes) if s is not None]

    flat = iter(jax.jit(init)(seed_key(seed)))
    return [next(flat) if s is not None else None for s in shapes]


def check_graph(graph, table: List[Dict]) -> None:
    """The program's layer graph has the reference's layers, in order."""
    if len(graph.layers) != len(table):
        raise BenchError(f"{graph.name}: {len(graph.layers)} layers, the "
                         f"reference has {len(table)}")
    for l, r in zip(graph.layers, table):
        got = (OPS.get(l.conv_t.name),) + tuple(getattr(l, k)
                                                 for k in SHAPE_KEYS)
        want = (r["op"],) + tuple(r[k] for k in SHAPE_KEYS)
        if got != want:
            raise BenchError(f"{graph.name} layer {l.name}: program "
                             f"{got}, reference {r['name']} {want}")


def reference_logits(ref, cfg: Dict, weights: List, pool, images,
                     precision: str) -> Dict[int, object]:
    """The plain reference's logits for the pool images ``images``, in
    jitted blocks of ``REF_BLOCK`` on the default device."""
    import jax
    import numpy as np
    ws = [w for w in weights if w is not None]
    fwd = jax.jit(lambda w, x: ref.forward(cfg, w, x, precision))
    idx = sorted(images)
    out: Dict[int, object] = {}
    for i in range(0, len(idx), REF_BLOCK):
        blk = idx[i:i + REF_BLOCK]
        x = np.zeros((REF_BLOCK,) + pool.shape[1:], pool.dtype)
        x[:len(blk)] = pool[blk]
        y = np.asarray(fwd(ws, x))
        out.update((j, y[n]) for n, j in enumerate(blk))
    return out


def rel_err(answer, ref) -> float:
    """``max|answer - ref| / max|ref|`` over one image's logits."""
    import numpy as np
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(answer.reshape(ref.shape) - ref))) / scale


def percentile_ms(values_s: List[float], q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values_s) * 1e3, q))


class _EventCount:
    """Counts compile and trace events from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.counts = {COMPILE_EVENT: 0, TRACE_EVENT: 0}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if event in self.counts:
            self.counts[event] += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._event)


def _emit(obj: Dict) -> None:
    print(json.dumps(obj), flush=True)


def _lowering(graph, weights, plan, nodes: int) -> Dict:
    from collections import Counter

    from repro.runtime.engine import pallas_lowering
    rows = pallas_lowering(graph, weights, plan, nodes)
    xla = Counter(f"{name}: {why}" for name, _, why in rows
                  if why is not None)
    return {"line": "lowering", "pallas_records": len(rows) - sum(
        xla.values()), "xla_records": sum(xla.values()),
        "xla_reasons": [f"{k} (x{n})" for k, n in sorted(xla.items())]}


def _schemes(plan) -> str:
    runs: List[List] = []
    for s, m in plan.steps:
        step = f"{s.name}/{m.name}"
        if runs and runs[-1][0] == step:
            runs[-1][1] += 1
        else:
            runs.append([step, 1])
    return " ".join(f"{step}x{n}" for step, n in runs)


def build(c: SimpleNamespace, seed: int, devices, plan=None
          ) -> SimpleNamespace:
    """Everything a run serves with: the program's graph and plan (the
    planner's own choice, one node per chip, unless ``plan`` is given),
    the seeded weights and images, and the ``Session`` with its
    ``serve(image)``.  Call inside ``jax.default_matmul_precision`` of
    the configuration, as every later call of ``serve``."""
    import jax
    import numpy as np

    from repro.configs.edge_models import EDGE_MODELS
    from repro.core import AnalyticEstimator, Testbed
    from repro.core.dpp import plan_search
    from repro.runtime.session import ExecConfig, Session

    cfg, mix = c.config, c.mix
    chips = c.cell["chips"]
    ref = importlib.import_module(f"chipbench.models.{cfg['reference']}")
    table = ref.layers(cfg)
    graph = EDGE_MODELS[cfg["model"]](cfg["image_size"])
    check_graph(graph, table)
    if plan is None:
        t = time.perf_counter()
        plan = plan_search(graph, AnalyticEstimator(),
                           Testbed(nodes=chips)).plan
        _emit({"line": "plan", "nodes": chips, "steps": len(plan.steps),
               "schemes": _schemes(plan),
               "plan_search_s": time.perf_counter() - t})
    weights = make_weights(table, seed, cfg["dtype"])
    shape = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    sess = Session(graph, weights, plan, chips,
                   ExecConfig(backend="pallas", executor="mesh",
                              fallback="raise"))
    b = SimpleNamespace(ref=ref, table=table, graph=graph, plan=plan,
                        weights=weights, sess=sess, stats=[],
                        pool=traffic.image_pool(mix, shape, seed),
                        seq=traffic.order(mix, seed))

    def serve(image):
        with jax.profiler.TraceAnnotation("request"):
            with jax.profiler.TraceAnnotation("put_input"):
                # uncommitted, so the mesh programs may place it
                x = jax.device_put(image)
            with jax.profiler.TraceAnnotation("session_run"):
                out, stats = b.sess.run(x)
            with jax.profiler.TraceAnnotation("fetch_output"):
                y = np.asarray(out).reshape(-1)
        if not b.stats:
            b.stats.append(stats)
        return None if stats.fallbacks > 0 else y

    b.serve = serve
    return b


def warm_up(b: SimpleNamespace) -> None:
    """The first request compiles, or loads from the compile cache, every
    program of the plan; the rest show that nothing is left."""
    for i in range(1 + WARMUP_REQUESTS):
        if b.serve(b.pool[b.seq[i % len(b.seq)]]) is None:
            raise BenchError("warm-up request fell back off the mesh")


class Control:
    """The correctness control in the program's place: the plain
    reference one precision below the configuration's (``numerics``
    ``"high"``), one image per call, on the default device.  A run that
    serves it must come out not correct."""

    def __init__(self, b: SimpleNamespace, cfg: Dict):
        import jax
        self._ws = [w for w in b.weights if w is not None]
        self._fwd = jax.jit(
            lambda w, x: b.ref.forward(cfg, w, x[None], "high"))
        self._stats = SimpleNamespace(
            fallbacks=0, sync_points=None, bytes_received=None,
            redundant_elems=None, compute_stages=None)

    def run(self, x):
        return self._fwd(self._ws, x), self._stats


def answer_errors(c: SimpleNamespace, b: SimpleNamespace, reqs,
                  precision: Optional[str] = None) -> List[float]:
    """``rel_err`` of every answered request against the plain reference
    at ``precision`` (the configuration's, unless given)."""
    import jax
    precision = precision or c.config["precision"]
    images = {r.image for r in reqs if r.error is None}
    with jax.default_matmul_precision(c.config["precision"]):
        refs = reference_logits(b.ref, c.config, b.weights, b.pool,
                                images, precision)
    return [rel_err(r.answer, refs[r.image]) for r in reqs
            if r.error is None]


def run_cell(c: SimpleNamespace, seed: int, seconds: float, trace: bool,
             devices, t0: float, peaks: Optional[Dict] = None,
             control: bool = False) -> Dict:
    """Set up, serve the window, check every answer, and return the
    result line.  ``devices`` are the cell's devices; ``t0`` is the host
    clock at the start of set-up.  With ``control`` the window is served
    by :class:`Control` in the ``Session``'s place."""
    import jax

    cfg, name, chips = c.config, c.cell["name"], c.cell["chips"]
    kind = devices[0].device_kind
    if peaks is None:
        peaks = _json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"chipbench/peaks.json")
    peak = peaks[kind]
    itemsize = work.DTYPE_BYTES[cfg["dtype"]]

    with jax.default_matmul_precision(cfg["precision"]):
        b = build(c, seed, devices)
        if control:
            b.sess = Control(b, cfg)
        warm_up(b)
        setup_s = time.perf_counter() - t0

        events = _EventCount()
        tdir = None
        start = time.perf_counter()
        end = start + seconds
        if trace:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(tdir)
            reqs = traffic.closed_loop(b.serve, b.pool, b.seq,
                                       min(end, start + TRACE_SECONDS))
            jax.profiler.stop_trace()
            reqs += traffic.closed_loop(b.serve, b.pool, b.seq, end,
                                        first=len(reqs))
        else:
            reqs = traffic.closed_loop(b.serve, b.pool, b.seq, end)
        events.close()
    b.sess = None

    ok = [r for r in reqs if r.error is None]
    failed = len(reqs) - len(ok)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    st = b.stats[0]
    _emit({"line": "exec_stats", "sync_points": st.sync_points,
           "bytes_received_f32": st.bytes_received,
           "redundant_elems": st.redundant_elems,
           "compute_stages": st.compute_stages})
    _emit({"line": "window", "requests": len(reqs), "failed": failed,
           "compiles_in_window": events.counts[COMPILE_EVENT],
           "traces_in_window": events.counts[TRACE_EVENT],
           "peak_bytes_in_use": mem,
           "first_error": next((r.error for r in reqs if r.error), None)})
    _emit(_lowering(b.graph, b.weights, b.plan, chips))

    t_ref = time.perf_counter()
    errs = answer_errors(c, b, reqs)
    _emit({"line": "reference", "images": len({r.image for r in ok}),
           "reference_s": time.perf_counter() - t_ref,
           "rel_err_median": statistics.median(errs) if errs else None})
    checks = {
        "rel_err": {"value": max(errs) if errs else math.inf,
                    "limit": c.limits["rel_err"]["limit"]},
        "failed": {"value": failed, "limit": 0},
    }
    correct = bool(ok) and all(v["value"] <= v["limit"]
                               for v in checks.values())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": chips, "memory_peak_bytes": max(mem)}
    result = {"correct": correct, "attempted": len(reqs), "failed": failed}
    if trace:
        paths = [os.path.join(dp, f) for dp, _, fs in os.walk(tdir)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise BenchError(f"expected one .xplane.pb, found {paths}")
        reduced = reduce_xspace(paths[0], chips)
        shutil.rmtree(tdir, ignore_errors=True)
        tr = Trace(reduced)
        conv_min_s, bound = work.conv_min_seconds(b.table, itemsize, peak)
        _emit({"line": "work", **work.summary(b.table, itemsize),
               "conv_min_s": conv_min_s, "conv_bound_layers": bound,
               "traced_images": tr.images})
        ctx = SimpleNamespace(trace=tr, peak=peak, table=b.table,
                              model_flops=work.model_flops(b.table),
                              conv_min_s=conv_min_s)
        metrics = {}
        for m in per_layer_metrics(c.spec, name):
            mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        n = len(tr.devices)
        device.update(busy_s=sum(tr.busy_s(d) for d in range(n)) / n,
                      window_s=tr.window_s)
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": tr.top_ops(), "idle_gaps": tr.idle_by_span()})
    else:
        lat = [r.latency_s for r in ok]
        span = (max(r.done_s for r in ok) - start) if ok else math.inf
        e2e = {"latency_p50_ms": percentile_ms(lat, 50) if lat else None,
               "latency_p90_ms": percentile_ms(lat, 90) if lat else None,
               "images_per_s": len(ok) / span, "setup_s": setup_s}
        units = {m["name"]: m["unit"] for m in c.spec["end_to_end"]
                 if _applies(m, name)}
        result.update(metrics={k: {"value": v, "unit": units[k]}
                               for k, v in e2e.items()
                               if k in units and v is not None},
                      device=device)
    result["checks"] = checks
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv) -> int:
    t0 = time.perf_counter()
    args = parse_args(argv)
    c = load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU found: JAX runs on {devs[0].platform}",
              file=sys.stderr)
        return 1
    chips = c.cell["chips"]
    if len(devs) < chips:
        print(f"{args.workload} needs {chips} TPU chips, JAX finds "
              f"{len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    result = run_cell(c, args.seed, args.seconds, bool(args.trace),
                      devs[:chips], t0)
    for k, v in result["checks"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
