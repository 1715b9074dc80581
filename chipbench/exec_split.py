"""Split of a cell's request time into the mesh executor's spans.

Run from the root of a checkout, on a machine with the cell's chips:

    python chipbench/exec_split.py --workload <cell> --seed <n> \
        [--windows 3] [--tail-seconds 6] [--save <dir>]

It sets the cell up as ``run.py`` does, then serves ``--windows`` pairs
of profiled windows of ``run.TRACE_SECONDS``: one with the executor's
profiler-sink tracer (``repro.obs.Tracer(sink="profiler")``) installed
just before ``jax.profiler.start_trace`` and removed just after
``stop_trace``, one without.  Each profiled window is followed by an
untraced tail of ``--tail-seconds``.  One JSON line per window gives the
accepted per-layer metrics read as ``run.py`` reads them, the window's
and the tail's latency, and, with the tracer, the split below.  The
answers are not checked: ``run.py`` does that.

The split reads the executor's spans (``repro.runtime.mesh_exec``) over
the window's requests (``trace.Trace``), per image:

- ``geometry_ms``: time in ``mesh.geometry``;
- ``launch_ms``: time in ``mesh.launch``;
- ``wait_ms``: time in ``mesh.wait``;
- ``executor_other_ms``: ``mesh.request`` time in none of those three:
  program lookup, argument assembly, the flight ring, Python between
  stages;
- ``idle_gaps_inner``: each device gap, at its midpoint, given to the
  innermost of the benchmark's spans and the executor's.

``--save`` keeps the first window with the tracer in ``<dir>``: its
``.xplane.pb``, to read by hand, and its reduced trace, executor spans
included under ``host``, as ``reduced.json``, for a test fixture.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from collections import Counter
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import run, traffic, work  # noqa: E402
from chipbench.trace import (BETWEEN, HOST_SPANS, Trace, _inside,  # noqa: E402
                             reduce_xspace)

#: the executor's spans, outermost first
EXEC_SPANS = ("mesh.request", "mesh.geometry", "mesh.lookup", "mesh.build",
              "mesh.launch", "mesh.wait")
#: the per-layer metrics that ``run.py`` reads from every cell's trace
ACCEPTED = ("device_idle_share", "programs_per_image", "collective_ms",
            "conv_roofline", "mfu")


def reduce_exec_spans(path: str) -> Dict[str, List[List[int]]]:
    """The executor's spans on the trace's host planes, by name, as
    sorted ``[start_ns, dur_ns]`` rows (``reduce_xspace``'s form)."""
    from jax.profiler import ProfileData

    out: Dict[str, List[List[int]]] = {n: [] for n in EXEC_SPANS}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append([int(e.start_ns),
                                        int(e.duration_ns)])
    for spans in out.values():
        spans.sort()
    return out


def _clip(spans, lo: int, hi: int) -> int:
    return sum(max(0, min(a + d, hi) - max(a, lo)) for a, d in spans)


class ExecSplit:
    """The executor's spans over a :class:`Trace`'s window."""

    def __init__(self, tr: Trace):
        self.tr = tr

    def seconds(self, name: str) -> float:
        """Time in the benchmark's or the executor's spans ``name``
        that lies inside the window's requests (one thread's spans of one
        name never overlap)."""
        return sum(_clip(self.tr.host.get(name, []), a, b)
                   for a, b in self.tr.requests) * 1e-9

    def count(self, name: str) -> int:
        """Spans ``name`` that start inside the window's requests."""
        reqs = [(a, b - a) for a, b in self.tr.requests]
        return sum(_inside(reqs, a) for a, _ in self.tr.host.get(name, []))

    def span_at(self, t: int) -> str:
        """The innermost benchmark or executor span the host was in at
        ``t``."""
        found = BETWEEN
        for name in HOST_SPANS + EXEC_SPANS:
            if _inside(self.tr.host.get(name, []), t):
                found = name
        return found

    def idle_by_inner_span(self) -> List[List]:
        """``Trace.idle_by_span``, by the innermost span."""
        tr = self.tr
        tot: Dict[str, float] = {}
        for d in range(len(tr.devices)):
            for a, b in tr.gaps(d):
                k = self.span_at((a + b) // 2)
                tot[k] = tot.get(k, 0.0) + (b - a) * 1e-9
        n = len(tr.devices)
        return sorted(([k, v / n] for k, v in tot.items()),
                      key=lambda kv: -kv[1])

    def programs_by_kind(self, d: int = 0) -> Dict[str, float]:
        """Module launches on device ``d`` inside the window's requests,
        per image, by module name without its hash: a stage program's is
        ``jit_stage_<kind>``."""
        reqs = [(a, b - a) for a, b in self.tr.requests]
        kinds = Counter(name.split("(")[0]
                        for name, a, _ in self.tr.devices[d]["modules"]
                        if _inside(reqs, a))
        return {k: v / self.tr.images for k, v in sorted(kinds.items())}

    def split(self) -> Dict:
        """Per-image milliseconds, counts and coverage."""
        n = self.tr.images
        if n == 0 or not self.tr.host.get("mesh.request"):
            return {}
        ms = {k: 1e3 * self.seconds(k) / n for k in EXEC_SPANS}
        other = ms["mesh.request"] - ms["mesh.geometry"] - \
            ms["mesh.launch"] - ms["mesh.wait"]
        session = self.seconds("session_run")
        idle = self.idle_by_inner_span()
        idle_session = sum(v for k, v in self.tr.idle_by_span()
                           if k == "session_run")
        idle_named = sum(v for k, v in idle if k in EXEC_SPANS)
        return {
            "geometry_ms": ms["mesh.geometry"],
            "launch_ms": ms["mesh.launch"],
            "executor_other_ms": other,
            "wait_ms": ms["mesh.wait"],
            "lookup_ms": ms["mesh.lookup"],
            "request_ms": ms["mesh.request"],
            "session_run_ms": 1e3 * session / n,
            "request_cover_of_session_run": (
                self.seconds("mesh.request") / session if session else None),
            "idle_session_run_under_exec_span": (
                idle_named / idle_session if idle_session else None),
            "per_image": {k: self.count(k) / n for k in EXEC_SPANS},
            "idle_gaps_inner": idle,
            "programs_by_kind": self.programs_by_kind(),
        }


def accepted_metrics(tr: Trace, b, cfg: Dict, peak: Dict) -> Dict:
    """The accepted per-layer metrics, read as ``run.py`` reads them."""
    itemsize = work.DTYPE_BYTES[cfg["dtype"]]
    conv_min_s, _ = work.conv_min_seconds(b.table, itemsize, peak, b.ops)
    ctx = SimpleNamespace(trace=tr, peak=peak, table=b.table,
                          model_flops=work.model_flops(b.table, b.ops),
                          conv_min_s=conv_min_s)
    out = {}
    for name in ACCEPTED:
        v = importlib.import_module(f"chipbench.metrics.{name}").read(ctx)
        if v is not None:
            out[name] = v
    return out


def _p50_ms(reqs) -> float:
    lat = [r.latency_s for r in reqs if r.error is None]
    return 1e3 * statistics.median(lat) if lat else float("nan")


def window(b, chips: int, tracer: bool, tail_s: float, cfg: Dict,
           peak: Dict, save: str = "") -> Dict:
    """One profiled window, with or without the executor's tracer, then
    an untraced tail."""
    import jax

    from repro import obs
    tdir = tempfile.mkdtemp(prefix="chipbench-split-")
    start = time.perf_counter()
    if tracer:
        obs.set_tracer(obs.Tracer(sink="profiler"))
    jax.profiler.start_trace(tdir)
    reqs = traffic.closed_loop(b.serve, b.pool, b.seq,
                               start + run.TRACE_SECONDS)
    jax.profiler.stop_trace()
    obs.set_tracer(None)
    t = time.perf_counter()
    tail = traffic.closed_loop(b.serve, b.pool, b.seq, t + tail_s,
                               first=len(reqs))
    paths = [os.path.join(dp, f) for dp, _, fs in os.walk(tdir)
             for f in fs if f.endswith(".xplane.pb")]
    reduced = reduce_xspace(paths[0], chips)
    reduced["host"].update(reduce_exec_spans(paths[0]))
    if save:
        os.makedirs(save, exist_ok=True)
        shutil.copy(paths[0], os.path.join(save, "trace.xplane.pb"))
        Path(save, "reduced.json").write_text(json.dumps(reduced))
    shutil.rmtree(tdir, ignore_errors=True)
    tr = Trace(reduced)
    n = len(tr.devices)
    out = {"tracer": tracer, "images": tr.images,
           "window_p50_ms": _p50_ms(reqs), "tail_p50_ms": _p50_ms(tail),
           "failed": sum(r.error is not None for r in reqs + tail),
           "busy_s": sum(tr.busy_s(d) for d in range(n)) / n,
           "window_s": tr.window_s,
           "metrics": accepted_metrics(tr, b, cfg, peak),
           "idle_gaps": tr.idle_by_span()}
    out.update(ExecSplit(tr).split())
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--tail-seconds", type=float, default=6.0)
    ap.add_argument("--save", default="")
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    import jax
    devs = jax.devices()
    chips = c.cell["chips"]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"{args.workload} needs {chips} TPU chips, JAX finds "
              f"{len(devs)} {devs[0].platform}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = c.config
    peak = json.loads((run.BENCH / "peaks.json").read_text()
                      )["devices"][devs[0].device_kind]
    with jax.default_matmul_precision(cfg["precision"]):
        b = run.build(c, args.seed, devs[:chips])
        run.warm_up(b)
        for i in range(args.windows):
            for tracer in (True, False):
                line = window(b, chips, tracer, args.tail_seconds, cfg,
                              peak, args.save if i == 0 and tracer else "")
                print(json.dumps({"workload": args.workload,
                                  "seed": args.seed, **line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
