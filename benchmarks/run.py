# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--json [PATH]`` additionally writes the search-time records to
# BENCH_search.json (default) for the CI perf-trajectory artifact.
# ``--trace-dir PATH`` captures Perfetto traces + metrics snapshots from
# the mesh and churn benches into PATH (see repro.obs).
from __future__ import annotations

import os
import sys


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if os.environ.get("JAX_PLATFORMS") == "cpu" \
            and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # every bench runs in this process: give the CPU platform the
        # mesh and decode benches' fake devices before JAX starts
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    from .common import json_arg, trace_dir_arg
    json_path = json_arg(argv)
    trace_dir = trace_dir_arg(argv)

    from . import (churn_bench, decode_bench, engine_comm,
                   estimator_quality, fig2_microbench,
                   fig7_fig9_comparison, fig8_score, kernel_bench,
                   mesh_bench, roofline_table, search_time, sweep, tpu_ce)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    fig2_microbench.run()
    fig7_fig9_comparison.run(4, "fig7")
    fig7_fig9_comparison.run(3, "fig9")
    fig8_score.run()
    search_time.run(json_path=json_path)
    # heterogeneous-cluster scale sweep, reduced grid (full grid + JSON via
    # benchmarks.sweep --json)
    sweep.run(smoke=True)
    engine_comm.run()
    # Pallas-vs-XLA shard kernel timings + conformance flags (JSON via
    # benchmarks.kernel_bench --json)
    kernel_bench.run()
    # mesh executor vs single-process engine, reduced model set (full set
    # + JSON via benchmarks.mesh_bench --json; respawns with fake devices)
    mesh_bench.run(smoke=True, trace_dir=trace_dir)
    # elastic-cluster churn replay: gated scenarios only (full scenario
    # set + JSON via benchmarks.churn_bench --full --json)
    churn_bench.run(smoke=True, trace_dir=trace_dir)
    # autoregressive decode: sharded-vs-oracle flags + tok/s, smoke grid
    # (full spec x nodes grid + JSON via benchmarks.decode_bench --json)
    decode_bench.run(smoke=True)
    # data-driven CE: small trace budget by default (full 330K via
    # benchmarks.estimator_quality --full)
    estimator_quality.run(n_samples=8_000, trees=40)
    roofline_table.run()
    tpu_ce.run()


if __name__ == "__main__":
    main()
