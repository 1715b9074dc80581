"""Shared helpers for the benchmark harness."""
from __future__ import annotations

import os
import time
from typing import Callable, Tuple

from repro.core import AnalyticEstimator

EST = AnalyticEstimator()


def time_call(fn: Callable, repeats: int = 3) -> Tuple[float, object]:
    best = float("inf")
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out   # us


def emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")


def trace_dir_arg(argv):
    """Parse an optional ``--trace-dir PATH`` flag (shared by run.py and
    the mesh/churn bench CLIs).  Returns None when absent."""
    if "--trace-dir" not in argv:
        return None
    i = argv.index("--trace-dir")
    if i + 1 >= len(argv) or argv[i + 1].startswith("-"):
        raise SystemExit("--trace-dir requires a PATH argument")
    return argv[i + 1]


def json_arg(argv, default: str = "BENCH_search.json"):
    """Parse an optional ``--json [PATH]`` flag (shared by run.py and
    search_time's CLI).  Returns None when absent, ``default`` when the
    flag has no value (or the next token is another flag)."""
    if "--json" not in argv:
        return None
    i = argv.index("--json")
    if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
        return argv[i + 1]
    return default


def respawn_for_host_devices(n: int) -> bool:
    """Whether a bench that needs ``n`` devices should re-run itself in a
    child with ``n`` faked host devices.  Only on the CPU platform
    (``JAX_PLATFORMS=cpu``) whose ``XLA_FLAGS`` ask for fewer, and only
    while this process has not initialised a JAX backend: a process that
    has touched JAX may hold the chip, and a child could not have it.
    Otherwise the bench runs in-process on the devices it is given."""
    from jax._src import xla_bridge

    from repro.launch.mesh import requested_host_devices
    return (os.environ.get("JAX_PLATFORMS") == "cpu"
            and (requested_host_devices() or 1) < n
            and not xla_bridge.backends_are_initialized())
