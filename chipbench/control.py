"""Readings that a cell's correctness limit is set from.

    python chipbench/control.py --workload <cell> --seeds 12 \
        --first-seed <n> --seconds 5

In one process, for each seed, two runs of the cell through
``run.run_cell``, each with its own set-up, a closed-loop window of
``--seconds`` and every answer compared with the plain reference under
the cell's limit: the program (``program``), and the control
(``run.Control``, the plain reference one precision lower, in the
``Session``'s place).  One JSON line per seed with each run's ``rel_err``
and ``correct``, then the summary: the lower reading is the largest
``program`` value, the upper reading the smallest ``control`` value.  A
limit is sound only between them, and only if every program run came
out correct and every control run not.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import run  # noqa: E402


def readings(c, seeds, seconds: float, devices, peaks=None) -> dict:
    """``program`` and ``control`` rel_err and ``correct`` per seed, and
    the summary."""
    rows = []
    for seed in seeds:
        row = {"seed": seed}
        for side, control in (("program", False), ("control", True)):
            res = run.run_cell(c, seed, seconds, False, devices,
                               time.perf_counter(), peaks=peaks,
                               control=control)
            row[side] = res["checks"]["rel_err"]["value"]
            row[f"{side}_correct"] = res["correct"]
            row[f"{side}_requests"] = res["attempted"]
        print(json.dumps(row), flush=True)
        rows.append(row)
    lower = max(r["program"] for r in rows)
    upper = min(r["control"] for r in rows)
    return {"workload": c.cell["name"], "seeds": len(rows),
            "limit": c.limits["rel_err"]["limit"],
            "lower": lower, "upper": upper,
            "upper_over_lower": upper / lower if lower > 0 else None,
            "program_all_correct": all(r["program_correct"] for r in rows),
            "control_none_correct": not any(r["control_correct"]
                                            for r in rows)}


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    c = run.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < c.cell["chips"]:
        print(f"{args.workload} needs {c.cell['chips']} TPU chips, JAX "
              f"finds {len(devs)} {devs[0].platform} devices",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    print(json.dumps(readings(c, seeds, args.seconds,
                              devs[:c.cell["chips"]])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
