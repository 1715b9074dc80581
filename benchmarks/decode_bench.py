"""Autoregressive decode benchmark: prefill vs decode tokens/s and
sharded-vs-single-device equivalence flags -> ``BENCH_decode.json``.

For each transformer spec and node count this searches a decode plan
(head-sharding testbed), runs greedy decode through :class:`DecodeSession`
on the local executor and on the mesh executor (one device per node:
points with more nodes than devices skip the mesh run; on the CPU the
bench fakes 8 host devices, respawn pattern shared with ``mesh_bench``),
and records:

* ``head_sharded`` — the planner chose OutC on every ATTN step (the
  decode-graph cost physics held up);
* ``tokens_match_local`` / ``tokens_match_mesh`` — greedy tokens are
  identical to the single-device contiguous oracle
  (``reference_decode``), token for token;
* ``logits_rel_err`` — max relative logits error vs the oracle;
* ``prefill_tok_s`` / ``decode_tok_s`` — warm tokens/s for the prompt
  pass and the generation loop (the decode-phase number is the one the
  paged cache exists for);
* ``decode_step_us`` — warm per-token step wall time, local executor.

``check_regression.py --kind decode`` gates the three boolean flags
**hard**; every timing is **advisory** — same CPU-fake-device rationale
as ``BENCH_mesh.json`` (see ``noise_note``), and interpret-mode Pallas
timings would be meaningless anyway.  The smoke subset (per-push CI)
covers the tiny spec at 2/4 nodes; the full run adds 8 nodes, the larger
spec, and a pallas-backend decode flag.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit, json_arg, respawn_for_host_devices

#: OutC-friendly decode testbed (SRIO-class link latency) — matches the
#: equivalence suite in tests/test_decode.py
BANDWIDTH_GBPS = 5.0
LINK_LATENCY_US = 1.0

SPECS = {
    "tiny": dict(n_layers=2, d_model=256, n_heads=8, d_ff=1024, vocab=64),
    "small": dict(n_layers=4, d_model=512, n_heads=8, d_ff=2048,
                  vocab=256),
}
SMOKE = {"tiny": (2, 4)}
FULL = {"tiny": (2, 4, 8), "small": (2, 4, 8)}

PROMPT_LEN = 8
N_NEW = 8
KV_LEN = 2048      # planning horizon for the decode-step cost model

NOISE_NOTE = (
    "All *_us / *_tok_s fields are advisory on CPU CI: mesh 'devices' "
    "are XLA host-platform fakes time-sharing one CPU and the pallas "
    "decode kernel runs in interpret mode. Only the boolean flags "
    "(head_sharded/tokens_match_local/tokens_match_mesh/"
    "tokens_match_pallas) are gated.")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: host devices the CPU platform fakes for the mesh runs
MESH_DEVICES = 8


def _bench_point(spec_name: str, nodes: int, full: bool) -> dict:
    import time

    import jax
    import numpy as np
    from repro.core import Scheme, Testbed
    from repro.runtime.decode import (DecodeSession, TransformerSpec,
                                      greedy_decode, init_transformer,
                                      plan_decode, reference_decode)
    from repro.runtime.session import ExecConfig

    spec = TransformerSpec(**SPECS[spec_name])
    w = init_transformer(spec, seed=1)
    prompt = [(7 * i + 3) % spec.vocab for i in range(PROMPT_LEN)]
    ref_toks, ref_lg = reference_decode(spec, w, prompt, N_NEW)
    scale = max(1.0, float(np.max(np.abs(np.asarray(ref_lg)))))

    tb = Testbed(nodes=nodes, bandwidth_gbps=BANDWIDTH_GBPS,
                 link_latency_us=LINK_LATENCY_US)
    plan = plan_decode(spec, KV_LEN, nodes, tb=tb).plan
    head_sharded = all(s == Scheme.OUTC for i, (s, _) in
                       enumerate(plan.steps) if i % 2 == 0)

    def _decode(config):
        sess = DecodeSession(spec, w, plan, nodes, config, page_size=16,
                             capacity=PROMPT_LEN + N_NEW + 8)
        t0 = time.perf_counter()
        sess.prefill(prompt[:-1])
        t1 = time.perf_counter()
        # greedy_decode prefills its prompt arg: feed it the held-back
        # last prompt token so the cache sees the full prompt exactly once
        toks, lg = greedy_decode(sess, prompt[-1:], N_NEW)
        t2 = time.perf_counter()
        err = float(np.max(np.abs(np.asarray(lg) -
                                  np.asarray(ref_lg)))) / scale
        return toks == ref_toks, err, t1 - t0, t2 - t1

    # warm + timed local pass (second DecodeSession reuses the process-wide
    # compiled step via jit cache keyed on geometry)
    _decode(ExecConfig())
    ok_local, rel_err, prefill_s, decode_s = _decode(ExecConfig())

    ok_mesh = None
    if nodes <= len(jax.devices()):
        ok_mesh, _, _, _ = _decode(ExecConfig(executor="mesh"))

    rec = {
        "head_sharded": head_sharded,
        "schemes": [s.name for s, _ in plan.steps],
        "tokens_match_local": ok_local,
        "tokens_match_mesh": ok_mesh,
        "logits_rel_err": rel_err,
        "prefill_tok_s": (PROMPT_LEN - 1) / max(prefill_s, 1e-12),
        "decode_tok_s": (N_NEW + 1) / max(decode_s, 1e-12),
        "decode_step_us": decode_s / (N_NEW + 1) * 1e6,
    }
    if full:
        ok_pallas, _, _, _ = _decode(ExecConfig(backend="pallas"))
        rec["tokens_match_pallas"] = ok_pallas
    return rec


def _run_inner(json_path: str | None, smoke: bool) -> dict:
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    grid = SMOKE if smoke else FULL
    record = {"devices": len(jax.devices()), "noise_note": NOISE_NOTE,
              "prompt_len": PROMPT_LEN, "n_new": N_NEW, "kv_len": KV_LEN,
              "specs": {}}
    for spec_name, node_counts in grid.items():
        record["specs"][spec_name] = {}
        for nodes in node_counts:
            rec = _bench_point(spec_name, nodes, full=not smoke)
            record["specs"][spec_name][str(nodes)] = rec
            flags = "ok" if (rec["head_sharded"]
                             and rec["tokens_match_local"]
                             and rec["tokens_match_mesh"] is not False
                             and rec.get("tokens_match_pallas", True)) \
                else "FLAG"
            emit(f"decode_{spec_name}_n{nodes}", rec["decode_step_us"],
                 f"decode={rec['decode_tok_s']:.0f}tok/s "
                 f"prefill={rec['prefill_tok_s']:.0f}tok/s "
                 f"rel_err={rec['logits_rel_err']:.1e} {flags}")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    return record


def run(json_path: str | None = None, smoke: bool = False) -> dict:
    """Entry point used by ``benchmarks.run``: runs in-process, or
    respawns with forced host devices where
    :func:`~benchmarks.common.respawn_for_host_devices` allows (same
    pattern as ``mesh_bench``)."""
    if not respawn_for_host_devices(MESH_DEVICES):
        return _run_inner(json_path, smoke)
    out_path = os.path.abspath(json_path) if json_path else \
        os.path.join(_ROOT, "BENCH_decode.json")
    cmd = [sys.executable, "-m", "benchmarks.decode_bench",
           "--json", out_path]
    if smoke:
        cmd.append("--smoke")
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={MESH_DEVICES}"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"),
                    env.get("PYTHONPATH", "")) if p)
    r = subprocess.run(cmd, env=env, cwd=_ROOT, capture_output=True,
                       text=True, timeout=3600)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        raise RuntimeError("decode_bench subprocess failed")
    with open(out_path) as f:
        return json.load(f)


if __name__ == "__main__":
    argv = sys.argv[1:]
    run(json_path=json_arg(argv, default="BENCH_decode.json"),
        smoke="--smoke" in argv)
