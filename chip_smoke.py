"""Bring-up check of the main path on a TPU chip.

Run from the root of a checkout:

    python chip_smoke.py              # one chip: phases A and B below
    python chip_smoke.py --chips 4    # the mesh executor across four chips

Phase A plans ``resnet101(224)`` for 4 nodes with ``plan_search`` and runs
it through ``Session(..., ExecConfig(backend="pallas"))`` on the local
executor, against ``run_reference``.  Phase B plans the ``small`` decode
spec of ``benchmarks/decode_bench.py`` for 4 nodes and decodes 8 prompt
and 16 new tokens through ``DecodeSession`` and ``greedy_decode`` with the
Pallas paged-attention kernel, against ``reference_decode``.  With
``--chips 4`` only the mesh-executor versions run: phase A on 4 devices
against the one-device local run of the same plan, phase B on 4 devices
against ``reference_decode``; each prints the devices its arrays sit on.

Precision: both sides of every comparison run at f32.  The kernels' dots
contract at ``Precision.HIGHEST``, and each phase runs system and
reference under ``jax.default_matmul_precision("highest")``, so XLA's
convs and matmuls also use f32 (multi-pass) contraction instead of the
TPU's default single bf16 pass.  The tolerance on
``max|out - ref| / max|ref|`` follows from that: with unit roundoff
u <= 2**-22 for the multi-pass f32 emulation, a dot over n <= 4608 terms
is off by about sqrt(n)*u <= 2e-5 relative, and the error random-walks
over the ~100 convs of resnet101's longest path to about 2e-4.  ``TOL``
allows 5x that.  A single bf16 pass (u = 2**-9) would give about 3e-2,
so a kernel or op that silently drops to bf16 fails the check.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
and is printed only when every phase passed.  Without a TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
NODES = 4
TOL = 1e-3          # max|out - ref| / max|ref|; see the module docstring
PROMPT_LEN = 8
N_NEW = 16
KV_LEN = 2048       # decode planning horizon, as in benchmarks/decode_bench


def _rel_err(out, ref) -> float:
    import numpy as np
    out, ref = np.asarray(out), np.asarray(ref)
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(out - ref))) / scale


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _schemes(plan) -> str:
    """Run-length form of the plan's (scheme, mode) steps."""
    runs: list = []
    for s, m in plan.steps:
        step = f"{s.name}/{m.name}"
        if runs and runs[-1][0] == step:
            runs[-1][1] += 1
        else:
            runs.append([step, 1])
    return " ".join(f"{step}x{n}" for step, n in runs)


def _devices(arr) -> list:
    return sorted(int(d.id) for d in arr.sharding.device_set)


def _lowering(graph, weights, plan, nodes: int) -> dict:
    """Pallas/XLA record counts of the pallas backend for this plan; only
    POOL and degenerate shards may run on XLA."""
    from repro.core.graph import ConvT
    from repro.runtime.engine import pallas_lowering
    kernels = (ConvT.CONV, ConvT.POINTWISE, ConvT.DWCONV, ConvT.FC,
               ConvT.POOL)
    rows = [r for r in pallas_lowering(graph, weights, plan, nodes)
            if r[1] in kernels]
    xla = [(name, t, why) for name, t, why in rows if why is not None]
    bad = [(name, why) for name, t, why in xla
           if t != ConvT.POOL and not why.startswith("degenerate")]
    return {
        "pallas_records": len(rows) - len(xla),
        "xla_records": len(xla),
        "xla_reasons": [f"{name}: {why} (x{n})" for (name, why), n in
                        sorted(Counter((r[0], r[2]) for r in xla).items())],
        "unexpected_xla": [f"{name}: {why}" for name, why in bad],
    }


def phase_image(width: int = 224, nodes: int = NODES,
                executor: str = "local") -> dict:
    """Phase A: ``resnet101(width)`` planned for ``nodes`` nodes through
    ``Session`` on the pallas backend.  The local executor is compared
    with ``run_reference``; the mesh executor with the local executor's
    output for the same plan."""
    import jax
    import jax.numpy as jnp
    from repro.configs.edge_models import resnet101
    from repro.core import AnalyticEstimator, Testbed
    from repro.core.dpp import plan_search
    from repro.runtime.engine import init_weights, run_reference
    from repro.runtime.session import ExecConfig, Session

    with jax.default_matmul_precision("highest"):
        graph = resnet101(width)
        w = init_weights(graph, jax.random.PRNGKey(SEED))
        l0 = graph.layers[0]
        x = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                              (l0.in_h, l0.in_w, l0.in_c), jnp.float32)
        plan = plan_search(graph, AnalyticEstimator(),
                           Testbed(nodes=nodes)).plan
        sess = Session(graph, w, plan, nodes,
                       ExecConfig(backend="pallas", executor=executor,
                                  fallback="raise"))
        (_, _), first_s = _timed(lambda: sess.run(x))
        (out, stats), warm_s = _timed(lambda: sess.run(x))
        if executor == "local":
            ref = run_reference(graph, w, x)
            against = "run_reference"
        else:
            ref, _ = Session(graph, w, plan, nodes,
                             ExecConfig(backend="pallas")).run(x)
            against = "local executor, one device"
        err = _rel_err(out, ref)
    rec = {
        "phase": "A" if executor == "local" else "A-mesh",
        "model": f"resnet101({width})", "nodes": nodes,
        "executor": executor, "backend": "pallas",
        "schemes": _schemes(plan),
        **_lowering(graph, w, plan, nodes),
        "compile_and_first_run_s": first_s, "warm_run_s": warm_s,
        "rel_err": err, "tol": TOL, "against": against,
        "fallbacks": stats.fallbacks,
    }
    if executor == "mesh":
        rec["out_devices"] = _devices(out)
    rec["ok"] = (err <= TOL and stats.fallbacks == 0
                 and not rec["unexpected_xla"])
    return rec


def phase_decode(spec_name: str = "small", nodes: int = NODES,
                 executor: str = "local") -> dict:
    """Phase B: greedy decode of ``PROMPT_LEN`` + ``N_NEW`` tokens through
    ``DecodeSession`` (pallas paged attention) against
    ``reference_decode``."""
    import jax
    import numpy as np
    from benchmarks.decode_bench import SPECS
    from repro.core import Testbed
    from repro.runtime.decode import (DecodeSession, TransformerSpec,
                                      greedy_decode, init_transformer,
                                      plan_decode, reference_decode)
    from repro.runtime.session import ExecConfig

    with jax.default_matmul_precision("highest"):
        spec = TransformerSpec(**SPECS[spec_name])
        w = init_transformer(spec, seed=SEED)
        rng = np.random.default_rng(SEED)
        prompt = [int(t) for t in rng.integers(0, spec.vocab, PROMPT_LEN)]
        tb = Testbed(nodes=nodes, bandwidth_gbps=5.0, link_latency_us=1.0)
        plan = plan_decode(spec, KV_LEN, nodes, tb=tb).plan
        sess = DecodeSession(spec, w, plan, nodes,
                             ExecConfig(backend="pallas", executor=executor))
        # the first step compiles the step program; the rest reuse it
        _, first_s = _timed(lambda: sess.step(prompt[0]))
        (toks, lg), rest_s = _timed(
            lambda: greedy_decode(sess, prompt[1:], N_NEW))
        ref_toks, ref_lg = reference_decode(spec, w, prompt, N_NEW)
        err = _rel_err(lg, ref_lg)
    rec = {
        "phase": "B" if executor == "local" else "B-mesh",
        "model": f"decode[{spec_name}] {spec}", "nodes": nodes,
        "executor": executor, "backend": "pallas",
        "schemes": _schemes(plan),
        "compile_and_first_step_s": first_s,
        "warm_step_s": rest_s / (PROMPT_LEN - 1 + N_NEW),
        "rel_err": err, "tol": TOL, "against": "reference_decode",
        "tokens_match": toks == ref_toks,
    }
    if executor == "mesh":
        rec["page_devices"] = [_devices(sess.cache.pages(0, n)[0])
                               for n in range(nodes)]
        rec["logits_devices"] = _devices(lg)
    rec["ok"] = err <= TOL
    return rec


def _run_phase(fn, *args, **kw) -> dict:
    try:
        rec = fn(*args, **kw)
    except Exception as exc:    # report the phase, then fail the run
        traceback.print_exc()
        rec = {"phase": fn.__name__, "ok": False, "error": repr(exc)}
    print(json.dumps(rec), flush=True)
    return rec


def main(argv) -> int:
    chips = 1
    if argv:
        if len(argv) != 2 or argv[0] != "--chips" or argv[1] not in ("1", "4"):
            print("usage: chip_smoke.py [--chips 1|4]", file=sys.stderr)
            return 2
        chips = int(argv[1])
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU found: JAX runs on {devs[0].platform}",
              file=sys.stderr)
        return 1
    if len(devs) < chips:
        print(f"--chips {chips} needs {chips} TPU devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if chips == 1:
        recs = [_run_phase(phase_image), _run_phase(phase_decode)]
    else:
        recs = [_run_phase(phase_image, executor="mesh"),
                _run_phase(phase_decode, executor="mesh")]
    if not all(r["ok"] for r in recs):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
