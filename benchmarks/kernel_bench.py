"""Per-layer shard-kernel timings: Pallas vs the XLA lowering.

For each representative shard geometry of the edge benchmarks (conv /
strided conv / stem / depthwise / pointwise on an INH shard slice with
halo rows, plus the FC matmul tile) this times the jitted Pallas path
against the jitted XLA path on one node's halo-extended input, checks
conformance (scale-normalized max error), and records everything into
``BENCH_kernels.json``:

* ``kernels.<name>``: ``{pallas_us, xla_us, ratio, max_rel_err,
  conformant}``
* ``backend_equiv.<model>``: engine-level ``backend="pallas"`` vs
  ``backend="xla"`` on the planner's plan — ``{rel_err, stats_equal,
  agree}``

``benchmarks/check_regression.py --kind kernels`` gates CI on the
committed baseline: a flipped ``conformant``/``agree``/``stats_equal``
flag always fails; timing ratios follow the usual 2x / noise-floor rule.
In this container Pallas runs in interpret mode, so ``pallas_us`` is an
emulation number — the conformance flags are the point; on a TPU the same
record tracks real kernel time.
"""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

from repro.configs.edge_models import EDGE_MODELS
from repro.core import Testbed
from repro.core.dpp import plan_search
from repro.kernels.conv2d import conv2d_shard
from repro.kernels.mode import interpret_mode
from repro.kernels.ops import matmul_tiled
from repro.kernels.ref import conv2d_shard_ref, matmul_ref
from repro.runtime.engine import init_weights
from repro.runtime.session import ExecConfig, Session

from .common import EST, emit, json_arg, time_call

#: (name, kind, geometry) — shard shapes of the edge models' hot layers
#: on one of 4 INH nodes (height quarter + halo), channel counts trimmed
#: so interpret-mode timing stays tractable
_SHARD_CASES = [
    # name, (Hl, Wl, cin, cout, k, s, pads)
    ("conv3x3_s1_interior", (16, 56, 32, 32, 3, 1, (0, 0, 1, 1))),
    ("conv3x3_s2_down", (16, 56, 32, 64, 3, 2, (0, 0, 1, 1))),
    ("stem7x7_s2_top", (31, 56, 3, 32, 7, 2, (3, 0, 3, 3))),
    ("dw3x3_s1_interior", (16, 56, 64, 64, 3, 1, (0, 0, 1, 1))),
    ("dw3x3_s2_down", (16, 56, 64, 64, 3, 2, (0, 0, 1, 1))),
    ("pw1x1_s1", (14, 56, 64, 128, 1, 1, (0, 0, 0, 0))),
]

_FC_CASES = [
    ("fc_seq128", (128, 256, 256)),
    ("fc_head", (1, 512, 1000)),
]

#: engine equivalence models (test scale; see tests/test_kernel_conformance)
_EQUIV_MODELS = {
    "resnet18": dict(width=32),
    "inception": dict(width=32),
}

_REL_TOL = 1e-4


def _rel_err(a, b) -> float:
    scale = max(1.0, float(jnp.max(jnp.abs(b))))
    return float(jnp.max(jnp.abs(a - b))) / scale


def _bench_shard(name: str, geo) -> dict:
    Hl, Wl, cin, cout, k, s, pads = geo
    dw = name.startswith("dw")
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (Hl, Wl, cin))
    wshape = (k, k, 1, cin) if dw else (k, k, cin, cout)
    w = jax.random.normal(jax.random.PRNGKey(1), wshape) * 0.1

    pall = jax.jit(lambda a, b: conv2d_shard(
        a, b, pads=pads, stride=s, depthwise=dw))
    xla = jax.jit(lambda a, b: conv2d_shard_ref(
        a, b, pads=pads, stride=s, depthwise=dw))
    out_p = pall(x, w).block_until_ready()      # compile outside the timer
    out_x = xla(x, w).block_until_ready()
    us_p, _ = time_call(lambda: pall(x, w).block_until_ready())
    us_x, _ = time_call(lambda: xla(x, w).block_until_ready())
    err = _rel_err(out_p, out_x)
    return {
        "pallas_us": round(us_p, 1),
        "xla_us": round(us_x, 1),
        "ratio": round(us_p / max(us_x, 1e-9), 2),
        "max_rel_err": err,
        "conformant": bool(err < _REL_TOL),
    }


def _bench_fc(geo) -> dict:
    m, cin, cout = geo
    x = jax.random.normal(jax.random.PRNGKey(2), (m, cin))
    w = jax.random.normal(jax.random.PRNGKey(3), (cin, cout)) * 0.1
    pall = jax.jit(lambda a, b: matmul_tiled(a, b))
    xla = jax.jit(matmul_ref)
    out_p = pall(x, w).block_until_ready()
    out_x = xla(x, w).block_until_ready()
    us_p, _ = time_call(lambda: pall(x, w).block_until_ready())
    us_x, _ = time_call(lambda: xla(x, w).block_until_ready())
    err = _rel_err(out_p, out_x)
    return {
        "pallas_us": round(us_p, 1),
        "xla_us": round(us_x, 1),
        "ratio": round(us_p / max(us_x, 1e-9), 2),
        "max_rel_err": err,
        "conformant": bool(err < _REL_TOL),
    }


def _bench_equiv(model: str, kw: dict) -> dict:
    g = EDGE_MODELS[model](**kw)
    key = jax.random.PRNGKey(0)
    ws = init_weights(g, key)
    l0 = g.layers[0]
    x = jax.random.normal(key, (l0.in_h, l0.in_w, l0.in_c))
    plan = plan_search(g, EST, Testbed(nodes=4, bandwidth_gbps=0.5)).plan
    out_x, st_x = Session(g, ws, plan, 4, ExecConfig(backend="xla")).run(x)
    out_p, st_p = Session(g, ws, plan, 4,
                          ExecConfig(backend="pallas")).run(x)
    err = _rel_err(out_p, out_x)
    return {
        "rel_err": err,
        "stats_equal": bool(st_x == st_p),
        "agree": bool(err < _REL_TOL),
    }


def run(json_path: str | None = None) -> dict:
    out: dict = {"interpret": interpret_mode(),
                 "kernels": {}, "backend_equiv": {}}
    for name, geo in _SHARD_CASES:
        rec = _bench_shard(name, geo)
        out["kernels"][name] = rec
        emit(f"kernel/{name}", rec["pallas_us"],
             f"xla_us={rec['xla_us']};ratio={rec['ratio']};"
             f"conformant={rec['conformant']}")
    for name, geo in _FC_CASES:
        rec = _bench_fc(geo)
        out["kernels"][name] = rec
        emit(f"kernel/{name}", rec["pallas_us"],
             f"xla_us={rec['xla_us']};ratio={rec['ratio']};"
             f"conformant={rec['conformant']}")
    for model, kw in _EQUIV_MODELS.items():
        rec = _bench_equiv(model, kw)
        out["backend_equiv"][model] = rec
        emit(f"kernel/equiv_{model}", rec["rel_err"] * 1e6,
             f"stats_equal={rec['stats_equal']};agree={rec['agree']}")
        assert rec["agree"] and rec["stats_equal"], (
            f"{model}: pallas/xla engine divergence {rec}")
    bad = [n for n, r in out["kernels"].items() if not r["conformant"]]
    assert not bad, f"non-conformant kernels: {bad}"
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print(f"# wrote {json_path}", file=sys.stderr)
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(json_path=json_arg(sys.argv[1:], default="BENCH_kernels.json"))
