"""A test-only reference: the program's ``bert`` graph, a chain of FC
layers (per block the QKV projection, the attention's output projection,
and the two FFN matmuls; the graph computes no attention), at
``seq=16, d=32, n_layers=1, d_ff=64``.  A request is one ``[seq, 1, d]``
float32 tensor.  It needs no op kind of its own."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from chipbench.work import layer

CONFIG = {"name": "bert_chain", "model": "bert",
          "reference": "ref_bert_chain", "seq": 16, "d": 32,
          "n_layers": 1, "d_ff": 64, "dtype": "float32",
          "precision": "highest"}


def model_args(cfg) -> Dict:
    return {k: cfg[k] for k in ("seq", "d", "n_layers", "d_ff")}


def layers(cfg) -> List[Dict]:
    seq, d, ff = cfg["seq"], cfg["d"], cfg["d_ff"]
    out = []
    for i in range(cfg["n_layers"]):
        for name, cin, cout in (("qkv", d, 3 * d), ("attn_out", 3 * d, d),
                                ("ffn_up", d, ff), ("ffn_down", ff, d)):
            out.append(layer(f"b{i}.{name}", "fc", seq, 1, cin, cout))
    return out


def inputs(cfg, rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.standard_normal((n, cfg["seq"], 1, cfg["d"]),
                               dtype=np.float32)


def forward(cfg, ws, x, precision: str):
    """``[N, seq, 1, d]`` of ``x`` ``[N, seq, 1, d]``."""
    from chipbench import numerics as nm
    for w in ws:
        x = nm.dot(x, w, precision)
    return x
