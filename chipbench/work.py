"""The algorithm's work per layer, from the layer shapes alone.

FLOP count a multiply-add as two operations (a pooling compare and an
elementwise add as one), as the paper's cost model does.  Bytes are what
the algorithm must move at least once: its inputs, its weights and its
output, each read or written once at the configuration's dtype.  Halo
recompute, padding and phase-split copies, which a partitioned run adds,
are not work and are left out.

A layer is a plain dict (see :func:`layer`), as the references in
``chipbench/models`` build their tables.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}

#: layer kinds whose work runs on the conv shard kernel
CONV_OPS = ("conv", "dwconv")


def layer(name: str, op: str, h: int, w: int, cin: int, cout: int,
          k: int = 1, s: int = 1, p: int = 0) -> Dict:
    """One row of a layer table: ``op`` is conv, dwconv, pool, add or fc
    and ``h``, ``w``, ``cin`` are the input's shape."""
    return {"name": name, "op": op, "in_h": h, "in_w": w, "in_c": cin,
            "out_c": cout, "k": k, "s": s, "p": p,
            "out_h": (h + 2 * p - k) // s + 1,
            "out_w": (w + 2 * p - k) // s + 1}


def weight_shape(l: Dict) -> Optional[Tuple[int, ...]]:
    """HWIO for convs, ``(1, ...)``-grouped for depthwise, ``(in, out)``
    for the classifier; None for layers without weights."""
    if l["op"] == "conv":
        return (l["k"], l["k"], l["in_c"], l["out_c"])
    if l["op"] == "dwconv":
        return (l["k"], l["k"], 1, l["in_c"])
    if l["op"] == "fc":
        return (l["in_c"], l["out_c"])
    return None


def weight_elems(l: Dict) -> int:
    shape = weight_shape(l)
    n = 1
    for d in shape or (0,):
        n *= d
    return n


def flops(l: Dict) -> int:
    out = l["out_h"] * l["out_w"] * l["out_c"]
    taps = l["k"] * l["k"]
    if l["op"] == "conv":
        return 2 * out * l["in_c"] * taps
    if l["op"] == "dwconv":
        return 2 * out * taps
    if l["op"] == "pool":
        return out * taps
    if l["op"] == "add":
        return out
    if l["op"] == "fc":
        return 2 * l["in_h"] * l["in_c"] * l["out_c"]
    raise ValueError(f"unknown layer op {l['op']!r}")


def bytes_moved(l: Dict, itemsize: int) -> int:
    inputs = 2 if l["op"] == "add" else 1
    elems = (inputs * l["in_h"] * l["in_w"] * l["in_c"] + weight_elems(l)
             + l["out_h"] * l["out_w"] * l["out_c"])
    return elems * itemsize


def model_flops(table: Sequence[Dict]) -> int:
    return sum(flops(l) for l in table)


def min_seconds(l: Dict, itemsize: int, peak: Dict) -> Tuple[float, str]:
    """The least time one chip could take for the layer, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    t_c = flops(l) / peak["flops_per_s"]
    t_m = bytes_moved(l, itemsize) / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def conv_min_seconds(table: Sequence[Dict], itemsize: int,
                     peak: Dict) -> Tuple[float, Dict[str, int]]:
    """Summed least time of the conv-kernel layers, and how many of them
    each bound sets."""
    total, bound = 0.0, {"compute": 0, "memory": 0}
    for l in table:
        if l["op"] in CONV_OPS:
            t, which = min_seconds(l, itemsize, peak)
            total += t
            bound[which] += 1
    return total, bound


def summary(table: List[Dict], itemsize: int) -> Dict:
    return {"layers": len(table),
            "weights": sum(weight_elems(l) for l in table),
            "weight_bytes": sum(weight_elems(l) for l in table) * itemsize,
            "flops": model_flops(table)}
