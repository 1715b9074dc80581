"""The algorithm's work per layer, from the layer shapes alone.

FLOP count a multiply-add as two operations (a pooling compare and an
elementwise add as one), as the paper's cost model does.  Bytes are what
the algorithm must move at least once: its inputs, its weights and its
output, each read or written once at the configuration's dtype.  Halo
recompute, padding and phase-split copies, which a partitioned run adds,
are not work and are left out.

A layer is a plain dict (see :func:`layer`), as the references in
``chipbench/models`` build their tables.  ``OPS`` holds each op kind's
work; a reference module adds the kinds it needs in its own ``OPS``,
which the harness merges with this one (``run.op_tables``).  Every
function below takes the merged table as ``ops``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Mapping, Sequence, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}

#: layer kinds whose work runs on the conv shard kernel
CONV_OPS = ("conv", "dwconv")

Shape = Tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class Op:
    """One op kind's work: the shapes of its weight tensors (none, one,
    or several, each drawn from a key of its own), its FLOP, and the bytes
    it moves at a given item size."""
    weights: Callable[[Dict], Tuple[Shape, ...]]
    flops: Callable[[Dict], int]
    bytes: Callable[[Dict, int], int]


def layer(name: str, op: str, h: int, w: int, cin: int, cout: int,
          k: int = 1, s: int = 1, p: int = 0) -> Dict:
    """One row of a layer table: ``op`` names an op kind and ``h``, ``w``,
    ``cin`` are the input's shape."""
    return {"name": name, "op": op, "in_h": h, "in_w": w, "in_c": cin,
            "out_c": cout, "k": k, "s": s, "p": p,
            "out_h": (h + 2 * p - k) // s + 1,
            "out_w": (w + 2 * p - k) // s + 1}


def in_elems(l: Dict) -> int:
    return l["in_h"] * l["in_w"] * l["in_c"]


def out_elems(l: Dict) -> int:
    return l["out_h"] * l["out_w"] * l["out_c"]


def moved(l: Dict, itemsize: int, shapes: Sequence[Shape] = (),
          inputs: int = 1) -> int:
    """Bytes of ``inputs`` reads of the input, one read of each weight
    tensor in ``shapes`` and one write of the output."""
    elems = (inputs * in_elems(l) + sum(math.prod(s) for s in shapes)
             + out_elems(l))
    return elems * itemsize


def _conv_w(l: Dict) -> Tuple[Shape, ...]:
    return ((l["k"], l["k"], l["in_c"], l["out_c"]),)


def _dwconv_w(l: Dict) -> Tuple[Shape, ...]:
    return ((l["k"], l["k"], 1, l["in_c"]),)


def _fc_w(l: Dict) -> Tuple[Shape, ...]:
    return ((l["in_c"], l["out_c"]),)


def _taps(l: Dict) -> int:
    return l["k"] * l["k"]


#: the op kinds of the image networks: HWIO weights for convs,
#: ``(1, ...)``-grouped for depthwise, ``(in, out)`` for the classifier
OPS: Dict[str, Op] = {
    "conv": Op(_conv_w,
               lambda l: 2 * out_elems(l) * l["in_c"] * _taps(l),
               lambda l, b: moved(l, b, _conv_w(l))),
    "dwconv": Op(_dwconv_w,
                 lambda l: 2 * out_elems(l) * _taps(l),
                 lambda l, b: moved(l, b, _dwconv_w(l))),
    "pool": Op(lambda l: (),
               lambda l: out_elems(l) * _taps(l),
               lambda l, b: moved(l, b)),
    "add": Op(lambda l: (),
              out_elems,
              lambda l, b: moved(l, b, inputs=2)),
    "fc": Op(_fc_w,
             lambda l: 2 * l["in_h"] * l["in_c"] * l["out_c"],
             lambda l, b: moved(l, b, _fc_w(l))),
}


def op_of(l: Dict, ops: Mapping[str, Op] = OPS) -> Op:
    if l["op"] not in ops:
        raise ValueError(f"unknown layer op {l['op']!r}")
    return ops[l["op"]]


def weight_shapes(l: Dict, ops: Mapping[str, Op] = OPS
                  ) -> Tuple[Shape, ...]:
    return op_of(l, ops).weights(l)


def weight_elems(l: Dict, ops: Mapping[str, Op] = OPS) -> int:
    return sum(math.prod(s) for s in weight_shapes(l, ops))


def flops(l: Dict, ops: Mapping[str, Op] = OPS) -> int:
    return op_of(l, ops).flops(l)


def bytes_moved(l: Dict, itemsize: int,
                ops: Mapping[str, Op] = OPS) -> int:
    return op_of(l, ops).bytes(l, itemsize)


def model_flops(table: Sequence[Dict], ops: Mapping[str, Op] = OPS) -> int:
    return sum(flops(l, ops) for l in table)


def min_seconds(l: Dict, itemsize: int, peak: Dict,
                ops: Mapping[str, Op] = OPS) -> Tuple[float, str]:
    """The least time one chip could take for the layer, and which bound
    sets it (``"compute"`` or ``"memory"``)."""
    t_c = flops(l, ops) / peak["flops_per_s"]
    t_m = bytes_moved(l, itemsize, ops) / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def conv_min_seconds(table: Sequence[Dict], itemsize: int, peak: Dict,
                     ops: Mapping[str, Op] = OPS
                     ) -> Tuple[float, Dict[str, int]]:
    """Summed least time of the conv-kernel layers, and how many of them
    each bound sets."""
    total, bound = 0.0, {"compute": 0, "memory": 0}
    for l in table:
        if l["op"] in CONV_OPS:
            t, which = min_seconds(l, itemsize, peak, ops)
            total += t
            bound[which] += 1
    return total, bound


def summary(table: List[Dict], itemsize: int,
            ops: Mapping[str, Op] = OPS) -> Dict:
    weights = sum(weight_elems(l, ops) for l in table)
    return {"layers": len(table), "weights": weights,
            "weight_bytes": weights * itemsize,
            "flops": model_flops(table, ops)}
