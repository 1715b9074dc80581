"""Distributed edge-inference engine: executes a FlexPie Plan on real
tensors, node by node, and verifies exact reassembly.

Each simulated edge node computes only from data it actually holds: the
engine backward-chains the receptive field from the node's exact output
shard at the segment end (T layer) through every NT-fused layer, slices
that input region once at the segment entry (counting the bytes the node
did not own — the measured communication), then runs the whole segment
locally.  This exercises the paper's core mechanics end to end: halo
growth, redundant computation, scheme-dependent re-layout.

Branched graphs execute branch by branch (``ModelGraph.linearize()``):
every branch is a chain run through the same segment machinery, fork
outputs are read by each consuming branch, and merge layers (ADD/CONCAT)
reassemble their incoming branch shards at a forced sync point before the
next branch continues.

Correctness contract (tested): for ANY valid plan — chain or DAG — the
reassembled output is identical to the unpartitioned reference inference.

Backends: ``run_partitioned(..., backend="pallas")`` dispatches every
NT-fused segment layer to the Pallas shard kernels (``repro.kernels``) —
conv/depthwise/pointwise shards consume their halo-extended local slice
directly (zero padding applied in VMEM, no re-materialized padded copy per
segment layer) and FC layers run the row-tiled MXU matmul.  Geometries the
kernels cannot lower (POOL, degenerate shard outputs) fall back to the XLA
path per layer record automatically; ``backend="xla"`` (default) is the
historical ``lax.conv_general_dilated`` lowering.  The backend is part of
the compiled-segment cache key, so both backends stay jit-cached side by
side.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import ConvT, LayerSpec, ModelGraph
from repro.core.partition import (DTYPE_BYTES, Mode, Scheme, grid_dims,
                                  split_sizes)
from repro.core.plan import Plan, steps_segments
from repro.kernels.conv2d import UnsupportedGeometry, conv2d_shard
from repro.kernels.ops import matmul_tiled

Rect = Tuple[Tuple[int, int], Tuple[int, int], Tuple[int, int]]

BACKENDS = ("xla", "pallas")
EXECUTORS = ("local", "mesh")


# ---------------------------------------------------------------------------
# Reference (unpartitioned) inference
# ---------------------------------------------------------------------------

def init_weights(graph: ModelGraph, key) -> List[Optional[jnp.ndarray]]:
    ws: List[Optional[jnp.ndarray]] = []
    for l in graph.layers:
        if l.conv_t in (ConvT.CONV, ConvT.POINTWISE):
            key, k = jax.random.split(key)
            ws.append(jax.random.normal(k, (l.k, l.k, l.in_c, l.out_c),
                                        jnp.float32)
                      / np.sqrt(l.k * l.k * l.in_c))
        elif l.conv_t == ConvT.DWCONV:
            key, k = jax.random.split(key)
            ws.append(jax.random.normal(k, (l.k, l.k, 1, l.in_c), jnp.float32)
                      / np.sqrt(l.k * l.k))
        elif l.conv_t == ConvT.FC:
            key, k = jax.random.split(key)
            ws.append(jax.random.normal(k, (l.in_c, l.out_c), jnp.float32)
                      / np.sqrt(l.in_c))
        else:
            ws.append(None)
    return ws


def apply_layer(l: LayerSpec, w, x: jnp.ndarray) -> jnp.ndarray:
    """Full-tensor layer application. x: [H, W, C] (FC: [seq, 1, C])."""
    out = _conv_region(l, w, x, pads=((l.p, l.p), (l.p, l.p)))
    return out


def _conv_region(l: LayerSpec, w, x: jnp.ndarray, pads) -> jnp.ndarray:
    return _conv_region_p(l.conv_t, l.k, l.s, w, x, pads)


def _conv_region_p(conv_t: ConvT, k: int, s: int, w, x: jnp.ndarray,
                   pads) -> jnp.ndarray:
    """Parameter form of :func:`_conv_region` — shared with the jitted
    segment programs, whose cache keys are name-blind geometry tuples."""
    if conv_t in (ConvT.CONV, ConvT.POINTWISE):
        return jax.lax.conv_general_dilated(
            x[None], w, (s, s), list(pads),
            dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]
    if conv_t == ConvT.DWCONV:
        return jax.lax.conv_general_dilated(
            x[None], w, (s, s), list(pads),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1])[0]
    if conv_t == ConvT.POOL:
        return jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (k, k, 1), (s, s, 1),
            [tuple(pads[0]), tuple(pads[1]), (0, 0)])
    if conv_t == ConvT.FC:
        return (x.reshape(x.shape[0], x.shape[-1]) @ w).reshape(
            x.shape[0], 1, -1)
    if conv_t in (ConvT.ADD, ConvT.CONCAT):
        return x   # single-input (chain-compat) merge is the identity
    raise ValueError(conv_t)


def merge_tensors(l: LayerSpec, inputs: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Combine the producer tensors of a multi-input merge layer."""
    if len(inputs) == 1:
        return inputs[0]
    if l.conv_t == ConvT.ADD:
        out = inputs[0]
        for t in inputs[1:]:
            out = out + t
        return out
    if l.conv_t == ConvT.CONCAT:
        return jnp.concatenate(list(inputs), axis=-1)
    raise ValueError(f"{l.name}: only ADD/CONCAT layers can merge")


def run_reference(graph: ModelGraph, weights, x: jnp.ndarray) -> jnp.ndarray:
    if graph.is_chain:
        for l, w in zip(graph.layers, weights):
            x = apply_layer(l, w, x)
        return x
    outs: Dict[int, jnp.ndarray] = {-1: x}
    for i, (l, w) in enumerate(zip(graph.layers, weights)):
        prods = graph.producer_ids[i]
        if len(prods) >= 2:
            outs[i] = merge_tensors(l, [outs[p] for p in prods])
        else:
            outs[i] = apply_layer(l, w, outs[prods[0]])
    return outs[len(graph) - 1]


# ---------------------------------------------------------------------------
# Shard geometry
# ---------------------------------------------------------------------------

def _ranges(total: int, parts: int) -> List[Tuple[int, int]]:
    sizes = split_sizes(total, parts)
    out, a = [], 0
    for s in sizes:
        out.append((a, a + s))
        a += s
    return out


def exact_regions(l: LayerSpec, scheme: Scheme,
                  nodes: int) -> List[List[Rect]]:
    """Per-node exact (halo-free) output cells of layer ``l``.  One cell per
    node for the 1-D schemes; round-robin cell assignment for 2D-grid on
    non-square node counts (the paper's 3-node imbalance case)."""
    oh, ow, oc = l.out_h, l.out_w, l.out_c
    if scheme == Scheme.INH:
        return [[((r0, r1), (0, ow), (0, oc))]
                for r0, r1 in _ranges(oh, nodes)]
    if scheme == Scheme.INW:
        return [[((0, oh), (c0, c1), (0, oc))]
                for c0, c1 in _ranges(ow, nodes)]
    if scheme == Scheme.OUTC:
        return [[((0, oh), (0, ow), (k0, k1))]
                for k0, k1 in _ranges(oc, nodes)]
    if scheme == Scheme.GRID2D:
        gh, gw = grid_dims(nodes)
        cells = [((r0, r1), (c0, c1), (0, oc))
                 for r0, r1 in _ranges(oh, gh) for c0, c1 in _ranges(ow, gw)]
        per_node: List[List[Rect]] = [[] for _ in range(nodes)]
        for i, cell in enumerate(cells):
            per_node[i % nodes].append(cell)
        return per_node
    raise ValueError(scheme)


def in_rows(l: LayerSpec, out_r: Tuple[int, int], dim: int
            ) -> Tuple[int, int]:
    """Unclipped input range needed for an output range along H (dim=0,
    bound l.in_h) or W (dim=1, bound l.in_w).  FC/ADD/CONCAT are 1:1."""
    if l.conv_t in (ConvT.FC, ConvT.ADD, ConvT.CONCAT):
        return out_r
    r0 = out_r[0] * l.s - l.p
    r1 = (out_r[1] - 1) * l.s - l.p + l.k
    return (r0, r1)


def _clip(r: Tuple[int, int], bound: int) -> Tuple[int, int]:
    return (max(0, r[0]), min(bound, r[1]))


# ---------------------------------------------------------------------------
# Plan execution
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StageTime:
    """Measured wall time of one dispatched pipeline stage (mesh executor,
    ``instrument=True``).  ``device_done_s`` holds per-device completion
    offsets of a compute stage's output shards, measured by blocking on
    the shards in mesh order — on shared-core host platforms the values
    are an upper envelope (a shard that finished before an earlier shard
    in the blocking order reports that earlier shard's completion time)."""

    kind: str                            # "compute" | "sync"
    label: str                           # simsched stage label convention
    wall_s: float
    device_done_s: Tuple[float, ...] = ()


@dataclasses.dataclass(frozen=True)
class MeasuredOccupancy:
    """Per-request resource-class occupancy measured from a real run —
    the drop-in counterpart of the simulator occupancy that
    ``cluster.refine`` extracts from a :class:`~repro.cluster.simsched.
    SimReport` (``occupancy_fn`` protocol)."""

    dev_occupancy_s: float     # max over devices of summed compute time
    link_occupancy_s: float    # summed sync-stage wall time
    period_s: float            # pipelined steady-state period estimate
    latency_s: float           # single-request wall time
    #: dispatch failures behind the measurement (retries + timeouts +
    #: degraded fallbacks) — ``cluster.refine`` treats any nonzero value
    #: as an untrusted sample and keeps its previous axis weights
    failures: int = 0


@dataclasses.dataclass
class ExecStats:
    sync_points: int = 0
    bytes_received: float = 0.0      # across all nodes/boundaries (fp32)
    redundant_elems: float = 0.0     # halo outputs computed more than once
    #: executed T-terminated segments — the plan's compute-stage count,
    #: matching ``plan.plan_stage_counts`` and the simulator's stage DAG
    #: (pipeline metadata: serving reads it to align engine runs with
    #: ``cluster.simsched`` schedules)
    compute_stages: int = 0
    #: measured pipeline stages (mesh executor with ``instrument=True``).
    #: Excluded from equality: geometry accounting is executor- and
    #: backend-independent by contract, wall times never are.
    stage_times: List[StageTime] = dataclasses.field(
        default_factory=list, compare=False, repr=False)
    #: end-to-end wall seconds of the run (mesh executor only)
    wall_s: float = dataclasses.field(default=0.0, compare=False)
    #: stage dispatches re-attempted after a failure (mesh executor with
    #: ``stage_retries > 0``).  Excluded from equality with the same
    #: rationale as wall times: failure incidence is environmental, the
    #: geometry accounting above is the executor contract.
    retries: int = dataclasses.field(default=0, compare=False)
    #: stage dispatches that exceeded ``stage_timeout_s``
    timeouts: int = dataclasses.field(default=0, compare=False)
    #: runs completed by the degraded single-process fallback
    fallbacks: int = dataclasses.field(default=0, compare=False)

    @property
    def failure_count(self) -> int:
        """Total faults observed while producing this run's numbers."""
        return self.retries + self.timeouts + self.fallbacks

    def to_occupancy(self) -> MeasuredOccupancy:
        """Fold the measured stage times into per-resource-class occupancy
        for ``cluster.refine`` (replacing sim-only occupancy when real
        measurements exist).  Device occupancy is the straggler device's
        summed compute time; link occupancy sums the sync-stage walls; the
        period is the busier class (the ``PipelineCost`` bottleneck
        semantics applied to measurements)."""
        if not self.stage_times:
            raise ValueError(
                "no measured stages — run with "
                'run_partitioned(..., executor="mesh", instrument=True) '
                "(only the mesh executor measures stage times)")
        per_dev: Dict[int, float] = {}
        sync = 0.0
        for st in self.stage_times:
            if st.kind == "compute":
                if st.device_done_s:
                    for d, t in enumerate(st.device_done_s):
                        per_dev[d] = per_dev.get(d, 0.0) + t
                else:
                    per_dev[0] = per_dev.get(0, 0.0) + st.wall_s
            else:
                sync += st.wall_s
        dev = max(per_dev.values()) if per_dev else 0.0
        return MeasuredOccupancy(
            dev_occupancy_s=dev, link_occupancy_s=sync,
            period_s=max(dev, sync), latency_s=self.wall_s,
            failures=self.failure_count)


def _rect_elems(r: Rect) -> int:
    return max(0, r[0][1] - r[0][0]) * max(0, r[1][1] - r[1][0]) \
        * max(0, r[2][1] - r[2][0])


def _rect_isect(a: Rect, b: Rect) -> Rect:
    return tuple((max(x[0], y[0]), min(x[1], y[1]))
                 for x, y in zip(a, b))  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# Compiled shard segment programs.
#
# One jitted program per *name-blind segment signature*: the per-layer conv
# parameters plus the static pad/slice/channel arithmetic of this cell's
# backward-chained regions.  Identical cells — every interior node of a
# balanced split, and every repetition of a ResNet bottleneck across blocks
# and planner sweeps — share one compiled executable; weights and the input
# tensor are traced arguments, so reuse survives weight changes.
# ---------------------------------------------------------------------------

def backward_chain(layers: Sequence[LayerSpec], a: int, b: int,
                   reg_b: Rect) -> Tuple[Dict[int, Rect], Rect]:
    """Backward-chain the receptive field of output region ``reg_b`` of
    layer ``b`` through segment ``[a..b]``: the per-layer needed output
    regions (clipped to each layer's bounds) and the clipped input rect at
    the segment entry.  Shared by the local executor, which slices the
    rect from the host-resident full tensor, and the mesh executor, which
    assembles it from collectives."""
    need: Dict[int, Rect] = {b: reg_b}
    rows, cols = reg_b[0], reg_b[1]
    for li in range(b, a, -1):
        rows = _clip(in_rows(layers[li], rows, 0), layers[li].in_h)
        cols = _clip(in_rows(layers[li], cols, 1), layers[li].in_w)
        need[li - 1] = (rows, cols, (0, layers[li - 1].out_c))
    l_in = layers[a]
    in_r = _clip(in_rows(l_in, need[a][0], 0), l_in.in_h)
    in_c = _clip(in_rows(l_in, need[a][1], 1), l_in.in_w)
    return need, (in_r, in_c, (0, l_in.in_c))


#: per-layer static record: (conv_t, k, s, pads(pt,pb,pl,pr) | None,
#: slices(r0,r1,c0,c1) | None, chans(c0,c1))
_SegRec = Tuple[int, int, int, Optional[Tuple[int, int, int, int]],
                Optional[Tuple[int, int, int, int]], Tuple[int, int]]


def _segment_records(layers: Sequence[LayerSpec], a: int, b: int,
                     need: Dict[int, Rect],
                     in_rect: Rect) -> Tuple[_SegRec, ...]:
    """Resolve the cell's per-layer slice/pad arithmetic into a static
    signature (the jit cache key; also the full program spec)."""
    recs: List[_SegRec] = []
    origin = (in_rect[0][0], in_rect[1][0])
    extent = (in_rect[0][1] - in_rect[0][0], in_rect[1][1] - in_rect[1][0])
    for li in range(a, b + 1):
        l = layers[li]
        rows, cols, chans = need[li]
        if l.conv_t in (ConvT.FC, ConvT.ADD, ConvT.CONCAT):
            recs.append((int(l.conv_t), l.k, l.s, None, None, chans))
        else:
            nr = in_rows(l, rows, 0)
            nc = in_rows(l, cols, 1)
            pads = (max(0, -nr[0]), max(0, nr[1] - l.in_h),
                    max(0, -nc[0]), max(0, nc[1] - l.in_w))
            sl = (max(0, nr[0]) - origin[0], min(l.in_h, nr[1]) - origin[0],
                  max(0, nc[0]) - origin[1], min(l.in_w, nc[1]) - origin[1])
            assert sl[0] >= 0 and sl[2] >= 0 \
                and sl[1] <= extent[0] and sl[3] <= extent[1], (
                    "local slice does not cover the needed region", l.name)
            recs.append((int(l.conv_t), l.k, l.s, pads, sl, chans))
        origin = (rows[0], cols[0])
        extent = (rows[1] - rows[0], cols[1] - cols[0])
    return tuple(recs)


def _apply_record(rec: _SegRec, w, x: jnp.ndarray) -> jnp.ndarray:
    """One layer of a compiled segment program (static-geometry
    counterpart of :func:`_apply_local`)."""
    conv_t, k, s, pads, sl, chans = rec
    conv_t = ConvT(conv_t)
    if conv_t == ConvT.FC:
        seg = x.reshape(x.shape[0], x.shape[-1])
        return (seg @ w[:, chans[0]:chans[1]]).reshape(
            x.shape[0], 1, chans[1] - chans[0])
    if conv_t in (ConvT.ADD, ConvT.CONCAT):
        return x[:, :, chans[0]:chans[1]]
    pt, pb, pl_, pr = pads
    r0, r1, c0, c1 = sl
    xs = x[r0:r1, c0:c1, :]
    if conv_t in (ConvT.CONV, ConvT.POINTWISE):
        wsel = w[:, :, :, chans[0]:chans[1]]
        return _conv_region_p(conv_t, k, s, wsel, xs, ((pt, pb), (pl_, pr)))
    out = _conv_region_p(conv_t, k, s, w, xs, ((pt, pb), (pl_, pr)))
    return out[:, :, chans[0]:chans[1]]


def _apply_record_pallas(rec: _SegRec, w, x: jnp.ndarray) -> jnp.ndarray:
    """Pallas lowering of one segment-layer record: the local slice (halo
    rows included) goes to the shard kernel as-is with its per-side zero
    pads.  Raises :class:`UnsupportedGeometry` for records the kernels
    cannot lower (POOL, degenerate shard outputs) — the caller falls back
    to the XLA record path."""
    conv_t, k, s, pads, sl, chans = rec
    conv_t = ConvT(conv_t)
    if conv_t == ConvT.FC:
        seg = x.reshape(x.shape[0], x.shape[-1])
        out = matmul_tiled(seg, w[:, chans[0]:chans[1]])
        return out.reshape(x.shape[0], 1, chans[1] - chans[0])
    if conv_t in (ConvT.ADD, ConvT.CONCAT):
        return x[:, :, chans[0]:chans[1]]
    if conv_t not in (ConvT.CONV, ConvT.POINTWISE, ConvT.DWCONV):
        raise UnsupportedGeometry(f"no pallas kernel for {conv_t.name}")
    pt, pb, pl_, pr = pads
    r0, r1, c0, c1 = sl
    xs = x[r0:r1, c0:c1, :]
    if conv_t == ConvT.DWCONV:
        out = conv2d_shard(xs, w, pads=(pt, pb, pl_, pr), stride=s,
                           depthwise=True)
        return out[:, :, chans[0]:chans[1]]
    wsel = w[:, :, :, chans[0]:chans[1]]
    return conv2d_shard(xs, wsel, pads=(pt, pb, pl_, pr), stride=s)


def _apply_record_b(rec: _SegRec, w, x: jnp.ndarray, backend: str,
                    log: Optional[List[Optional[str]]] = None
                    ) -> jnp.ndarray:
    """Backend dispatch for one record.  Geometry support is static (shapes
    are known at trace time), so the pallas->xla fallback resolves during
    tracing and costs nothing at run time.  Only the geometry checks that
    run before a kernel is built can divert a record to XLA; a kernel the
    compiler refuses fails the run.  ``log`` (pallas backend) receives
    ``None`` per record lowered to Pallas, else the reason it runs on XLA."""
    if backend == "pallas":
        try:
            out = _apply_record_pallas(rec, w, x)
        except UnsupportedGeometry as exc:
            if log is not None:
                log.append(str(exc))
        else:
            if log is not None:
                log.append(None)
            return out
    return _apply_record(rec, w, x)


@functools.lru_cache(maxsize=None)
def _compiled_segment(recs: Tuple[_SegRec, ...], backend: str = "xla"):
    """Jitted program for one (segment-cell signature, backend) pair.
    ``jax.jit`` adds its own shape/dtype guard under this entry, so one
    signature serves every input that shares the geometry."""
    def run(x, ws):
        for rec, w in zip(recs, ws):
            x = _apply_record_b(rec, w, x, backend)
        return x
    return jax.jit(run)


def segment_cache_info():
    """(hits, misses, ...) of the compiled-segment cache — repeated blocks
    and repeated `run_partitioned` calls should mostly hit."""
    return _compiled_segment.cache_info()


def clear_segment_cache() -> None:
    _compiled_segment.cache_clear()


def chain_runs(graph: ModelGraph) -> List[List[int]]:
    """The layer-id runs both executors execute as chains of segments:
    the whole graph for a chain, else each linearized branch minus a
    merge head (merges run in ``merge_tensors``)."""
    if graph.is_chain:
        return [list(range(len(graph)))]
    runs = []
    for br in graph.linearize():
        ids = list(br.ids)
        rest = ids[1:] if len(graph.producer_ids[ids[0]]) >= 2 else ids
        if rest:
            runs.append(rest)
    return runs


def pallas_lowering(graph: ModelGraph, weights, plan: Plan,
                    nodes: int) -> List[Tuple[str, ConvT, Optional[str]]]:
    """``(layer name, conv type, reason)`` for every segment-layer record
    the pallas backend runs for ``plan`` on ``nodes`` nodes, one per
    cell: ``reason`` is None when the record runs on a Pallas kernel (or
    is an ADD/CONCAT channel pass-through), else why it runs on XLA.
    Decided by tracing the records abstractly — nothing is compiled."""
    out: List[Tuple[str, ConvT, Optional[str]]] = []
    for ids in chain_runs(graph):
        layers = [graph.layers[i] for i in ids]
        ws = tuple(weights[i] for i in ids)
        steps = [plan.steps[i] for i in ids]
        for (a, b) in steps_segments(steps):
            for cells in exact_regions(layers[b], steps[a][0], nodes):
                for reg in cells:
                    need, in_rect = backward_chain(layers, a, b, reg)
                    recs = _segment_records(layers, a, b, need, in_rect)
                    (r, c, ch) = in_rect
                    x = jax.ShapeDtypeStruct(
                        (r[1] - r[0], c[1] - c[0], ch[1] - ch[0]),
                        jnp.float32)
                    log: List[Optional[str]] = []

                    def run(x, w, recs=recs, log=log):
                        for rec, wi in zip(recs, w):
                            x = _apply_record_b(rec, wi, x, "pallas", log)
                        return x
                    jax.eval_shape(run, x, ws[a:b + 1])
                    out.extend((layers[li].name, layers[li].conv_t, why)
                               for li, why in zip(range(a, b + 1), log))
    return out


def _run_branch(layers: Sequence[LayerSpec],
                weights: Sequence,
                steps: Sequence[Tuple[Scheme, Mode]],
                x: jnp.ndarray,
                owned: Optional[List[List[Rect]]],
                nodes: int,
                stats: ExecStats,
                jit_segments: bool = True,
                backend: str = "xla"
                ) -> Tuple[jnp.ndarray, List[List[Rect]]]:
    """Execute one chain of layers segment by segment.  ``x`` is the full
    input tensor at the branch entry; ``owned`` is the per-node layout it is
    distributed in (None = initial input, no comm accounting).  Returns the
    full output and its per-node layout at the final T boundary."""
    full = x
    for (a, b) in steps_segments(steps):
        scheme = steps[a][0]
        regs_b = exact_regions(layers[b], scheme, nodes)
        cell_out: List[Tuple[Rect, jnp.ndarray]] = []
        computed = 0
        for n, cells in enumerate(regs_b):
            for reg_b in cells:
                # backward-chain the needed region through the segment
                need, in_rect = backward_chain(layers, a, b, reg_b)
                (in_r, in_c, _) = in_rect
                # communication accounting: elems this node did not hold
                if owned is not None:
                    held = sum(_rect_elems(_rect_isect(in_rect, o))
                               for o in owned[n])
                    stats.bytes_received += DTYPE_BYTES * (
                        _rect_elems(in_rect) - held)
                node_x = full[in_r[0]:in_r[1], in_c[0]:in_c[1], :]
                for li in range(a, b):
                    computed += _rect_elems(need[li])
                if jit_segments:
                    recs = _segment_records(layers, a, b, need, in_rect)
                    node_x = _compiled_segment(recs, backend)(
                        node_x, tuple(weights[a:b + 1]))
                elif backend != "xla":
                    # eager non-XLA path: same per-record dispatch, no jit
                    recs = _segment_records(layers, a, b, need, in_rect)
                    for rec, w in zip(recs, weights[a:b + 1]):
                        node_x = _apply_record_b(rec, w, node_x, backend)
                else:
                    origin = (in_r[0], in_c[0])
                    for li in range(a, b + 1):
                        l = layers[li]
                        node_x = _apply_local(l, weights[li], node_x,
                                              origin, need[li])
                        origin = (need[li][0][0], need[li][1][0])
                cell_out.append((reg_b, node_x))
        # T boundary: reassemble ("synchronize")
        lb = layers[b]
        rebuilt = jnp.zeros((lb.out_h, lb.out_w, lb.out_c), full.dtype)
        for (r, c, ch), shard in cell_out:
            rebuilt = rebuilt.at[r[0]:r[1], c[0]:c[1],
                                 ch[0]:ch[1]].set(shard)
        stats.sync_points += 1
        stats.redundant_elems += float(computed)
        stats.compute_stages += 1
        owned = regs_b
        full = rebuilt
    assert owned is not None, "branch must contain at least one segment"
    return full, owned


def _merge_comm_bytes(l: LayerSpec, prods: Sequence[int],
                      prod_channels: Sequence[int],
                      owned_map: Dict[int, Optional[List[List[Rect]]]],
                      regs: List[List[Rect]]) -> float:
    """Bytes every node must receive to assemble its merge-output regions
    from the producers' shard layouts.  CONCAT maps output-channel windows
    back into each producer's channel range (``prod_channels`` includes the
    graph input's channels, keeping later windows aligned); ADD needs the
    same region of every input."""
    offsets: List[int] = []
    off = 0
    for c in prod_channels:
        offsets.append(off)
        off += c if l.conv_t == ConvT.CONCAT else 0
    total = 0.0
    for n, cells in enumerate(regs):
        for (rows, cols, chans) in cells:
            for j, pid in enumerate(prods):
                if l.conv_t == ConvT.CONCAT:
                    c0 = max(chans[0] - offsets[j], 0)
                    c1 = min(chans[1] - offsets[j], prod_channels[j])
                    if c1 <= c0:
                        continue
                    need: Rect = (rows, cols, (c0, c1))
                else:
                    need = (rows, cols, chans)
                owned = owned_map.get(pid)
                if owned is None:
                    continue   # graph input: pre-distributed, not counted
                held = sum(_rect_elems(_rect_isect(need, o))
                           for o in owned[n])
                total += DTYPE_BYTES * (_rect_elems(need) - held)
    return total


def run_partitioned(graph: ModelGraph, weights, x: jnp.ndarray, plan: Plan,
                    nodes: int,
                    jit_segments: bool = True,
                    backend: str = "xla",
                    executor: str = "local",
                    mesh=None,
                    instrument: bool = False,
                    overlap: bool = True,
                    stage_timeout_s: Optional[float] = None,
                    stage_retries: int = 0,
                    fallback: str = "raise"
                    ) -> Tuple[jnp.ndarray, ExecStats]:
    """Deprecated kwarg-sprawl entry point — use
    :class:`repro.runtime.session.Session` with
    :class:`repro.runtime.session.ExecConfig`.

    Equivalent to ``Session(graph, weights, plan, nodes,
    ExecConfig(backend=..., executor=..., ...), mesh=mesh).run(x)``;
    kept as a thin shim so existing callers keep working, at the cost of
    rebuilding the Session (and, for the mesh executor, re-deriving the
    mesh) on every call."""
    import warnings
    warnings.warn(
        "run_partitioned is deprecated; build a repro.runtime.session."
        "Session with an ExecConfig and call session.run(x)",
        DeprecationWarning, stacklevel=2)
    from repro.runtime.session import ExecConfig, Session
    cfg = ExecConfig(backend=backend, executor=executor,
                     jit_segments=jit_segments, instrument=instrument,
                     overlap=overlap, stage_timeout_s=stage_timeout_s,
                     stage_retries=stage_retries, fallback=fallback)
    return Session(graph, weights, plan, nodes, cfg, mesh=mesh).run(x)


def _run_partitioned_local(graph: ModelGraph, weights, x: jnp.ndarray,
                           plan: Plan, nodes: int,
                           jit_segments: bool = True,
                           backend: str = "xla"
                           ) -> Tuple[jnp.ndarray, ExecStats]:
    """Execute ``plan`` on ``nodes`` simulated devices in-process (the
    ``executor="local"`` path behind :class:`~repro.runtime.session.
    Session`).  ``jit_segments`` routes each segment cell through the
    compiled-program cache (repeated blocks compile once and reuse across
    calls); ``False`` keeps the historical eager path.  ``backend``
    selects the segment-layer lowering: ``"xla"`` (generic
    ``conv_general_dilated``) or ``"pallas"`` (shard kernels with
    automatic per-record XLA fallback); stats accounting is
    backend-independent by construction."""
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    stats = ExecStats()
    if graph.is_chain:
        plan.validate()
        if len(plan) != len(graph):
            raise ValueError("plan/graph length mismatch")
        full, _ = _run_branch(graph.layers, weights, plan.steps, x, None,
                              nodes, stats, jit_segments, backend)
        return full, stats

    plan.validate_for(graph)
    layers = graph.layers
    outs: Dict[int, jnp.ndarray] = {-1: x}
    owned_map: Dict[int, Optional[List[List[Rect]]]] = {-1: None}
    for br in graph.linearize():
        ids = list(br.ids)
        head = ids[0]
        prods = graph.producer_ids[head]
        if len(prods) >= 2:
            l_m = layers[head]
            q = plan.steps[head][0]
            merged = merge_tensors(l_m, [outs[p] for p in prods])
            regs = exact_regions(l_m, q, nodes)
            stats.sync_points += 1
            # the merge layer's T-singleton segment executes inside
            # merge_tensors — still one compute stage of the pipeline
            stats.compute_stages += 1
            stats.bytes_received += _merge_comm_bytes(
                l_m, prods,
                [layers[p].out_c if p >= 0 else layers[0].in_c
                 for p in prods],
                owned_map, regs)
            cur, owned = merged, regs
            rest = ids[1:]
        else:
            src = prods[0]
            cur, owned = outs[src], owned_map[src]
            rest = ids
        if rest:
            ls = [layers[i] for i in rest]
            ws = [weights[i] for i in rest]
            st = [plan.steps[i] for i in rest]
            cur, owned = _run_branch(ls, ws, st, cur, owned, nodes, stats,
                                     jit_segments, backend)
        outs[ids[-1]] = cur
        owned_map[ids[-1]] = owned
    return outs[len(graph) - 1], stats


def _apply_local(l: LayerSpec, w, x_local: jnp.ndarray,
                 origin: Tuple[int, int], out_rect: Rect) -> jnp.ndarray:
    """Compute ``out_rect`` of layer ``l`` from a local input slice whose
    [0,0] corresponds to absolute input coords ``origin``."""
    rows, cols, chans = out_rect
    if l.conv_t == ConvT.FC:
        seg = x_local.reshape(x_local.shape[0], x_local.shape[-1])
        # local rows already correspond to rows (1:1 chain)
        return (seg @ w[:, chans[0]:chans[1]]).reshape(
            x_local.shape[0], 1, chans[1] - chans[0])
    if l.conv_t in (ConvT.ADD, ConvT.CONCAT):
        return x_local[:, :, chans[0]:chans[1]]
    # needed (unclipped) input range for this output region
    nr = in_rows(l, rows, 0)
    nc = in_rows(l, cols, 1)
    pt = max(0, -nr[0])
    pb = max(0, nr[1] - l.in_h)
    pl_ = max(0, -nc[0])
    pr = max(0, nc[1] - l.in_w)
    r0 = max(0, nr[0]) - origin[0]
    r1 = min(l.in_h, nr[1]) - origin[0]
    c0 = max(0, nc[0]) - origin[1]
    c1 = min(l.in_w, nc[1]) - origin[1]
    assert r0 >= 0 and c0 >= 0 and r1 <= x_local.shape[0] \
        and c1 <= x_local.shape[1], (
            "local slice does not cover the needed region", l.name)
    xs = x_local[r0:r1, c0:c1, :]
    if l.conv_t in (ConvT.CONV, ConvT.POINTWISE):
        wsel = w[:, :, :, chans[0]:chans[1]]
        return _conv_region(l, wsel, xs, ((pt, pb), (pl_, pr)))
    out = _conv_region(l, w, xs, ((pt, pb), (pl_, pr)))
    return out[:, :, chans[0]:chans[1]]
