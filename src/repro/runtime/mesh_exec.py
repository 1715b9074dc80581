"""Mesh executor: run a FlexPie plan on a real JAX device mesh.

The local engine (``runtime.engine``) executes every planned node's shard
program sequentially in one process — the pipelining the planner optimizes
for exists only in the analytic ``PipelineCost`` model and the
``cluster.simsched`` discrete-event schedule.  This module makes the plan
physical: each planned node's per-segment shard program is placed on its
own JAX device (CPU CI fakes the devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``), expressed as
``shard_map`` programs over a 1-D ``nodes`` mesh axis so all shards of a
segment execute concurrently.  Host-side slicing becomes collectives:

* **Neighbor halo exchange** — at a T boundary between two segments that
  share an InH/InW scheme, each node's next input rect extends only into
  its immediate neighbors' rows.  The boundary rows travel by
  ``jax.lax.ppermute`` (one shift up, one shift down); the receiving node
  splices them onto its own rows to assemble the halo-extended local
  slice that its compiled segment records consume — the same
  ``_segment_records`` signatures, and therefore the same Pallas shard
  kernels, as the local executor.
* **Gather re-layout** — scheme changes, OutC/2D-grid layouts, fork
  deliveries, CONCAT/ADD merges and the final gather are
  ``jax.lax.all_gather`` + static re-placement (every device rebuilds the
  full boundary tensor, then slices its next region; the per-node slice
  arithmetic lives in a ``lax.switch`` over ``axis_index('nodes')``, so
  one traced program serves all devices while each executes only its own
  branch).

**Double-buffered boundaries** (``overlap=True``, the default): a segment
whose exit boundary is permute-compatible computes its *border strips
first* — the rows its neighbors will need — issues the ``ppermute`` on
them, and only then computes its interior rows.  In the dataflow graph
the exchange depends only on the border compute, so segment *k+1*'s halo
exchange is in flight while segment *k*'s interior compute still runs
(XLA async collectives overlap them on real backends; on the CPU host
platform the schedule is still valid, just serialized).  With
``overlap=False`` every boundary exchange is dispatched as its own sync
stage, giving a 1:1 correspondence with ``cluster.simsched.build_stages``
— that is the mode ``instrument=True`` validation uses, and
:func:`validate_stage_decomposition` checks the measured stage DAG
against the simulator's.

Stats contract: geometry accounting (``sync_points`` / ``bytes_received``
/ ``redundant_elems`` / ``compute_stages``) is computed from the same
backward-chained rects as the local executor and is bit-identical to it;
measured ``stage_times`` / ``wall_s`` are instrumentation-only fields
excluded from ``ExecStats`` equality.

A 1-node plan degenerates to plain jitted programs on the first device —
no ``shard_map``, no collectives.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.graph import LayerSpec, ModelGraph
from repro.core.partition import DTYPE_BYTES, Scheme
from repro.core.plan import Plan, steps_segments
from repro.launch.mesh import make_nodes_mesh
from repro.obs import flight as _obs_flight
from repro.obs import trace as _obs_trace
from repro.runtime.engine import (BACKENDS, ExecStats, Rect, StageTime,
                                  _apply_record_b, _merge_comm_bytes,
                                  _rect_elems, _rect_isect,
                                  _segment_records, backward_chain,
                                  exact_regions, merge_tensors)

AXIS = "nodes"

#: terminal-stage-failure behaviours of ``run_partitioned_mesh``
FALLBACKS = ("raise", "local")


class StageFailure(RuntimeError):
    """Base of the mesh executor's fault exceptions (a dispatched pipeline
    stage did not complete)."""


class StageTimeoutError(StageFailure):
    """A stage exceeded ``stage_timeout_s``.  Timeouts are counted in
    ``ExecStats.timeouts`` but never retried — a wedged collective stays
    wedged, re-dispatching just stacks another stuck module on the pool."""


class StageDispatchError(StageFailure):
    """A stage dispatch raised and exhausted its ``stage_retries``
    re-attempts (each re-attempt is counted in ``ExecStats.retries``)."""


def _timeout_message(label: str, timeout_s: float, nodes: int) -> str:
    return (
        f"mesh stage {label!r} exceeded stage_timeout_s={timeout_s:g}s "
        f"({nodes} plan nodes). Likely causes, most common first: "
        f"(1) CPU host-platform thread-pool starvation — all fake devices "
        f"share one dispatch pool, so threads parked in one stage module's "
        f"collective rendezvous can starve another module's participants "
        f"(the known 'collective_ops_utils ... may be stuck' stall; reduce "
        f"XLA_FLAGS=--xla_force_host_platform_device_count or keep the "
        f"executor's serialized CPU dispatch enabled); "
        f"(2) first-call XLA compilation of a large stage program — warm "
        f"the program cache with one untimed run or raise the timeout; "
        f"(3) a genuinely lost device — pass fallback='local' to degrade "
        f"to the single-process engine instead of raising."
    )


#: compiled stage programs keyed by full static signature (mesh devices,
#: per-node record tuples, shapes, backend) — repeated blocks across a
#: model and repeated ``run_partitioned_mesh`` calls reuse one executable
_PROG_CACHE: Dict[tuple, object] = {}


def mesh_program_cache_info() -> Tuple[int, int]:
    """(entries, -1) — entry count of the mesh stage-program cache."""
    return (len(_PROG_CACHE), -1)


def clear_mesh_program_cache() -> None:
    _PROG_CACHE.clear()


# ---------------------------------------------------------------------------
# axis-generic helpers (InH splits rows, InW splits columns)
# ---------------------------------------------------------------------------

def _slc(x, a: int, b: int, axis: int):
    return x[a:b] if axis == 0 else x[:, a:b]

def _cat(parts, axis: int):
    parts = [p for p in parts if p.shape[axis] > 0]
    if len(parts) == 1:
        return parts[0]
    return jnp.concatenate(parts, axis=axis)

def _pad_dim(x, size: int, axis: int):
    if x.shape[axis] == size:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, size - x.shape[axis])
    return jnp.pad(x, widths)

def _pad3(x, shape3: Tuple[int, int, int]):
    widths = [(0, s - d) for d, s in zip(x.shape, shape3)]
    if all(w == (0, 0) for w in widths):
        return x
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# carried state between pipeline stages
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Full:
    """Boundary tensor replicated on every device."""

    arr: jnp.ndarray


@dataclasses.dataclass
class _Rows:
    """Sharded 1-D spatial layout: node ``n`` holds rows/cols
    ``ranges[n]`` of the boundary tensor (padded to ``pad``), plus the
    halo blocks received from its neighbors for the next segment."""

    block: jnp.ndarray                   # [N, pad, ...] sharded over AXIS
    axis: int                            # 0 = rows (InH), 1 = cols (InW)
    ranges: Tuple[Tuple[int, int], ...]
    up: Optional[jnp.ndarray]            # [N, h_up, ...] sharded
    dn: Optional[jnp.ndarray]            # [N, h_dn, ...]
    halo: Tuple[int, int]


@dataclasses.dataclass
class _Cells:
    """Sharded exact-region layout: node ``n`` owns ``cells[n]`` of the
    boundary tensor, zero-padded into a uniform stack."""

    stack: jnp.ndarray                   # [N, cmax, Rm, Cm, Chm] sharded
    cells: Tuple[Tuple[Rect, ...], ...]
    shape: Tuple[int, int, int]          # full boundary tensor shape


@dataclasses.dataclass(frozen=True)
class _CellProg:
    reg: Rect
    in_rect: Rect
    recs: tuple


@dataclasses.dataclass(frozen=True)
class _RowsPlan:
    """Permute-compatible boundary: per-node owned ranges plus the global
    halo sizes the ppermute exchange must carry."""

    axis: int
    ranges: Tuple[Tuple[int, int], ...]
    h_up: int
    h_dn: int


def _run_recs(recs, ws, x, backend: str):
    for rec, w in zip(recs, ws):
        x = _apply_record_b(rec, w, x, backend)
    return x


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

class _MeshRun:
    def __init__(self, graph: ModelGraph, mesh, nodes: int, backend: str,
                 instrument: bool, overlap: bool, stats: ExecStats,
                 dtype, stage_timeout_s: Optional[float] = None,
                 stage_retries: int = 0,
                 fault_hook: Optional[Callable[[str, str, int],
                                               None]] = None) -> None:
        self.graph = graph
        self.mesh = mesh
        self.n = nodes
        self.backend = backend
        self.instrument = instrument
        self.overlap = overlap
        self.stats = stats
        self.dtype = dtype
        self.stage_timeout_s = stage_timeout_s
        self.stage_retries = stage_retries
        self.fault_hook = fault_hook
        # observability: tracer is cached once (None = tracing off, the
        # zero-overhead default); the flight ring is always on — deque
        # appends never touch numerics, so runs stay bit-identical
        self.tracer = _obs_trace.get_tracer()
        self.flight = _obs_flight.get_flight()
        self.mesh_key = tuple(int(d.id) for d in mesh.devices.flat) \
            if mesh is not None else (0,)
        # The host ("cpu") platform executes dispatched modules on one
        # shared thread pool: with many collective-bearing stage modules
        # in flight, threads parked in one module's collective rendezvous
        # can starve the participants of another (observed as
        # collective_ops_utils "may be stuck" stalls on deep models).
        # Serialize stage dispatches there; on real accelerator backends
        # per-device FIFO launch order makes async dispatch safe and the
        # pipeline stays in flight.
        self.serialize = (
            self.n > 1 and mesh is not None
            and mesh.devices.flat[0].platform == "cpu")

    # -- program cache ----------------------------------------------------

    def _cached(self, key: tuple, build):
        full_key = (self.mesh_key, self.backend, self.n, self.overlap) + key
        fn = _PROG_CACHE.get(full_key)
        if fn is None:
            fn = build()
            _PROG_CACHE[full_key] = fn
        return fn

    def _smap(self, fn, in_specs, out_specs):
        """jit(shard_map(fn)) over the nodes axis; plain jit at N == 1
        (degenerate plans bypass collectives entirely)."""
        if self.n == 1:
            return jax.jit(fn)
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    # -- dispatch + instrumentation ---------------------------------------

    def _dispatch(self, kind: str, label: str, fn, *args):
        """Run one pipeline stage with the fault policy: a stage that
        exceeds ``stage_timeout_s`` raises :class:`StageTimeoutError`
        (counted, never retried — see the class docstring); any other
        dispatch exception is re-attempted up to ``stage_retries`` times
        (each counted) before :class:`StageDispatchError`.  ``fault_hook``
        is a test seam called as ``(kind, label, attempt)`` before every
        attempt — raising from it injects a deterministic fault.

        Every dispatch rides the flight ring; terminal failures dump a
        postmortem artifact (``obs.flight.dump_postmortem`` — a no-op
        unless a postmortem directory is configured)."""
        attempt = 0
        self.flight.record("stage_dispatch", stage_kind=kind,
                           label=label)
        while True:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(kind, label, attempt)
                return self._execute(kind, label, fn, *args)
            except StageTimeoutError:
                self.stats.timeouts += 1
                self.flight.record("stage_timeout", stage_kind=kind,
                                   label=label,
                                   timeout_s=self.stage_timeout_s)
                _obs_flight.dump_postmortem(
                    "stage_timeout",
                    context={"kind": kind, "label": label,
                             "timeout_s": self.stage_timeout_s,
                             "nodes": self.n, "attempt": attempt})
                raise
            except StageFailure as exc:
                self.flight.record("stage_failure", stage_kind=kind,
                                   label=label)
                _obs_flight.dump_postmortem(
                    "stage_failure",
                    context={"kind": kind, "label": label,
                             "nodes": self.n, "attempt": attempt,
                             "error": repr(exc)})
                raise
            except Exception as exc:
                if attempt >= self.stage_retries:
                    self.flight.record("stage_dispatch_error",
                                       stage_kind=kind,
                                       label=label, attempts=attempt + 1)
                    _obs_flight.dump_postmortem(
                        "stage_dispatch_error",
                        context={"kind": kind, "label": label,
                                 "nodes": self.n,
                                 "attempts": attempt + 1,
                                 "stage_retries": self.stage_retries,
                                 "error": repr(exc)})
                    raise StageDispatchError(
                        f"mesh stage {label!r} failed after "
                        f"{attempt + 1} attempt(s) "
                        f"(stage_retries={self.stage_retries}): "
                        f"{exc!r}") from exc
                self.stats.retries += 1
                self.flight.record("stage_retry", stage_kind=kind,
                                   label=label,
                                   attempt=attempt)
                if self.tracer is not None:
                    self.tracer.instant(_obs_trace.CONTROL_TRACK,
                                        f"retry:{label}", cat="retry",
                                        attempt=attempt)
                attempt += 1

    def _watched(self, label: str, body):
        """Run ``body`` under the per-stage watchdog: a daemon worker
        thread does the (blocking) JAX work while this thread joins with
        ``stage_timeout_s``.  A stuck collective cannot be interrupted —
        on timeout the worker is abandoned (daemonized, so it cannot hang
        interpreter exit) and :class:`StageTimeoutError` surfaces."""
        timeout = self.stage_timeout_s
        if timeout is None:
            return body()
        box: Dict[str, object] = {}

        def worker():
            try:
                box["out"] = body()
            except BaseException as exc:    # noqa: BLE001 — re-raised
                box["err"] = exc

        th = threading.Thread(target=worker, daemon=True,
                              name=f"mesh-stage:{label}")
        th.start()
        th.join(timeout)
        if th.is_alive():
            raise StageTimeoutError(
                _timeout_message(label, timeout, self.n))
        if "err" in box:
            raise box["err"]
        return box["out"]

    def _execute(self, kind: str, label: str, fn, *args):
        timed = self.stage_timeout_s is not None
        if not self.instrument:
            def body():
                out = fn(*args)
                # async dispatch returns before the module runs — with a
                # watchdog armed the stage must block inside it or the
                # timeout would never observe the execution
                if self.serialize or timed:
                    jax.block_until_ready(out)
                return out
            return self._watched(label, body) if timed else body()

        def body():
            tr = self.tracer
            t0 = time.perf_counter()
            t0_us = tr.now_us() if tr is not None else 0.0
            out = fn(*args)
            dev_done: Tuple[float, ...] = ()
            lead = out[0] if isinstance(out, (tuple, list)) else out
            if kind == "compute" and self.n > 1 \
                    and hasattr(lead, "addressable_shards"):
                shards = sorted(lead.addressable_shards,
                                key=lambda s: s.index[0].start or 0)
                done = []
                for sh in shards:
                    sh.data.block_until_ready()
                    done.append(time.perf_counter() - t0)
                dev_done = tuple(done)
            jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            self.stats.stage_times.append(
                StageTime(kind, label, wall, dev_done))
            if tr is not None:
                # one control-track stage span per StageTime row (the
                # 1:1 contract), plus a per-device span bounded by each
                # shard's completion time
                tr.add_complete(_obs_trace.CONTROL_TRACK, label, t0_us,
                                wall * 1e6, cat=_obs_trace.STAGE_CAT,
                                args={"kind": kind})
                for d, done_s in enumerate(dev_done):
                    tr.add_complete(_obs_trace.device_track(d), label,
                                    t0_us, done_s * 1e6, cat="device",
                                    args={"kind": kind})
            return out
        return self._watched(label, body) if timed else body()

    # -- boundary classification ------------------------------------------

    def _permute_plan(self, scheme: Scheme, regs_b, layers, a2: int,
                      b2: int, q2: Scheme) -> Optional[_RowsPlan]:
        """Neighbor-exchange eligibility of the boundary into segment
        ``[a2..b2]``: same 1-D spatial scheme on both sides and every
        node's next input rect contained in its own + immediate
        neighbors' ranges (equivalently: every range can donate the
        global halo strips)."""
        if self.n == 1 or scheme != q2 \
                or q2 not in (Scheme.INH, Scheme.INW):
            return None
        axis = 0 if q2 == Scheme.INH else 1
        ranges = tuple(cells[0][axis] for cells in regs_b)
        next_regs = exact_regions(layers[b2], q2, self.n)
        h_up = h_dn = 0
        for nd in range(self.n):
            _, in_rect = backward_chain(layers, a2, b2, next_regs[nd][0])
            i0, i1 = in_rect[axis]
            o0, o1 = ranges[nd]
            h_up = max(h_up, o0 - i0)
            h_dn = max(h_dn, i1 - o1)
        h_up, h_dn = max(h_up, 0), max(h_dn, 0)
        if min(r1 - r0 for r0, r1 in ranges) < max(h_up + h_dn, 1):
            return None
        return _RowsPlan(axis, ranges, h_up, h_dn)

    # -- entry assembly (inside a switch branch) --------------------------

    def _entry_slice(self, state_kind: str, entry_meta, nd: int,
                     in_rect: Rect, full, x_rows, u, d):
        """The halo-extended local input slice of node ``nd``'s segment
        program — from the replicated full tensor (gather path) or from
        own rows + received ppermute halos (permute path)."""
        if state_kind == "full":
            (r, c, _) = in_rect
            return full[r[0]:r[1], c[0]:c[1], :]
        axis, ranges, h_up, h_dn = entry_meta
        o0, o1 = ranges[nd]
        i0, i1 = in_rect[axis]
        ext = _cat([u, _slc(x_rows, 0, o1 - o0, axis), d], axis)
        return _slc(ext, i0 - (o0 - h_up), i1 - (o0 - h_up), axis)

    # -- compute stage: segment -> cells ----------------------------------

    def _seg_to_cells(self, label: str, weights_seg, state,
                      cellprogs: List[List[_CellProg]],
                      out_shape: Tuple[int, int, int]) -> _Cells:
        n = self.n
        cmax = max(len(ps) for ps in cellprogs)
        rm = cm = chm = 0
        for ps in cellprogs:
            for cp in ps:
                (r, c, ch) = cp.reg
                rm = max(rm, r[1] - r[0])
                cm = max(cm, c[1] - c[0])
                chm = max(chm, ch[1] - ch[0])
        pad_shape = (rm, cm, chm)
        state_kind, entry_meta, args = self._entry_args(state)
        backend = self.backend
        dtype = self.dtype

        def branch(nd):
            progs = cellprogs[nd]

            def run(full, x_rows, u, d, ws):
                outs = []
                for cp in progs:
                    xs = self._entry_slice(state_kind, entry_meta, nd,
                                           cp.in_rect, full, x_rows, u, d)
                    y = _run_recs(cp.recs, ws, xs, backend)
                    outs.append(_pad3(y, pad_shape))
                while len(outs) < cmax:
                    outs.append(jnp.zeros(pad_shape, dtype))
                return jnp.stack(outs)
            return run

        sig = ("seg2cells", state_kind, entry_meta, pad_shape, cmax,
               tuple(tuple(ps) for ps in cellprogs))

        def build():
            branches = [branch(nd) for nd in range(n)]
            if n == 1:
                def fn1(full, x_rows, u, d, ws):
                    return branches[0](full, x_rows, u, d, ws)[None]
                return self._smap(fn1, None, None)

            def fn(full, x_rows, u, d, ws):
                xr = None if x_rows is None else x_rows[0]
                uu = None if u is None else u[0]
                dd = None if d is None else d[0]
                idx = jax.lax.axis_index(AXIS)
                out = jax.lax.switch(
                    idx, [lambda f, xr, uu, dd, w, _br=br:
                          _br(f, xr, uu, dd, w) for br in branches],
                    full, xr, uu, dd, ws)
                return out[None]
            in_specs = (P(), P(AXIS), P(AXIS), P(AXIS), P())
            return self._smap(fn, in_specs, P(AXIS))
        prog = self._cached(sig, build)
        stack = self._dispatch("compute", label, prog, *args, weights_seg)
        cells = tuple(tuple(cp.reg for cp in ps) for ps in cellprogs)
        return _Cells(stack=stack, cells=cells, shape=out_shape)

    # -- compute stage: segment -> rows (+ overlapped halo exchange) ------

    def _seg_to_rows(self, label: str, bound_label: str, layers, a: int,
                     b: int, weights_seg, state,
                     cellprogs: List[List[_CellProg]],
                     rp: _RowsPlan) -> _Rows:
        n = self.n
        axis = rp.axis
        pad_out = max(r1 - r0 for r0, r1 in rp.ranges)
        state_kind, entry_meta, args = self._entry_args(state)
        backend = self.backend
        dtype = self.dtype
        lb = layers[b]
        other = (lb.out_w if axis == 0 else lb.out_h)
        strip_shape = ((rp.h_dn, other, lb.out_c) if axis == 0
                       else (other, rp.h_dn, lb.out_c))

        def strip_progs(nd):
            """(top, interior, bottom) record programs of node nd's region
            — border strips first, so the ppermute issued on them
            overlaps the interior compute (the double buffer)."""
            cp = cellprogs[nd][0]
            (r, c, ch) = cp.reg
            r0, r1 = cp.reg[axis]
            t1 = min(r0 + rp.h_dn, r1)
            b0 = max(r1 - rp.h_up, t1)
            out: List[Tuple[tuple, int]] = []
            for s0, s1 in ((r0, t1), (t1, b0), (b0, r1)):
                if s1 <= s0:
                    out.append((None, 0))
                    continue
                reg = tuple((s0, s1) if i == axis else cp.reg[i]
                            for i in range(3))
                need, _ = backward_chain(layers, a, b, reg)  # type: ignore
                out.append((_segment_records(layers, a, b, need,
                                             cp.in_rect), s1 - s0))
            return out

        use_overlap = self.overlap and (rp.h_up > 0 or rp.h_dn > 0)

        def branch(nd):
            cp = cellprogs[nd][0]
            strips = strip_progs(nd) if use_overlap else None

            def run(full, x_rows, u, d, ws):
                xs = self._entry_slice(state_kind, entry_meta, nd,
                                       cp.in_rect, full, x_rows, u, d)
                if strips is None:
                    y = _run_recs(cp.recs, ws, xs, backend)
                    top = _slc(y, 0, rp.h_dn, axis)
                    bot = _slc(y, y.shape[axis] - rp.h_up,
                               y.shape[axis], axis)
                    return (_pad_dim(y, pad_out, axis), top, bot)
                parts = []
                for recs, span in strips:
                    if recs is None:
                        sh = list(strip_shape)
                        sh[axis] = 0
                        parts.append(jnp.zeros(tuple(sh), dtype))
                    else:
                        parts.append(_run_recs(recs, ws, xs, backend))
                top, interior, bot = parts
                # sends are the full-height border strips (padded with
                # interior rows when a strip spans less than the halo)
                y = _cat([top, interior, bot], axis)
                send_up = _slc(y, 0, rp.h_dn, axis)
                send_dn = _slc(y, y.shape[axis] - rp.h_up,
                               y.shape[axis], axis)
                return (_pad_dim(y, pad_out, axis), send_up, send_dn)
            return run

        sig = ("seg2rows", state_kind, entry_meta, axis, pad_out,
               rp.ranges, rp.h_up, rp.h_dn, use_overlap,
               tuple(cellprogs[nd][0] for nd in range(n)))

        def build():
            branches = [branch(nd) for nd in range(n)]
            perm_dn = [(i, i + 1) for i in range(n - 1)]
            perm_up = [(i + 1, i) for i in range(n)[:-1]]

            def fn(full, x_rows, u, d, ws):
                xr = None if x_rows is None else x_rows[0]
                uu = None if u is None else u[0]
                dd = None if d is None else d[0]
                idx = jax.lax.axis_index(AXIS)
                y, send_up, send_dn = jax.lax.switch(
                    idx, [lambda f, xr, uu, dd, w, _br=br:
                          _br(f, xr, uu, dd, w) for br in branches],
                    full, xr, uu, dd, ws)
                if not use_overlap:
                    return (y[None],)
                up_recv = (jax.lax.ppermute(send_dn, AXIS, perm_dn)
                           if rp.h_up > 0 else send_dn[0:0] if axis == 0
                           else send_dn)
                dn_recv = (jax.lax.ppermute(send_up, AXIS, perm_up)
                           if rp.h_dn > 0 else send_up)
                return (y[None], up_recv[None], dn_recv[None])
            in_specs = (P(), P(AXIS), P(AXIS), P(AXIS), P())
            n_out = 3 if use_overlap else 1
            return self._smap(fn, in_specs, tuple([P(AXIS)] * n_out))
        prog = self._cached(sig, build)
        out = self._dispatch("compute", label, prog, *args, weights_seg)
        if use_overlap:
            block, up, dn = out
            return _Rows(block, axis, rp.ranges, up, dn,
                         (rp.h_up, rp.h_dn))
        block = out[0]
        # non-overlap mode: the exchange is its own sync stage, 1:1 with
        # the simulator's boundary stage
        up, dn = self._halo_sync_stage(bound_label, block, rp)
        return _Rows(block, axis, rp.ranges, up, dn, (rp.h_up, rp.h_dn))

    def _halo_sync_stage(self, label: str, block, rp: _RowsPlan):
        n = self.n
        axis = rp.axis
        pad = block.shape[1 + 0] if axis == 0 else block.shape[2]
        sig = ("halo_sync", axis, rp.ranges, rp.h_up, rp.h_dn,
               tuple(block.shape))

        def build():
            perm_dn = [(i, i + 1) for i in range(n - 1)]
            perm_up = [(i + 1, i) for i in range(n - 1)]

            def sends(nd):
                rn = rp.ranges[nd][1] - rp.ranges[nd][0]

                def run(x):
                    return (_slc(x, 0, rp.h_dn, axis),
                            _slc(x, rn - rp.h_up, rn, axis))
                return run

            def fn(blk):
                x = blk[0]
                idx = jax.lax.axis_index(AXIS)
                send_up, send_dn = jax.lax.switch(
                    idx, [lambda xx, _s=sends(nd): _s(xx)
                          for nd in range(n)], x)
                up_recv = (jax.lax.ppermute(send_dn, AXIS, perm_dn)
                           if rp.h_up > 0 else send_dn)
                dn_recv = (jax.lax.ppermute(send_up, AXIS, perm_up)
                           if rp.h_dn > 0 else send_up)
                return up_recv[None], dn_recv[None]
            return self._smap(fn, (P(AXIS),), (P(AXIS), P(AXIS)))
        del pad
        prog = self._cached(sig, build)
        return self._dispatch("sync", label, prog, block)

    # -- sync stage: cells -> replicated full -----------------------------

    def _gather_stage(self, label: str, state: _Cells) -> _Full:
        n = self.n
        cells = state.cells
        shape = state.shape
        dtype = self.dtype
        sig = ("gather", cells, shape, tuple(state.stack.shape))

        def build():
            def rebuild(allc):
                full = jnp.zeros(shape, dtype)
                for nd in range(n):
                    for j, (r, c, ch) in enumerate(cells[nd]):
                        dr, dc, dch = (r[1] - r[0], c[1] - c[0],
                                       ch[1] - ch[0])
                        if dr <= 0 or dc <= 0 or dch <= 0:
                            continue
                        full = full.at[r[0]:r[1], c[0]:c[1],
                                       ch[0]:ch[1]].set(
                            allc[nd, j, :dr, :dc, :dch])
                return full
            if n == 1:
                return jax.jit(rebuild)

            def fn(stack):
                return rebuild(jax.lax.all_gather(stack[0], AXIS))
            return self._smap(fn, (P(AXIS),), P())
        prog = self._cached(sig, build)
        return _Full(self._dispatch("sync", label, prog, state.stack))

    # -- merge stages ------------------------------------------------------

    def _merge_stages(self, l_m: LayerSpec, prods: Sequence[int],
                      outs: Dict[int, object], x_full) -> _Full:
        """One sync stage gathering every producer's shards (the
        simulator's single per-merge delivery stage) followed by the merge
        layer's own singleton compute stage."""
        n = self.n
        shapes = []
        stacks = []
        metas = []
        for pid in prods:
            if pid == -1:
                metas.append(None)
                shapes.append(tuple(x_full.shape))
            else:
                st = outs[pid]
                assert isinstance(st, _Cells)
                metas.append((st.cells, st.shape))
                shapes.append(st.shape)
                stacks.append(st.stack)
        dtype = self.dtype
        sig = ("merge", tuple(metas), tuple(shapes))

        def build():
            def rebuild(meta, allc):
                cells, shape = meta
                full = jnp.zeros(shape, dtype)
                for nd in range(n):
                    for j, (r, c, ch) in enumerate(cells[nd]):
                        dr, dc, dch = (r[1] - r[0], c[1] - c[0],
                                       ch[1] - ch[0])
                        if dr <= 0 or dc <= 0 or dch <= 0:
                            continue
                        full = full.at[r[0]:r[1], c[0]:c[1],
                                       ch[0]:ch[1]].set(
                            allc[nd, j, :dr, :dc, :dch])
                return full

            def core(x_rep, stks):
                fulls = []
                it = iter(stks)
                for meta in metas:
                    if meta is None:
                        fulls.append(x_rep)
                    else:
                        s = next(it)
                        allc = (s[0] if n == 1
                                else jax.lax.all_gather(s[0], AXIS))
                        if n == 1:
                            allc = s[0] if s.ndim == 5 else s
                        fulls.append(rebuild(meta, allc))
                return tuple(fulls)
            if n == 1:
                def fn1(x_rep, stks):
                    fulls = []
                    it = iter(stks)
                    for meta in metas:
                        if meta is None:
                            fulls.append(x_rep)
                        else:
                            fulls.append(rebuild(meta, next(it)))
                    return tuple(fulls)
                return jax.jit(fn1)

            def fn(x_rep, stks):
                return core(x_rep, stks)
            return self._smap(fn, (P(), P(AXIS)),
                              tuple([P()] * len(metas)))
        prog = self._cached(sig, build)
        fulls = self._dispatch("sync", f"merge->{l_m.name}", prog,
                               x_full, tuple(stacks))

        msig = ("merge_apply", l_m.conv_t, tuple(shapes))

        def mbuild():
            def fn(fulls_in):
                return merge_tensors(l_m, list(fulls_in))
            return jax.jit(fn)
        mprog = self._cached(msig, mbuild)
        merged = self._dispatch(
            "compute", f"seg[{l_m.name}..{l_m.name}]", mprog, fulls)
        return _Full(merged)

    # -- plumbing ----------------------------------------------------------

    def _entry_args(self, state):
        """(state_kind, static entry meta, traced args) of a compute
        stage.  Traced args are always the 4-tuple (full, rows, up, dn)
        with the unused ones None, so every stage shares one signature."""
        if isinstance(state, _Full):
            return "full", None, (state.arr, None, None, None)
        assert isinstance(state, _Rows)
        meta = (state.axis, state.ranges) + state.halo
        return "rows", meta, (None, state.block, state.up, state.dn)

    # -- branch execution --------------------------------------------------

    def run_branch(self, layers: Sequence[LayerSpec], weights,
                   steps, state, owned):
        segs = steps_segments(list(steps))
        regs_b = None
        for si, (a, b) in enumerate(segs):
            scheme = steps[a][0]
            lb = layers[b]
            regs_b = exact_regions(lb, scheme, self.n)
            cellprogs: List[List[_CellProg]] = []
            computed = 0
            for nd, cells in enumerate(regs_b):
                ps = []
                for reg in cells:
                    need, in_rect = backward_chain(layers, a, b, reg)
                    if owned is not None:
                        held = sum(_rect_elems(_rect_isect(in_rect, o))
                                   for o in owned[nd])
                        self.stats.bytes_received += DTYPE_BYTES * (
                            _rect_elems(in_rect) - held)
                    for li in range(a, b):
                        computed += _rect_elems(need[li])
                    ps.append(_CellProg(
                        reg, in_rect,
                        _segment_records(layers, a, b, need, in_rect)))
                cellprogs.append(ps)
            self.stats.sync_points += 1
            self.stats.redundant_elems += float(computed)
            self.stats.compute_stages += 1
            label = f"seg[{layers[a].name}..{layers[b].name}]"

            rows_plan = None
            if si + 1 < len(segs):
                a2, b2 = segs[si + 1]
                rows_plan = self._permute_plan(scheme, regs_b, layers,
                                               a2, b2, steps[a2][0])
            ws = tuple(weights[a:b + 1])
            out_shape = (lb.out_h, lb.out_w, lb.out_c)
            if rows_plan is None:
                state = self._seg_to_cells(label, ws, state, cellprogs,
                                           out_shape)
                if si + 1 < len(segs):
                    state = self._gather_stage(f"bound@{lb.name}", state)
            else:
                state = self._seg_to_rows(label, f"bound@{lb.name}",
                                          layers, a, b, ws, state,
                                          cellprogs, rows_plan)
            owned = regs_b
        assert regs_b is not None, "branch must contain >= 1 segment"
        return state, owned


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def _run_degraded(graph: ModelGraph, weights, x, plan: Plan, nodes: int,
                  backend: str, stats: ExecStats
                  ) -> Tuple[jnp.ndarray, ExecStats]:
    """Degraded single-process fallback: execute the plan's shard
    programs host-side (``runtime.engine`` local executor — no devices
    needed) and carry the mesh run's failure counters over so
    ``ExecStats.failure_count`` (and through it
    ``MeasuredOccupancy.failures``) records the degradation."""
    from repro.runtime import engine as _engine
    _obs_flight.get_flight().record("fallback_local",
                                    graph=graph.name, nodes=nodes)
    out, local_stats = _engine._run_partitioned_local(
        graph, weights, x, plan, nodes, backend=backend)
    local_stats.retries = stats.retries
    local_stats.timeouts = stats.timeouts
    local_stats.fallbacks = stats.fallbacks + 1
    return out, local_stats


def run_partitioned_mesh(graph: ModelGraph, weights, x: jnp.ndarray,
                         plan: Plan, nodes: int, *,
                         backend: str = "xla", mesh=None,
                         instrument: bool = False,
                         overlap: bool = True,
                         stage_timeout_s: Optional[float] = None,
                         stage_retries: int = 0,
                         fallback: str = "raise",
                         fault_hook: Optional[Callable[[str, str, int],
                                                       None]] = None
                         ) -> Tuple[jnp.ndarray, ExecStats]:
    """Execute ``plan`` on a real JAX device mesh — one device per plan
    node.  See the module docstring for the stage/collective model.
    Returns the reassembled full output (replicated) and ``ExecStats``
    whose geometry accounting equals the local executor's; with
    ``instrument=True`` the stats additionally carry measured per-stage
    wall times (run twice and read the second run's stats — the first
    call pays compilation).

    Fault handling: ``stage_timeout_s`` arms a per-stage watchdog (the
    timeout covers first-call compilation — warm the program cache or
    budget for it); ``stage_retries`` bounds re-dispatches of a failed
    stage; ``fallback="local"`` degrades to the single-process engine
    instead of raising when the backing platform has fewer devices than
    the plan needs (mesh shrink) or a stage fails terminally.
    ``fault_hook(kind, label, attempt)`` is called before every stage
    attempt — a test seam for deterministic fault injection.
    ``ExecStats.retries/timeouts/fallbacks`` record what happened."""
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")
    if nodes < 1:
        raise ValueError(f"nodes must be >= 1, got {nodes}")
    if fallback not in FALLBACKS:
        raise ValueError(f"fallback {fallback!r} not in {FALLBACKS}")
    if stage_retries < 0:
        raise ValueError(f"stage_retries must be >= 0, got {stage_retries}")
    if stage_timeout_s is not None and stage_timeout_s <= 0:
        raise ValueError(
            f"stage_timeout_s must be > 0, got {stage_timeout_s}")
    stats = ExecStats()
    if mesh is None and nodes > 1 and fallback == "local" \
            and len(jax.devices()) < nodes:
        # mesh shrink: the plan wants more devices than the platform has
        # left — degrade instead of failing make_nodes_mesh
        return _run_degraded(graph, weights, x, plan, nodes, backend,
                             stats)
    if mesh is None:
        mesh = make_nodes_mesh(nodes) if nodes > 1 else None
    if mesh is not None:
        if AXIS not in mesh.shape or mesh.shape[AXIS] != nodes or \
                len(mesh.shape) != 1:
            raise ValueError(
                f"mesh must be 1-D over axis {AXIS!r} with size {nodes}, "
                f"got {dict(mesh.shape)}")
    run = _MeshRun(graph, mesh, nodes, backend, instrument, overlap,
                   stats, x.dtype, stage_timeout_s, stage_retries,
                   fault_hook)
    try:
        return _mesh_body(run, graph, weights, x, plan, nodes, stats)
    except StageFailure:
        if fallback != "local":
            raise
        return _run_degraded(graph, weights, x, plan, nodes, backend,
                             stats)


def _mesh_body(run: _MeshRun, graph: ModelGraph, weights, x, plan: Plan,
               nodes: int, stats: ExecStats
               ) -> Tuple[jnp.ndarray, ExecStats]:
    t0 = time.perf_counter()

    if graph.is_chain:
        plan.validate()
        if len(plan) != len(graph):
            raise ValueError("plan/graph length mismatch")
        state, _ = run.run_branch(graph.layers, weights, plan.steps,
                                  _Full(x), None)
        out = run._gather_stage("gather", state).arr
        jax.block_until_ready(out)
        stats.wall_s = time.perf_counter() - t0
        return out, stats

    plan.validate_for(graph)
    layers = graph.layers
    outs: Dict[int, object] = {}
    owned_map: Dict[int, Optional[List[List[Rect]]]] = {-1: None}
    final = None
    for br in graph.linearize():
        ids = list(br.ids)
        head = ids[0]
        prods = graph.producer_ids[head]
        if len(prods) >= 2:
            l_m = layers[head]
            q = plan.steps[head][0]
            regs = exact_regions(l_m, q, nodes)
            stats.sync_points += 1
            stats.compute_stages += 1
            stats.bytes_received += _merge_comm_bytes(
                l_m, prods,
                [layers[p].out_c if p >= 0 else layers[0].in_c
                 for p in prods],
                owned_map, regs)
            cur = run._merge_stages(l_m, prods, outs, x)
            owned = regs
            rest = ids[1:]
        else:
            src = prods[0]
            if src == -1:
                cur, owned = _Full(x), None
            else:
                tail = outs[src]
                assert isinstance(tail, _Cells)
                cur = run._gather_stage(f"fork->{layers[head].name}",
                                        tail)
                owned = owned_map[src]
            rest = ids
        if rest:
            ls = [layers[i] for i in rest]
            ws = [weights[i] for i in rest]
            st = [plan.steps[i] for i in rest]
            cur, owned = run.run_branch(ls, ws, st, cur, owned)
        if isinstance(cur, _Full):
            # merge-only branch (no trailing layers): keep replicated;
            # re-shard into the merge layout for downstream consumers
            cur = _full_to_cells(run, cur, owned,
                                 (layers[ids[-1]].out_h,
                                  layers[ids[-1]].out_w,
                                  layers[ids[-1]].out_c))
        elif isinstance(cur, _Rows):
            raise AssertionError("branch tails always exit as cells")
        outs[ids[-1]] = cur
        owned_map[ids[-1]] = owned
        if not graph.consumer_ids[ids[-1]]:
            final = run._gather_stage("gather", cur)
    assert final is not None
    out = final.arr
    jax.block_until_ready(out)
    stats.wall_s = time.perf_counter() - t0
    return out, stats


def _full_to_cells(run: _MeshRun, state: _Full, owned,
                   shape: Tuple[int, int, int]) -> _Cells:
    """Re-shard a replicated tensor into its owned layout (merge-only
    branches: the merged tensor is replicated but downstream consumers
    expect the branch tail in shard form).  Pure slicing — no collective,
    each device takes its own cells."""
    n = run.n
    cells = tuple(tuple(c for c in owned[nd]) for nd in range(n))
    rm = cm = chm = 0
    for ps in cells:
        for (r, c, ch) in ps:
            rm = max(rm, r[1] - r[0])
            cm = max(cm, c[1] - c[0])
            chm = max(chm, ch[1] - ch[0])
    cmax = max(len(ps) for ps in cells)
    pad_shape = (rm, cm, chm)
    dtype = run.dtype
    sig = ("reshard", cells, pad_shape, cmax, shape)

    def build():
        def branch(nd):
            def f(full):
                outs = [_pad3(full[r[0]:r[1], c[0]:c[1], ch[0]:ch[1]],
                              pad_shape)
                        for (r, c, ch) in cells[nd]]
                while len(outs) < cmax:
                    outs.append(jnp.zeros(pad_shape, dtype))
                return jnp.stack(outs)
            return f
        branches = [branch(nd) for nd in range(n)]
        if n == 1:
            return jax.jit(lambda full: branches[0](full)[None])

        def fn(full):
            idx = jax.lax.axis_index(AXIS)
            return jax.lax.switch(idx, branches, full)[None]
        return run._smap(fn, (P(),), P(AXIS))
    prog = run._cached(sig, build)
    stack = run._dispatch("sync", "reshard", prog, state.arr)
    return _Cells(stack=stack, cells=cells, shape=shape)


# ---------------------------------------------------------------------------
# stage-decomposition validation against the simulator
# ---------------------------------------------------------------------------

def validate_stage_decomposition(stats: ExecStats, stages) -> dict:
    """Compare the measured stage DAG (mesh executor with
    ``instrument=True, overlap=False``) against
    ``cluster.simsched.build_stages``: the (kind, label) multisets must
    match 1:1 (the PR 4 stage-decomposition contract made physical);
    per-stage durations are paired up for inspection but never asserted
    here — CPU host devices share cores, so wall times are advisory
    (the bench records them with a documented noise tolerance).

    Two documented physical-vs-model equivalences are applied before
    comparing:

    * ``reshard`` stages (merge-only branch re-sharding, a pure local
      slice) are ignored — the simulator has no counterpart because
      they move no bytes;
    * a sim ``bound@X`` where ``X`` is a merge layer is *subsumed* by
      the measured ``merge->X`` stage — the mesh merge gather leaves the
      merged tensor replicated on every device, so the simulator's
      post-merge distribution boundary has no separate physical stage
      (its bytes already traveled in the ``all_gather``).  Subsumed
      stages are reported in ``subsumed``, not ``missing``."""
    from collections import Counter
    meas = Counter((s.kind, s.label) for s in stats.stage_times
                   if s.label != "reshard")
    sim = Counter((s.kind, s.label) for s in stages)
    merge_names = {s.label[len("merge->"):] for s in stages
                   if s.kind == "sync" and s.label.startswith("merge->")}
    subsumed = []
    for name in merge_names:
        key = ("sync", f"bound@{name}")
        k = sim[key] - meas[key]
        if k > 0:
            sim[key] -= k
            subsumed.extend([key] * k)
    missing = sorted((sim - meas).elements())
    extra = sorted((meas - sim).elements())
    per_stage = []
    meas_by = {}
    for s in stats.stage_times:
        meas_by.setdefault((s.kind, s.label), []).append(s.wall_s)
    for s in stages:
        walls = meas_by.get((s.kind, s.label), [])
        per_stage.append({
            "kind": s.kind, "label": s.label,
            "sim_s": max(s.durations) if s.durations else 0.0,
            "measured_s": walls.pop(0) if walls else None,
        })
    return {"structure_match": not missing and not extra,
            "missing": missing, "extra": extra,
            "subsumed": sorted(subsumed), "stages": per_stage}
