"""Consolidated execution API: :class:`ExecConfig` + :class:`Session`.

``run_partitioned`` grew ten orthogonal keyword arguments (backend,
executor, mesh, instrumentation, overlap, jit caching, fault policy) that
every caller had to re-thread on every call — untenable for decode loops
that execute one plan hundreds of times.  The consolidation splits the
sprawl into its two actual lifetimes:

* :class:`ExecConfig` — frozen, hashable *policy*: which backend/executor,
  how to instrument, how to fail.  Build it once, share it anywhere.
* :class:`Session` — *bound state*: one (graph, weights, plan, nodes)
  binding plus the device mesh and compiled-program reuse across ``run``
  calls.  Step programs are cached process-wide keyed by segment geometry
  (``engine._compiled_segment``) and mesh program signature
  (``mesh_exec._PROG_CACHE``), so a Session's second ``run`` skips
  retracing entirely; the Session additionally pins the mesh object so
  repeated mesh runs don't rebuild device layouts, and places the
  weights on every device of that mesh once.

The mesh executor runs a request on one of two paths, chosen by the
policy alone (no field of its own): a policy with per-stage semantics —
``instrument``, ``stage_timeout_s``, ``stage_retries > 0`` or a
``fault_hook`` — dispatches each pipeline stage as its own program (the
staged path); every other policy launches the whole request as one
jitted program traced from the same stages (the plan path), one launch
per request once warm.  ``runtime.mesh_exec`` describes both and the
``mesh.request`` span's ``path`` arg that names the one taken.

``run_partitioned(**kwargs)`` survives as a thin back-compat shim over
``Session`` and warns ``DeprecationWarning``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ExecConfig", "Session"]

BACKENDS = ("xla", "pallas")
EXECUTORS = ("local", "mesh")
FALLBACKS = ("raise", "local")


@dataclasses.dataclass(frozen=True)
class ExecConfig:
    """Execution policy — everything about *how* to run that is not the
    model, the plan, or the data.

    Fields mirror the historical ``run_partitioned`` kwargs:

    * ``backend``: segment lowering, ``"xla"`` or ``"pallas"`` (shard
      kernels with per-record XLA fallback).
    * ``executor``: ``"local"`` single-process reference executor or
      ``"mesh"`` (one JAX device per planned node, collective exchanges).
    * ``jit_segments``: route local-executor segments through the
      compiled-program cache (mesh is always compiled).
    * ``instrument``: record measured per-stage times into ``ExecStats``.
    * ``overlap``: fuse halo exchanges into the consuming compute stage
      (mesh executor).
    * ``stage_timeout_s`` / ``stage_retries`` / ``fallback``: mesh fault
      policy (watchdog, bounded dispatch retries, degrade-to-local).
    """

    backend: str = "xla"
    executor: str = "local"
    jit_segments: bool = True
    instrument: bool = False
    overlap: bool = True
    stage_timeout_s: Optional[float] = None
    stage_retries: int = 0
    fallback: str = "raise"

    def __post_init__(self) -> None:
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        if self.executor not in EXECUTORS:
            raise ValueError(f"executor {self.executor!r} not in "
                             f"{EXECUTORS}")
        if self.fallback not in FALLBACKS:
            raise ValueError(f"fallback {self.fallback!r} not in "
                             f"{FALLBACKS}")
        if self.stage_retries < 0:
            raise ValueError(f"stage_retries must be >= 0, got "
                             f"{self.stage_retries}")
        if self.stage_timeout_s is not None and self.stage_timeout_s <= 0:
            raise ValueError(f"stage_timeout_s must be positive, got "
                             f"{self.stage_timeout_s}")


class Session:
    """One plan bound to one executor, reusable across many inputs.

    ``Session(graph, weights, plan, nodes, config).run(x)`` replaces
    ``run_partitioned(graph, weights, x, plan, nodes, **ten_kwargs)``.
    The Session validates the plan/config once, builds (or adopts) the
    device mesh once, and leans on the process-wide compiled-program
    caches so repeated ``run`` calls — a decode loop, a benchmark's warm
    iterations — skip retracing.

    ``mesh`` optionally passes a prebuilt 1-D ``nodes`` mesh (it is
    unhashable, hence not an :class:`ExecConfig` field); ``fault_hook``
    is the mesh executor's fault-injection test hook.
    """

    def __init__(self, graph, weights, plan, nodes: int,
                 config: ExecConfig = ExecConfig(), *, mesh=None,
                 fault_hook=None):
        if nodes < 1:
            raise ValueError(f"nodes must be >= 1, got {nodes}")
        self.graph = graph
        self.weights = weights
        self.plan = plan
        self.nodes = nodes
        self.config = config
        self.fault_hook = fault_hook
        if graph.is_chain:
            plan.validate()
            if len(plan) != len(graph):
                raise ValueError("plan/graph length mismatch")
        else:
            plan.validate_for(graph)
        self._mesh = mesh
        if config.executor == "mesh" and mesh is None and nodes > 1:
            from repro.launch.mesh import make_nodes_mesh
            try:
                self._mesh = make_nodes_mesh(nodes)
            except RuntimeError:
                # too few devices: only the degrade-to-local policy may
                # run without the mesh; every other policy fails here
                if config.fallback != "local":
                    raise
                self._mesh = None
        #: the weights the executor reads: on a mesh, placed once on
        #: every device, as the stage programs replicate them, so that no
        #: request copies them from one device to the others again
        self._weights = weights
        if config.executor == "mesh" and self._mesh is not None:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            self._weights = jax.device_put(
                weights, NamedSharding(self._mesh, PartitionSpec()))

    @property
    def mesh(self):
        """The bound device mesh (``None`` for the local executor)."""
        return self._mesh

    def run(self, x) -> Tuple[object, object]:
        """Execute the bound plan on ``x`` → ``(output, ExecStats)``."""
        cfg = self.config
        if cfg.executor == "mesh":
            from repro.runtime.mesh_exec import run_partitioned_mesh
            return run_partitioned_mesh(
                self.graph, self._weights, x, self.plan, self.nodes,
                backend=cfg.backend, mesh=self._mesh,
                instrument=cfg.instrument, overlap=cfg.overlap,
                stage_timeout_s=cfg.stage_timeout_s,
                stage_retries=cfg.stage_retries, fallback=cfg.fallback,
                fault_hook=self.fault_hook)
        from repro.runtime.engine import _run_partitioned_local
        return _run_partitioned_local(
            self.graph, self.weights, x, self.plan, self.nodes,
            jit_segments=cfg.jit_segments, backend=cfg.backend)

    def __call__(self, x):
        """Convenience: ``session(x)`` → output only (stats dropped)."""
        return self.run(x)[0]
