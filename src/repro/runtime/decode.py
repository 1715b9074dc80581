"""Autoregressive transformer decode over the partition planner's plans.

The missing FlexPie workload: a decoder-only transformer generating one
token at a time.  This module supplies the whole vertical slice —

* :class:`TransformerSpec` + :func:`decode_graph` / :func:`prefill_graph`:
  the workload expressed in the planner IR (``ConvT.ATTN`` / ``ConvT.FFN``
  layers carrying head counts and folded score-matmul flops), so
  :func:`repro.core.dpp.plan_search` prices head-sharded decode like any
  other graph.
* :func:`init_transformer` / :func:`reference_decode`: a seeded pre-norm
  reference model with a contiguous, single-device KV cache — the oracle
  every sharded execution must match token for token.
* :class:`DecodeSession`: decode-step execution of a searched plan on
  ``nodes`` devices with the distributed paged KV cache
  (:class:`repro.runtime.kv_cache.PagedKVCache`).  ``Scheme.OUTC`` on an
  ATTN layer shards *heads* across nodes — each node projects, caches, and
  attends only its own heads, and the single cross-node exchange is the
  head-output gather feeding the (replicated) output projection.
  ``Scheme.OUTC`` on an FFN layer column-shards ``w1`` the same way
  (Megatron-style) with the gather before ``w2``.  Any other scheme runs
  the layer replicated.  Both the local executor and the mesh executor
  (``shard_map`` + ``all_gather``, one compiled step program reused for
  every position) are supported via :class:`~repro.runtime.session.
  ExecConfig`.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.graph import ConvT, LayerSpec, ModelGraph, chain
from repro.core.partition import Scheme, split_sizes
from repro.kernels.flash_attention import NEG_INF, flash_decode_paged
from repro.runtime.kv_cache import PagedKVCache
from repro.runtime.session import ExecConfig

__all__ = [
    "TransformerSpec", "decode_graph", "prefill_graph", "init_transformer",
    "reference_decode", "DecodeSession", "greedy_decode", "plan_decode",
]

AXIS = "nodes"


# --------------------------------------------------------------------------
# workload spec + planner IR
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TransformerSpec:
    """Decoder-only transformer shape (pre-norm, MHA, ReLU FFN)."""

    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    vocab: int = 256

    def __post_init__(self) -> None:
        if self.n_layers < 1 or self.d_model < 1 or self.d_ff < 1:
            raise ValueError(f"bad transformer shape {self}")
        if self.n_heads < 1 or self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} not divisible by "
                             f"n_heads {self.n_heads}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def attn_flop_factor(spec: TransformerSpec, kv_len: int) -> float:
    """True attention flops relative to the IR base (one d->d matmul).

    Per query token: four d*d projections (8d^2) plus score and value
    matmuls against ``kv_len`` cached keys (4*d*kv_len), over the 2d^2
    base the estimator charges a ``d -> d`` layer."""
    d = spec.d_model
    return 4.0 + 2.0 * float(kv_len) / d


def ffn_flop_factor(spec: TransformerSpec) -> float:
    """Two d*d_ff matmuls over the 2d^2 base."""
    return 2.0 * spec.d_ff / spec.d_model


def _graph(spec: TransformerSpec, q_len: int, kv_len: int,
           name: str) -> ModelGraph:
    layers: List[LayerSpec] = []
    af = attn_flop_factor(spec, kv_len)
    ff = ffn_flop_factor(spec)
    for i in range(spec.n_layers):
        layers.append(LayerSpec(f"b{i}.attn", ConvT.ATTN, q_len, 1,
                                spec.d_model, spec.d_model,
                                extra_flop_factor=af, heads=spec.n_heads))
        layers.append(LayerSpec(f"b{i}.ffn", ConvT.FFN, q_len, 1,
                                spec.d_model, spec.d_model,
                                extra_flop_factor=ff))
    return chain(name, layers)


def decode_graph(spec: TransformerSpec, kv_len: int) -> ModelGraph:
    """One decode step (``q_len == 1``) attending to ``kv_len`` cached
    keys — the steady-state workload the planner should optimise for."""
    return _graph(spec, 1, kv_len, f"decode_kv{kv_len}")


def prefill_graph(spec: TransformerSpec, seq_len: int) -> ModelGraph:
    """Prompt ingestion: ``seq_len`` queries attending to ``seq_len``
    keys (causal on average halves the score flops; the factor keeps the
    full-matrix upper bound, matching the kernels' padded execution)."""
    return _graph(spec, seq_len, seq_len, f"prefill_s{seq_len}")


def plan_decode(spec: TransformerSpec, kv_len: int, nodes: int, tb=None,
                **kwargs):
    """Search a decode-step plan: :func:`plan_search` over
    :func:`decode_graph` with the analytic estimator."""
    from repro.core.cost import Testbed
    from repro.core.dpp import plan_search
    from repro.core.estimator import AnalyticEstimator
    if tb is None:
        tb = Testbed(nodes=nodes, bandwidth_gbps=5.0)
    if tb.nodes != nodes:
        raise ValueError(f"testbed nodes {tb.nodes} != {nodes}")
    return plan_search(decode_graph(spec, kv_len), AnalyticEstimator(), tb,
                       **kwargs)


# --------------------------------------------------------------------------
# seeded model + single-device oracle
# --------------------------------------------------------------------------
def init_transformer(spec: TransformerSpec, seed: int = 0) -> Dict:
    """Seeded float32 weights: ``{"emb": [vocab, d], "blocks": [{wq, wk,
    wv, wo: [d, d], w1: [d, d_ff], w2: [d_ff, d]}, ...]}``."""
    rng = np.random.default_rng(seed)
    d, dff = spec.d_model, spec.d_ff

    def g(rows, cols, scale):
        return jnp.asarray(rng.normal(0.0, scale, (rows, cols)),
                           jnp.float32)

    blocks = []
    for _ in range(spec.n_layers):
        blocks.append({
            "wq": g(d, d, d ** -0.5), "wk": g(d, d, d ** -0.5),
            "wv": g(d, d, d ** -0.5), "wo": g(d, d, d ** -0.5),
            "w1": g(d, dff, d ** -0.5), "w2": g(dff, d, dff ** -0.5),
        })
    return {"emb": g(spec.vocab, d, 1.0), "blocks": blocks}


def _rmsnorm(x: jnp.ndarray) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x) + 1e-6)


def _reference_step(spec: TransformerSpec, weights: Dict, x: jnp.ndarray,
                    caches: List[Tuple[jnp.ndarray, jnp.ndarray]]):
    """One pre-norm block stack step with contiguous growing K/V."""
    H, hd = spec.n_heads, spec.head_dim
    scale = 1.0 / math.sqrt(hd)
    new = []
    for blk, (K, V) in zip(weights["blocks"], caches):
        a = _rmsnorm(x)
        q = (a @ blk["wq"]).reshape(H, hd)
        k = (a @ blk["wk"]).reshape(H, hd)
        v = (a @ blk["wv"]).reshape(H, hd)
        K = jnp.concatenate([K, k[None]], axis=0)     # [t, H, hd]
        V = jnp.concatenate([V, v[None]], axis=0)
        s = jnp.einsum("hd,thd->ht", q, K) * scale
        p = jax.nn.softmax(s, axis=-1)
        x = x + jnp.einsum("ht,thd->hd", p, V).reshape(-1) @ blk["wo"]
        f = _rmsnorm(x)
        x = x + jnp.maximum(f @ blk["w1"], 0.0) @ blk["w2"]
        new.append((K, V))
    return x, new


def reference_decode(spec: TransformerSpec, weights: Dict,
                     prompt: Sequence[int], n_new: int):
    """Greedy single-device decode oracle → ``(tokens, logits)`` where
    ``logits`` is ``[n_new, vocab]`` (the distribution each emitted token
    was argmaxed from)."""
    z = jnp.zeros((0, spec.n_heads, spec.head_dim), jnp.float32)
    caches = [(z, z) for _ in range(spec.n_layers)]
    emb = weights["emb"]
    x = None
    for tok in prompt:
        x, caches = _reference_step(spec, weights, emb[tok], caches)
    tokens, logits = [], []
    for _ in range(n_new):
        lg = x @ emb.T
        tok = int(jnp.argmax(lg))
        tokens.append(tok)
        logits.append(lg)
        x, caches = _reference_step(spec, weights, emb[tok], caches)
    return tokens, jnp.stack(logits)


# --------------------------------------------------------------------------
# sharded decode execution
# --------------------------------------------------------------------------
def _paged_attn(q, kp, vp, table, kv_len, *, scale, backend):
    """Decode attention over one node's paged pools.

    ``q``: [lh, hd]; ``kp``/``vp``: [lh, P, ps, hd]; ``table``: static
    [P] logical→physical map; ``kv_len`` traced.  The XLA path gathers
    the *full* logical capacity (static shapes — the step compiles once)
    and masks positions ``>= kv_len``; masked scores contribute exactly
    0.0 to the softmax sums, so padding never perturbs live outputs."""
    lh, _, _, hd = kp.shape
    if backend == "pallas":
        return flash_decode_paged(q, kp, vp, table, kv_len, scale=scale)
    k = kp[:, table].reshape(lh, -1, hd)              # logical order
    v = vp[:, table].reshape(lh, -1, hd)
    s = jnp.einsum("hd,htd->ht", q, k) * scale
    live = jnp.arange(k.shape[1])[None, :] < kv_len
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("ht,htd->hd", p, v)


def _offsets(split: Sequence[int]) -> List[int]:
    out = [0]
    for s in split:
        out.append(out[-1] + s)
    return out


class DecodeSession:
    """Stateful decode of one plan on ``nodes`` devices.

    ``plan.steps`` must pair up with :func:`decode_graph`'s layers —
    entry ``2i`` is block ``i``'s ATTN layer, ``2i+1`` its FFN.  An OutC
    ATTN step head-shards block ``i`` (KV pages live only on the owning
    nodes); an OutC FFN step column-shards ``w1``.  Everything else is
    replicated (every node keeps all heads, all pools stay full — memory
    accounting via :meth:`PagedKVCache.bytes_per_node` reflects that).

    ``config.executor`` picks single-process simulation (``"local"``) or
    the ``shard_map`` mesh executor; ``config.backend`` picks the
    attention inner (``"xla"`` gather-and-mask vs the ``"pallas"`` paged
    decode kernel).  One step program is compiled per session and reused
    for every position — ``pos`` is traced, geometry is static.
    """

    def __init__(self, spec: TransformerSpec, weights: Dict, plan,
                 nodes: int, config: ExecConfig = ExecConfig(), *,
                 page_size: int = 16, capacity: int = 256,
                 cache_seed: int = 0, mesh=None):
        if len(plan.steps) != 2 * spec.n_layers:
            raise ValueError(f"plan has {len(plan.steps)} steps, decode "
                             f"graph needs {2 * spec.n_layers}")
        self.spec = spec
        self.weights = weights
        self.plan = plan
        self.nodes = int(nodes)
        self.config = config
        H, dff = spec.n_heads, spec.d_ff
        self.attn_sharded = [plan.steps[2 * i][0] == Scheme.OUTC
                             for i in range(spec.n_layers)]
        self.ffn_sharded = [plan.steps[2 * i + 1][0] == Scheme.OUTC
                            for i in range(spec.n_layers)]
        self.head_split = [split_sizes(H, nodes) if sh else [H] * nodes
                           for sh in self.attn_sharded]
        self.ff_split = [split_sizes(dff, nodes) if sh else [dff] * nodes
                         for sh in self.ffn_sharded]
        self.cache = PagedKVCache(self.head_split, spec.head_dim,
                                  page_size, capacity, seed=cache_seed)
        self._mesh = mesh
        if config.executor == "mesh":
            if mesh is None:
                from repro.launch.mesh import make_nodes_mesh
                self._mesh = make_nodes_mesh(nodes)
            self._step_fn = self._build_mesh_step()
            self._mesh_pools = self._stack_pools()
        else:
            self._step_fn = jax.jit(self._build_local_step())

    # ---- shared -----------------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    def step(self, token: int) -> jnp.ndarray:
        """Process one token at the cache's current position; returns the
        final hidden state (feed ``h @ emb.T`` to sample the next)."""
        x = self.weights["emb"][int(token)]
        pos = jnp.int32(self.cache.length)
        if self.config.executor == "mesh":
            h = self._mesh_step(x, pos)
        else:
            h = self._local_step(x, pos)
        self.cache.advance(1)
        return h

    def prefill(self, prompt: Sequence[int]) -> jnp.ndarray:
        """Sequential decode-steps over the prompt (the serving simulator
        models batched prefill; execution reuses the one step program)."""
        h = None
        for tok in prompt:
            h = self.step(tok)
        return h

    # ---- local executor ---------------------------------------------------
    def _build_local_step(self):
        spec, nodes = self.spec, self.nodes
        H, hd, dff = spec.n_heads, spec.head_dim, spec.d_ff
        ps = self.cache.page_size
        table = np.asarray(self.cache.page_table)
        jtable = jnp.asarray(table)
        scale = 1.0 / math.sqrt(hd)
        backend = self.config.backend
        attn_sh, ffn_sh = self.attn_sharded, self.ffn_sharded
        hsplits = self.head_split
        foffs = [_offsets(fs) for fs in self.ff_split]

        def step(x, pos, weights, kpools, vpools):
            kv_len = pos + 1
            phys = jtable[pos // ps]
            row = pos % ps
            nk, nv = [], []
            for i, blk in enumerate(weights["blocks"]):
                a = _rmsnorm(x)
                if attn_sh[i]:
                    hs, off = hsplits[i], _offsets(hsplits[i])
                    outs, lk, lv = [], [], []
                    for n in range(nodes):
                        if hs[n] == 0:
                            lk.append(kpools[i][n])
                            lv.append(vpools[i][n])
                            continue
                        cols = slice(off[n] * hd, off[n + 1] * hd)
                        q = (a @ blk["wq"][:, cols]).reshape(hs[n], hd)
                        k = (a @ blk["wk"][:, cols]).reshape(hs[n], hd)
                        v = (a @ blk["wv"][:, cols]).reshape(hs[n], hd)
                        kp = kpools[i][n].at[:, phys, row].set(k)
                        vp = vpools[i][n].at[:, phys, row].set(v)
                        lk.append(kp)
                        lv.append(vp)
                        outs.append(_paged_attn(q, kp, vp, table, kv_len,
                                                scale=scale,
                                                backend=backend))
                    o = jnp.concatenate(outs, 0).reshape(-1)
                else:
                    # replicated: one full computation; every node's pool
                    # receives the same K/V (replication costs memory on
                    # every node — by design)
                    q = (a @ blk["wq"]).reshape(H, hd)
                    k = (a @ blk["wk"]).reshape(H, hd)
                    v = (a @ blk["wv"]).reshape(H, hd)
                    lk = [kp.at[:, phys, row].set(k) for kp in kpools[i]]
                    lv = [vp.at[:, phys, row].set(v) for vp in vpools[i]]
                    o = _paged_attn(q, lk[0], lv[0], table, kv_len,
                                    scale=scale,
                                    backend=backend).reshape(-1)
                nk.append(lk)
                nv.append(lv)
                x = x + o @ blk["wo"]
                f = _rmsnorm(x)
                if ffn_sh[i]:
                    fo = foffs[i]
                    hv = jnp.concatenate(
                        [jnp.maximum(f @ blk["w1"][:, fo[n]:fo[n + 1]],
                                     0.0)
                         for n in range(nodes) if fo[n + 1] > fo[n]], -1)
                else:
                    hv = jnp.maximum(f @ blk["w1"], 0.0)
                x = x + hv @ blk["w2"]
            return x, nk, nv

        return step

    def _local_step(self, x, pos):
        L = self.spec.n_layers
        kp = [[self.cache.pages(i, n)[0] for n in range(self.nodes)]
              for i in range(L)]
        vp = [[self.cache.pages(i, n)[1] for n in range(self.nodes)]
              for i in range(L)]
        h, nk, nv = self._step_fn(x, pos, self.weights, kp, vp)
        for i in range(L):
            for n in range(self.nodes):
                self.cache.store(i, n, nk[i][n], nv[i][n])
        return h

    # ---- mesh executor ----------------------------------------------------
    def _stack_pools(self):
        """Zero-pad each layer's per-node pools to ``max_lh`` and stack
        into ``[nodes, max_lh, P, ps, hd]`` (the shard_map carries)."""
        kps, vps = [], []
        for i, hs in enumerate(self.head_split):
            mx = max(hs)
            lk, lv = [], []
            for n in range(self.nodes):
                kp, vp = self.cache.pages(i, n)
                pad = [(0, mx - hs[n]), (0, 0), (0, 0), (0, 0)]
                lk.append(jnp.pad(kp, pad))
                lv.append(jnp.pad(vp, pad))
            kps.append(jnp.stack(lk))
            vps.append(jnp.stack(lv))
        return kps, vps

    def _build_mesh_step(self):
        from jax.sharding import PartitionSpec as P
        spec, nodes = self.spec, self.nodes
        H, hd, d, dff = spec.n_heads, spec.head_dim, spec.d_model, spec.d_ff
        ps = self.cache.page_size
        table = np.asarray(self.cache.page_table)
        jtable = jnp.asarray(table)
        scale = 1.0 / math.sqrt(hd)
        backend = self.config.backend
        attn_sh, ffn_sh = self.attn_sharded, self.ffn_sharded
        hsplits, fsplits = self.head_split, self.ff_split

        # stacked per-node parameter shards, zero-padded to the layer max
        shard_p, rep_p = {"qkv": [], "w1": []}, {"wo": [], "w2": []}
        for i, blk in enumerate(self.weights["blocks"]):
            hs, mx = hsplits[i], max(hsplits[i])
            off = _offsets(hs)

            def col_shards(w, widths, offs, mxw):
                return jnp.stack([
                    jnp.pad(w[:, offs[n]:offs[n + 1]],
                            [(0, 0), (0, mxw - widths[n])])
                    for n in range(nodes)])
            if attn_sh[i]:
                qkv = tuple(col_shards(blk[key],
                                       [h * hd for h in hs],
                                       [o * hd for o in off], mx * hd)
                            for key in ("wq", "wk", "wv"))
            else:
                qkv = tuple(jnp.stack([blk[key]] * nodes)
                            for key in ("wq", "wk", "wv"))
            shard_p["qkv"].append(qkv)
            fs, fmx = fsplits[i], max(fsplits[i])
            if ffn_sh[i]:
                shard_p["w1"].append(col_shards(blk["w1"], fs,
                                                _offsets(fs), fmx))
            else:
                shard_p["w1"].append(jnp.stack([blk["w1"]] * nodes))
            rep_p["wo"].append(blk["wo"])
            rep_p["w2"].append(blk["w2"])
        self._mesh_shard_p, self._mesh_rep_p = shard_p, rep_p

        def body(x, pos, rep, shard, kps, vps):
            kv_len = pos + 1
            phys = jtable[pos // ps]
            row = pos % ps
            nk, nv = [], []
            for i in range(spec.n_layers):
                wq, wk, wv = (w[0] for w in shard["qkv"][i])
                mx = max(hsplits[i])
                a = _rmsnorm(x)
                q = (a @ wq).reshape(mx, hd)
                k = (a @ wk).reshape(mx, hd)
                v = (a @ wv).reshape(mx, hd)
                kp = kps[i][0].at[:, phys, row].set(k)
                vp = vps[i][0].at[:, phys, row].set(v)
                nk.append(kp[None])
                nv.append(vp[None])
                o = _paged_attn(q, kp, vp, table, kv_len, scale=scale,
                                backend=backend).reshape(-1)
                if attn_sh[i] and nodes > 1:
                    # the one decode-step exchange: head outputs gather,
                    # padded lanes sliced off by static per-node widths
                    g = jax.lax.all_gather(o, AXIS)
                    o = jnp.concatenate(
                        [g[n, :hsplits[i][n] * hd] for n in range(nodes)
                         if hsplits[i][n]], -1)
                else:
                    o = o[:H * hd]
                x = x + o @ rep["wo"][i]
                f = _rmsnorm(x)
                hv = jnp.maximum(f @ shard["w1"][i][0], 0.0)
                if ffn_sh[i] and nodes > 1:
                    g = jax.lax.all_gather(hv, AXIS)
                    hv = jnp.concatenate(
                        [g[n, :fsplits[i][n]] for n in range(nodes)
                         if fsplits[i][n]], -1)
                else:
                    hv = hv[:dff]
                x = x + hv @ rep["w2"][i]
            return x, nk, nv

        return jax.jit(jax.shard_map(
            body, mesh=self._mesh,
            in_specs=(P(), P(), P(), P(AXIS), P(AXIS), P(AXIS)),
            out_specs=(P(), P(AXIS), P(AXIS)),
            check_vma=False))

    def _mesh_step(self, x, pos):
        kps, vps = self._mesh_pools
        h, nk, nv = self._step_fn(x, pos, self._mesh_rep_p,
                                  self._mesh_shard_p, kps, vps)
        self._mesh_pools = (nk, nv)
        # mirror trimmed slices back so the cache object stays the
        # inspectable source of truth (lazy slices — cheap)
        for i, hs in enumerate(self.head_split):
            for n in range(self.nodes):
                self.cache.store(i, n, nk[i][n, :hs[n]], nv[i][n, :hs[n]])
        return h


def greedy_decode(session: DecodeSession, prompt: Sequence[int],
                  n_new: int):
    """Greedy generation through a :class:`DecodeSession` →
    ``(tokens, logits)`` shaped exactly like :func:`reference_decode`."""
    h = session.prefill(prompt)
    emb = session.weights["emb"]
    tokens, logits = [], []
    for _ in range(n_new):
        lg = h @ emb.T
        tok = int(jnp.argmax(lg))
        tokens.append(tok)
        logits.append(lg)
        h = session.step(tok)
    return tokens, jnp.stack(logits)
