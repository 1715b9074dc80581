"""Autoregressive decode: paged-KV kernel conformance, the distributed
KV cache, and sharded decode equivalence against the single-device
oracle.

Acceptance contract: greedy decode through a *searched* plan
(``plan_decode`` — head-sharded OutC on every ATTN step, not a
hand-written plan) is token-for-token identical to ``reference_decode``
at nodes 2/4/8 on both executors.  The mesh-executor half follows the
repo's multi-device convention: the main process keeps jax at 1 device,
so real-mesh runs happen in an 8-fake-device subprocess (``slow``).
"""
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ConvT, LayerSpec, Scheme, Testbed
from repro.kernels.flash_attention import flash_decode_paged
from repro.runtime.decode import (DecodeSession, TransformerSpec,
                                  decode_graph, greedy_decode,
                                  init_transformer, plan_decode,
                                  prefill_graph, reference_decode)
from repro.runtime.kv_cache import PagedKVCache
from repro.runtime.session import ExecConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: head-sharding-friendly testbed: SRIO-class latency makes the decode
#: gather cheap enough that OutC wins at every node count (cf. the
#: latency-dominated default where 8-node decode prefers replication)
TB = lambda nodes: Testbed(nodes=nodes, bandwidth_gbps=5.0,
                           link_latency_us=1.0)

SPEC = TransformerSpec(n_layers=2, d_model=256, n_heads=8, d_ff=1024,
                       vocab=64)
PROMPT = [3, 17, 42, 7]
N_NEW = 5


def _searched_plan(nodes, spec=SPEC, kv_len=2048):
    res = plan_decode(spec, kv_len, nodes, tb=TB(nodes))
    # the acceptance bar: the planner itself must choose head sharding
    attn = [s for i, (s, _) in enumerate(res.plan.steps) if i % 2 == 0]
    assert all(s == Scheme.OUTC for s in attn), attn
    return res.plan


@pytest.fixture(scope="module")
def oracle():
    w = init_transformer(SPEC, seed=1)
    toks, lg = reference_decode(SPEC, w, PROMPT, N_NEW)
    return w, toks, lg


# ---------------------------------------------------------------------------
# decode kernel conformance (q_len == 1 over a paged table)
# ---------------------------------------------------------------------------

def _decode_ref(q, k, v, kv_len, window):
    """Inline softmax reference over contiguous logical-order K/V."""
    hd = q.shape[-1]
    s = np.einsum("bd,btd->bt", q, k[:, :kv_len]) / math.sqrt(hd)
    if window is not None:
        t = np.arange(kv_len)
        s = np.where(t[None, :] > kv_len - 1 - window, s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bt,btd->bd", p, v[:, :kv_len])


@pytest.mark.parametrize("window", [None, 6, 2])
@pytest.mark.parametrize("kv_len", [1, 4, 7, 13, 20])
def test_flash_decode_paged_conformance(window, kv_len):
    """Scrambled page table, partial last page, sliding windows whose
    lower bound lands mid-page: the kernel must floor its block skip to
    the page boundary (a mid-page start would walk the wrong physical
    page) and mask in-page, matching the contiguous reference."""
    rng = np.random.default_rng(kv_len * 31 + (window or 0))
    BH, ps, hd = 3, 4, 8
    n_pages = 5
    assert kv_len <= n_pages * ps
    k = rng.normal(size=(BH, n_pages * ps, hd)).astype(np.float32)
    v = rng.normal(size=(BH, n_pages * ps, hd)).astype(np.float32)
    q = rng.normal(size=(BH, hd)).astype(np.float32)
    table = rng.permutation(n_pages).astype(np.int32)
    kp = np.zeros((BH, n_pages, ps, hd), np.float32)
    vp = np.zeros_like(kp)
    for lp in range(n_pages):
        kp[:, table[lp]] = k[:, lp * ps:(lp + 1) * ps]
        vp[:, table[lp]] = v[:, lp * ps:(lp + 1) * ps]
    out = flash_decode_paged(jnp.asarray(q), jnp.asarray(kp),
                             jnp.asarray(vp), table, kv_len, window=window)
    ref = _decode_ref(q, k, v, kv_len, window)
    assert float(np.max(np.abs(np.asarray(out) - ref))) < 1e-5


# ---------------------------------------------------------------------------
# distributed paged KV cache
# ---------------------------------------------------------------------------

def test_paged_cache_scrambled_table_roundtrip():
    cache = PagedKVCache([[2, 1]], head_dim=3, page_size=4, capacity=16,
                         seed=3)
    table = cache.page_table
    assert sorted(table.tolist()) == list(range(4))
    assert table.tolist() != list(range(4))   # genuinely scrambled
    rng = np.random.default_rng(0)
    ks = {0: [], 1: []}
    for pos in range(7):
        for node, lh in enumerate((2, 1)):
            k = jnp.asarray(rng.normal(size=(lh, 3)), jnp.float32)
            cache.append(0, node, pos, k, 2.0 * k)
            ks[node].append(np.asarray(k))
        cache.advance()
    assert cache.length == 7
    for node in (0, 1):
        k, v = cache.gather(0, node)
        assert k.shape == (7, (2, 1)[node], 3)
        np.testing.assert_allclose(np.asarray(k), np.stack(ks[node]))
        np.testing.assert_allclose(np.asarray(v),
                                   2.0 * np.stack(ks[node]))


def test_paged_cache_bytes_follow_head_ownership():
    """Pages live on head owners: a node owning 3x the heads holds 3x
    the bytes; a replicated layer costs full pool on every node."""
    sharded = PagedKVCache([[3, 1]], head_dim=4, page_size=2, capacity=8)
    assert sharded.bytes_per_node(0) == 3 * sharded.bytes_per_node(1)
    repl = PagedKVCache([[4, 4]], head_dim=4, page_size=2, capacity=8)
    assert repl.bytes_per_node(0) == repl.bytes_per_node(1)
    assert repl.bytes_per_node(1) == sharded.bytes_per_node(0) \
        + sharded.bytes_per_node(1)


def test_paged_cache_overflow_and_bounds():
    cache = PagedKVCache([[1]], head_dim=2, page_size=2, capacity=4)
    cache.advance(4)
    with pytest.raises(ValueError, match="overflow"):
        cache.advance(1)
    with pytest.raises(ValueError, match="capacity"):
        cache.slot(4)
    with pytest.raises(ValueError, match="pool shape"):
        cache.store(0, 0, jnp.zeros((2, 2, 2, 2)), jnp.zeros((2, 2, 2, 2)))


# ---------------------------------------------------------------------------
# IR: ATTN/FFN layers and the decode graphs
# ---------------------------------------------------------------------------

def test_attn_ir_validation():
    from repro.core.graph import chain
    ok = LayerSpec("a", ConvT.ATTN, 1, 1, 32, 32, heads=4)
    assert ok.heads == 4 and ok.flops() > 0
    with pytest.raises(ValueError, match="heads"):
        TransformerSpec(1, 32, 5, 64)          # 32 % 5 != 0
    with pytest.raises(ValueError, match="heads"):
        chain("bad", [LayerSpec("a", ConvT.ATTN, 1, 1, 32, 32, heads=3)])
    with pytest.raises(ValueError, match="head"):
        chain("bad", [LayerSpec("a", ConvT.FC, 1, 1, 32, 32, heads=4)])


def test_decode_graph_structure():
    g = decode_graph(SPEC, kv_len=512)
    assert len(g) == 2 * SPEC.n_layers
    for i, l in enumerate(g.layers):
        assert l.in_h == 1 and l.in_w == 1
        if i % 2 == 0:
            assert l.conv_t == ConvT.ATTN and l.heads == SPEC.n_heads
            # folded score/value matmuls grow with kv_len
            assert l.extra_flop_factor == pytest.approx(
                4.0 + 2.0 * 512 / SPEC.d_model)
        else:
            assert l.conv_t == ConvT.FFN and l.heads == 0
    p = prefill_graph(SPEC, seq_len=64)
    assert all(l.in_h == 64 for l in p.layers)


@pytest.mark.parametrize("nodes", [2, 4, 8])
def test_plan_search_head_shards_decode(nodes):
    """The planner head-shards decode on its own: every ATTN step OutC."""
    _searched_plan(nodes)


# ---------------------------------------------------------------------------
# sharded decode == single-device oracle (local executor, in-process)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nodes", [1, 2, 4, 8])
def test_decode_local_equivalence(oracle, nodes):
    w, ref_toks, ref_lg = oracle
    plan = _searched_plan(max(nodes, 2))
    sess = DecodeSession(SPEC, w, plan, nodes, ExecConfig(),
                         page_size=4, capacity=32)
    toks, lg = greedy_decode(sess, PROMPT, N_NEW)
    assert toks == ref_toks
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               rtol=1e-4, atol=1e-4)
    # pages really live on head owners for the searched (sharded) plan
    if nodes > 1:
        assert all(sess.cache.bytes_per_node(n)
                   < sess.cache.bytes_per_node(0) * nodes
                   for n in range(nodes))
    assert sess.cache.length == len(PROMPT) + N_NEW


def test_decode_local_pallas_backend(oracle):
    """The paged Pallas decode kernel slots into the same step program."""
    w, ref_toks, ref_lg = oracle
    sess = DecodeSession(SPEC, w, _searched_plan(4), 4,
                         ExecConfig(backend="pallas"),
                         page_size=4, capacity=32)
    toks, lg = greedy_decode(sess, PROMPT, N_NEW)
    assert toks == ref_toks
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               rtol=1e-4, atol=1e-4)


def test_decode_mixed_plan_replicated_layers(oracle):
    """Non-OutC steps run replicated and still match (the DP may mix)."""
    from repro.core.plan import Mode, Plan
    w, ref_toks, ref_lg = oracle
    plan = Plan(((Scheme.INH, Mode.T), (Scheme.OUTC, Mode.T),
                 (Scheme.OUTC, Mode.T), (Scheme.INH, Mode.T)))
    sess = DecodeSession(SPEC, w, plan, 4, ExecConfig(),
                         page_size=4, capacity=32)
    toks, lg = greedy_decode(sess, PROMPT, N_NEW)
    assert toks == ref_toks
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# property: paged+sharded == contiguous single-device, random geometry
# (hypothesis when installed, PR-7-style fixed-seed slice otherwise)
# ---------------------------------------------------------------------------

def _property_case(seed):
    rng = np.random.default_rng(seed)
    H = int(rng.choice([1, 2, 4, 6]))
    hd = int(rng.choice([4, 8]))
    spec = TransformerSpec(n_layers=int(rng.integers(1, 3)),
                           d_model=H * hd, n_heads=H,
                           d_ff=int(rng.choice([16, 32])), vocab=32)
    w = init_transformer(spec, seed=seed)
    page_size = int(rng.integers(1, 6))
    prompt = [int(t) for t in rng.integers(0, spec.vocab, rng.integers(1, 6))]
    n_new = int(rng.integers(1, 5))
    nodes = int(rng.integers(1, 5))
    total = len(prompt) + n_new
    ref_toks, ref_lg = reference_decode(spec, w, prompt, n_new)
    from repro.core.plan import Mode, Plan
    steps = []
    for _ in range(spec.n_layers):
        steps.append((Scheme.OUTC if rng.random() < 0.75 else Scheme.INH,
                      Mode.T))
        steps.append((Scheme.OUTC if rng.random() < 0.5 else Scheme.INH,
                      Mode.T))
    sess = DecodeSession(spec, w, Plan(tuple(steps)), nodes, ExecConfig(),
                         page_size=page_size,
                         capacity=total + int(rng.integers(0, 7)),
                         cache_seed=seed + 1)
    toks, lg = greedy_decode(sess, prompt, n_new)
    assert toks == ref_toks, (seed, spec)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref_lg),
                               rtol=1e-4, atol=1e-4)


try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:        # property tests only; see pyproject [dev]
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(st.integers(0, 2 ** 16))
    def test_property_paged_sharded_decode(seed):
        _property_case(seed)
else:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 5, 8, 13, 21])
    def test_property_paged_sharded_decode(seed):
        _property_case(seed)


# ---------------------------------------------------------------------------
# serving: prefill/decode split + continuous decode-step batching
# ---------------------------------------------------------------------------

def test_serve_decode_split_plans_differ_by_phase():
    """The split is real: decode head-shards (OutC), prefill — compute
    bound over seq — picks a spatial scheme."""
    from repro.cluster import homogeneous, plan_decode_serving
    cl = homogeneous(4, bandwidth_gbps=5.0)
    pre, dec = plan_decode_serving(SPEC, cl, prompt_len=64, n_new=16)
    assert all(s == Scheme.OUTC for i, (s, _) in
               enumerate(dec.plan.steps) if i % 2 == 0)
    assert any(s.spatial for s, _ in pre.plan.steps)


def test_serve_decode_continuous_batching():
    from repro.cluster import homogeneous, serve_decode
    cl = homogeneous(4, bandwidth_gbps=5.0)
    kw = dict(prompt_len=64, n_new=16, n_requests=24, max_batch=8)
    slow = serve_decode(SPEC, cl, arrival_rate_rps=2.0, **kw)
    fast = serve_decode(SPEC, cl, arrival_rate_rps=2000.0, **kw)
    # saturation batches decode steps; trickle arrivals decode solo
    assert slow.mean_batch == pytest.approx(1.0)
    assert fast.mean_batch > 2.0
    assert fast.tokens_per_s > slow.tokens_per_s
    assert fast.p99_latency_s >= fast.p50_latency_s > 0.0
    assert slow.prefill_s > slow.decode_step_s > 0.0
    with pytest.raises(ValueError, match="arrival rate"):
        serve_decode(SPEC, cl, arrival_rate_rps=0.0, **kw)


# ---------------------------------------------------------------------------
# mesh executor (subprocess, 8 fake devices)
# ---------------------------------------------------------------------------

#: mesh decode vs the oracle, as max|logits - ref| / max|ref|: both sides
#: run f32 on the CPU, and sharding heads and d_ff only reorders the sums
#: of those contractions (~1e-6 relative); 1e-4 leaves 100x headroom
MESH_LOGITS_TOL = 1e-4


@pytest.mark.slow
def test_decode_mesh_equivalence():
    """Logits within ``MESH_LOGITS_TOL`` of the oracle, and hence the same
    greedy tokens, on a real device mesh at nodes 2/4/8 (xla backend)
    plus a pallas spot-check — searched plans only."""
    code = f"TOL = {MESH_LOGITS_TOL!r}\n" + textwrap.dedent("""
        import numpy as np, jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.cost import Testbed
        from repro.core.partition import Scheme
        from repro.runtime.decode import (DecodeSession, TransformerSpec,
            greedy_decode, init_transformer, plan_decode, reference_decode)
        from repro.runtime.session import ExecConfig

        spec = TransformerSpec(n_layers=2, d_model=256, n_heads=8,
                               d_ff=1024, vocab=64)
        w = init_transformer(spec, seed=1)
        prompt, n_new = [3, 17, 42, 7], 5
        ref_toks, ref_lg = reference_decode(spec, w, prompt, n_new)
        for nodes, backend in ((2, "xla"), (4, "xla"), (8, "xla"),
                               (2, "pallas")):
            tb = Testbed(nodes=nodes, bandwidth_gbps=5.0,
                         link_latency_us=1.0)
            plan = plan_decode(spec, 2048, nodes, tb=tb).plan
            assert all(s == Scheme.OUTC for i, (s, _) in
                       enumerate(plan.steps) if i % 2 == 0)
            sess = DecodeSession(spec, w, plan, nodes,
                                 ExecConfig(executor="mesh",
                                            backend=backend),
                                 page_size=4, capacity=32)
            toks, lg = greedy_decode(sess, prompt, n_new)
            ref = np.asarray(ref_lg)
            err = float(np.max(np.abs(np.asarray(lg) - ref)
                              / np.max(np.abs(ref))))
            assert err < TOL, (nodes, backend, err)
            assert toks == ref_toks, (nodes, backend, toks)
            print("MESH_DECODE_OK", nodes, backend, err)
        print("ALL_MESH_DECODE_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert "ALL_MESH_DECODE_OK" in r.stdout, r.stdout + r.stderr
