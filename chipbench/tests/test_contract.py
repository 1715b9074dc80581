"""The harness's contract with a configuration: everything model-specific
comes from the reference module (graph arguments, inputs, op kinds), so
a model unlike the image networks runs through ``run.run_cell`` with no
edit to the harness.  And the image cells' inputs and weights are the
ones they were before that contract: pinned at width 32."""
import copy
import hashlib
import json
import math
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from jax.random import normal, split

from chipbench import run, traffic, work
from chipbench.tests import ref_bert_chain, ref_inception
from repro.runtime.session import Session

PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
SEED = 2**31 + 99
REFS = {m.CONFIG["reference"]: m for m in (ref_bert_chain, ref_inception)}


@pytest.fixture
def test_refs(monkeypatch):
    """The harness finds the test-only references by their names."""
    find = run.reference
    monkeypatch.setattr(
        run, "reference",
        lambda cfg: REFS.get(cfg["reference"]) or find(cfg))


def cell(ref, nodes: int) -> SimpleNamespace:
    """A cell of ``ref``'s configuration on ``nodes`` devices, under the
    benchmark's own metrics and mix (a pool of 4 inputs)."""
    name = f"{ref.CONFIG['name']}.nodes{nodes}.closed1"
    mix = dict(traffic.load("closed1"), image_pool=4)
    return SimpleNamespace(
        spec=json.loads((run.ROOT / "BENCHMARK.json").read_text()),
        cell={"name": name, "config": ref.CONFIG["name"],
              "traffic": "closed1", "chips": nodes},
        config=dict(copy.deepcopy(ref.CONFIG), nodes=nodes), mix=mix,
        limits={"rel_err": {"limit": 6e-6}})


def run_small(c: SimpleNamespace) -> dict:
    return run.run_cell(c, SEED, 0.5, False,
                        jax.devices()[:c.cell["chips"]],
                        time.perf_counter(), peaks=PEAKS)


CASES = [(ref, n) for ref in REFS.values() for n in (1, 4)]
IDS = [f"{ref.CONFIG['name']}-{n}" for ref, n in CASES]


@pytest.mark.parametrize("ref,nodes", CASES, ids=IDS)
def test_new_model_sound_run_is_correct(test_refs, ref, nodes):
    res = run_small(cell(ref, nodes))
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == nodes
    assert res["checks"]["rel_err"]["value"] <= 6e-6


def _stale(orig):
    """The state left unchanged: every request gets the first answer."""
    def run_(self, x):
        if not hasattr(self, "_first"):
            self._first = orig(self, x)
        return self._first
    return run_


def _altered(orig):
    """One output altered where the answer is produced."""
    def run_(self, x):
        out, stats = orig(self, x)
        return out.at[..., 7].add(1e-3 * jax.numpy.max(abs(out))), stats
    return run_


def _raises(orig):
    """Every fourth request fails."""
    def run_(self, x):
        self._n = getattr(self, "_n", 0) + 1
        if self._n > 3 and self._n % 4 == 0:
            raise RuntimeError("planted failure")
        return orig(self, x)
    return run_


@pytest.mark.parametrize("fault", [_stale, _altered, _raises])
@pytest.mark.parametrize("ref,nodes", CASES, ids=IDS)
def test_new_model_fault_is_caught(test_refs, monkeypatch, ref, nodes,
                                   fault):
    monkeypatch.setattr(Session, "run", fault(Session.run))
    res = run_small(cell(ref, nodes))
    assert res["attempted"] >= 2
    assert res["correct"] is False


def test_new_op_kinds_reach_the_work_line(test_refs):
    """The merged table prices the reference's own op: inception's
    concats move their bytes and add no FLOP."""
    ops, ir = run.op_tables(ref_inception)
    assert ir["CONCAT"] == "concat" and ir["CONV"] == "conv"
    table = ref_inception.layers(ref_inception.CONFIG)
    cat = next(l for l in table if l["op"] == "concat")
    assert work.flops(cat, ops) == 0
    assert work.bytes_moved(cat, 4, ops) == 4 * 2 * 16 * 16 * 56
    s = work.summary(table, 4, ops)
    assert s["flops"] == sum(work.flops(l, ops) for l in table)
    with pytest.raises(ValueError, match="unknown layer op"):
        work.summary(table, 4)


@pytest.mark.parametrize("key", ["OPS", "IR_OPS"])
def test_an_op_name_already_taken_is_refused(key):
    taken = {"OPS": {"conv": work.OPS["fc"]}, "IR_OPS": {"FC": "conv"}}
    ref = SimpleNamespace(__name__="chipbench.models.clash",
                          **{key: taken[key]})
    with pytest.raises(run.BenchError, match="redefines"):
        run.op_tables(ref)


def test_two_tensor_op_draws_one_key_per_tensor():
    """A layer of several tensors splits its own key, one per tensor,
    each scaled by its fan-in; the layers around it keep their keys."""
    glu = work.Op(lambda l: ((l["in_c"], l["out_c"]), (l["in_c"], 3)),
                  lambda l: 0, lambda l, b: 0)
    ops = {**work.OPS, "glu": glu}
    table = [work.layer("a", "fc", 1, 1, 4, 6),
             work.layer("p", "pool", 2, 2, 6, 6, 2, 2),
             work.layer("g", "glu", 1, 1, 6, 5),
             work.layer("b", "fc", 1, 1, 5, 2)]
    ws = run.make_weights(table, SEED, "float32", ops)
    keys = split(run.seed_key(SEED), 4)
    sub = split(keys[2], 2)

    def want(k, shape):   # jitted, as the harness draws them
        return np.asarray(jax.jit(
            lambda k: normal(k, shape) / math.sqrt(shape[0]))(k))

    assert ws[1] is None and isinstance(ws[2], tuple)
    np.testing.assert_array_equal(ws[0], want(keys[0], (4, 6)))
    np.testing.assert_array_equal(ws[2][0], want(sub[0], (6, 5)))
    np.testing.assert_array_equal(ws[2][1], want(sub[1], (6, 3)))
    np.testing.assert_array_equal(ws[3], want(keys[3], (5, 2)))


# sha256 of the weights (every array's bytes, in table order) and of the
# first image of the pool, at width 32 and seed 2**31 + 99, as the
# harness drew them before references gave their own inputs and ops
PINNED = {
    "resnet101_224": (
        "077bc965398cd596b7daa993ac1d6c2dee7e581faa26e3c090aea93d267fbe0a",
        "d7b6da09e023858fc81a7346c607c79d64c4022ed239a969efa66b51750f4380"),
    "mobilenet_v1_224": (
        "9a18327b3fff420c75af201fe4e58939e273996663339c9facc71b336e19a2d2",
        "d7b6da09e023858fc81a7346c607c79d64c4022ed239a969efa66b51750f4380"),
    "resnet101_224_mesh4": (
        "077bc965398cd596b7daa993ac1d6c2dee7e581faa26e3c090aea93d267fbe0a",
        "d7b6da09e023858fc81a7346c607c79d64c4022ed239a969efa66b51750f4380"),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_image_cells_draw_what_they_drew(name):
    cfg = json.loads((run.BENCH / "configs" / f"{name}.json").read_text())
    cfg["image_size"] = 32
    ref = run.reference(cfg)
    ops, _ = run.op_tables(ref)
    h = hashlib.sha256()
    for w in run.make_weights(ref.layers(cfg), SEED, cfg["dtype"], ops):
        if w is not None:
            h.update(np.asarray(w).tobytes())
    mix = dict(traffic.load("closed1"), image_pool=4)
    pool = traffic.pool(mix, SEED, lambda rng, n: ref.inputs(cfg, rng, n))
    assert pool.shape == (4, 32, 32, 3) and pool.dtype == np.float32
    assert (h.hexdigest(), hashlib.sha256(pool[0].tobytes()).hexdigest()) \
        == PINNED[name]
