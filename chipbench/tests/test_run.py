"""The harness driven on the CPU at width 32, past its look for a TPU:
sound runs come out correct, and each fault that a cell can have, planted
under the timed path, comes out not correct."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import control, run
from repro.runtime import mesh_exec
from repro.runtime.session import Session

WIDTH = 32
PEAKS = {"cpu": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}
SEED = 2**31 + 99
CELLS = ["resnet101_224.chip1.closed1", "mobilenet_v1_224.chip1.closed1",
         "resnet101_224.mesh4.closed1", "mobilenet_v1_224.mesh4.closed1"]
#: per cell, the configuration keys that cut it to a CPU size; mobilenet's
#: four-node plan exchanges halos by ppermute from width 48, not at 32
SMALL = {CELLS[0]: {"image_size": WIDTH}, CELLS[1]: {"image_size": WIDTH},
         CELLS[2]: {"image_size": WIDTH}, CELLS[3]: {"image_size": 64}}


def small(cell: str):
    c = run.load_cell(cell)
    c.config.update(SMALL[cell])
    c.mix["image_pool"] = 4
    return c


def run_small(cell: str, seconds: float = 0.5) -> dict:
    c = small(cell)
    devices = jax.devices()[:c.cell["chips"]]
    return run.run_cell(c, SEED, seconds, False, devices,
                        time.perf_counter(), peaks=PEAKS)


@pytest.fixture
def fresh_programs():
    """Programs traced under a planted fault must not outlive it."""
    mesh_exec.clear_mesh_program_cache()
    yield
    mesh_exec.clear_mesh_program_cache()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"latency_p50_ms", "latency_p90_ms",
                                   "images_per_s", "setup_s"}
    assert res["device"]["count"] == small(cell).cell["chips"]
    assert res["checks"]["rel_err"]["value"] <= \
        res["checks"]["rel_err"]["limit"]


def _stale(orig):
    """The state left unchanged: every request gets the first answer."""
    def run_(self, x):
        if not hasattr(self, "_first"):
            self._first = orig(self, x)
        return self._first
    return run_


def _altered(orig):
    """One logit altered where the answer is produced."""
    def run_(self, x):
        out, stats = orig(self, x)
        return out.at[..., 7].add(1e-3 * jnp.max(jnp.abs(out))), stats
    return run_


def _raises(orig):
    """Every fourth request fails."""
    def run_(self, x):
        self._n = getattr(self, "_n", 0) + 1
        if self._n > 3 and self._n % 4 == 0:
            raise RuntimeError("planted failure")
        return orig(self, x)
    return run_


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_stale, _altered, _raises])
def test_fault_in_the_answer_is_caught(monkeypatch, fault, cell):
    monkeypatch.setattr(Session, "run", fault(Session.run))
    res = run_small(cell)
    assert res["attempted"] >= 2
    assert res["correct"] is False


@pytest.mark.parametrize("cell", CELLS[2:])
def test_exchange_left_out_is_caught(monkeypatch, fresh_programs, cell):
    """The halo exchange between chips returns zeros."""
    calls = []

    def left_out(x, axis_name, perm):
        calls.append(x.shape)
        return jnp.zeros_like(x)

    monkeypatch.setattr(jax.lax, "ppermute", left_out)
    res = run_small(cell)
    assert calls
    assert res["correct"] is False
    assert res["checks"]["rel_err"]["value"] > \
        res["checks"]["rel_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_the_programs_place_is_not_correct(cell):
    """The plain reference one precision lower, served in the ``Session``'s
    place through the harness's own check, comes out not correct against
    the cell's own limit; the program on the same seed comes out correct."""
    c = small(cell)
    res = run.run_cell(c, SEED, 0.5, False,
                       jax.devices()[:c.cell["chips"]],
                       time.perf_counter(), peaks=PEAKS, control=True)
    assert res["correct"] is False
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["rel_err"]["value"] > c.limits["rel_err"]["limit"]


def test_control_reads_apart_from_the_program():
    """``control.py``'s readings: on every seed the program comes out
    correct and the control not, through ``run_cell``."""
    c = small(CELLS[0])
    out = control.readings(c, [SEED, SEED + 1], 0.5, jax.devices()[:1],
                           peaks=PEAKS)
    assert out["program_all_correct"] and out["control_none_correct"]
    assert out["lower"] <= out["limit"] < out["upper"]


@pytest.mark.parametrize("cell", CELLS)
def test_limit_lies_between_its_readings(cell):
    """Each limit lies at least 3x above the program's largest reading and
    3x below the control's smallest, as read on the chip."""
    r = run.load_cell(cell).limits["rel_err"]
    assert 3 * r["lower"] <= r["limit"] <= r["upper"] / 3


def _cli(cwd, *args) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_no_result():
    p = _cli(run.ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_metric_selection_follows_benchmark_json():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    names = {c: [m["name"] for m in run.per_layer_metrics(spec, c)]
             for c in CELLS}
    assert "collective_ms" not in names[CELLS[0]]
    assert "collective_ms" in names[CELLS[2]]
    assert "collective_ms" in names[CELLS[3]]
    assert "conv_roofline" in names[CELLS[1]]
    with pytest.raises(run.BenchError, match="no workload"):
        run.load_cell("no.such.cell")


def test_each_pair_of_config_and_traffic_once():
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    pairs = [(c["config"], c["traffic"]) for c in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    for cell in CELLS:
        c = run.load_cell(cell)
        assert c.config["nodes"] == c.cell["chips"]


def test_config_nodes_other_than_chips_is_refused(monkeypatch):
    read = run._json

    def four_nodes(path):
        out = read(path)
        return dict(out, nodes=4) if path.parent.name == "configs" else out

    monkeypatch.setattr(run, "_json", four_nodes)
    with pytest.raises(run.BenchError, match="plans 4 nodes"):
        run.load_cell(CELLS[0])
