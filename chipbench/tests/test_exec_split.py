"""The split of request time into the executor's spans
(``chipbench/exec_split.py``), on the hand-made trace of ``test_trace.py``
with executor spans added, every number worked out in the comments; and
the accepted per-layer metrics, which must read the same with the
executor's spans in the trace as without."""
import copy
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench.exec_split import EXEC_SPANS, ExecSplit
from chipbench.metrics import (collective_ms, conv_roofline,
                               device_idle_share, mfu, programs_per_image)
from chipbench.tests.test_trace import HAND
from chipbench.trace import BETWEEN, Trace

# The requests are 0-100 and 130-200 ns; `session_run` 10-90 and
# 140-185.  Inside each, the executor's spans as [start, duration]:
#   first:  mesh.request 11-89; geometry 11-13, 18-20; lookup 13-14,
#           20-24 with a build 21-23; launch 14-18, 24-27; wait 60-88
#   second: mesh.request 141-184; geometry 141-143; lookup 143-144;
#           launch 144-150; wait 160-183
EXEC = {
    "mesh.request": [[11, 78], [141, 43]],
    "mesh.geometry": [[11, 2], [18, 2], [141, 2]],
    "mesh.lookup": [[13, 1], [20, 4], [143, 1]],
    "mesh.build": [[21, 2]],
    "mesh.launch": [[14, 4], [24, 3], [144, 6]],
    "mesh.wait": [[60, 28], [160, 23]],
}


def with_exec(reduced):
    out = copy.deepcopy(reduced)
    out["host"].update(copy.deepcopy(EXEC))
    return out


@pytest.fixture
def split():
    return ExecSplit(Trace(with_exec(HAND)))


def test_split_per_image(split):
    got = split.split()
    # geometry 2+2+2, launch 4+3+6, wait 28+23, lookup 1+4+1, request
    # 78+43, session_run 80+45 ns, over 2 images, in ms
    assert got["geometry_ms"] == pytest.approx(3e-6)
    assert got["launch_ms"] == pytest.approx(6.5e-6)
    assert got["wait_ms"] == pytest.approx(25.5e-6)
    assert got["lookup_ms"] == pytest.approx(3e-6)
    assert got["request_ms"] == pytest.approx(60.5e-6)
    # 60.5 - 3 - 6.5 - 25.5
    assert got["executor_other_ms"] == pytest.approx(25.5e-6)
    assert got["session_run_ms"] == pytest.approx(62.5e-6)
    assert got["request_cover_of_session_run"] == pytest.approx(121 / 125)
    assert got["per_image"] == {
        "mesh.request": 1.0, "mesh.geometry": 1.5, "mesh.lookup": 1.5,
        "mesh.build": 0.5, "mesh.launch": 1.5, "mesh.wait": 1.0}
    # device 0 launches jit_f at 25 and jit_h at 150 inside requests
    assert got["programs_by_kind"] == {"jit_f": 0.5, "jit_h": 0.5}


def test_idle_gaps_inner(split):
    # The gap midpoints of test_trace.py's test_breakdown: at 15 on both
    # devices the host is in the first launch (14-18), not just in
    # session_run; at 105 between the requests; at 187 and 95 in
    # fetch_output, after the mesh.request spans end
    got = {k: v for k, v in split.idle_by_inner_span()}
    assert got == pytest.approx({"fetch_output": 62.5e-9,
                                 BETWEEN: 45e-9, "mesh.launch": 30e-9})
    # all of session_run's idle time lies under an executor span
    assert split.split()["idle_session_run_under_exec_span"] == \
        pytest.approx(1.0)


def test_span_outside_the_window_is_not_counted():
    reduced = with_exec(HAND)
    # a launch between the requests, and one that runs past the first
    # request's end: only 98-100 of it counts
    reduced["host"]["mesh.launch"] += [[105, 5], [98, 4]]
    reduced["host"]["mesh.launch"].sort()
    sp = ExecSplit(Trace(reduced))
    assert sp.count("mesh.launch") == 4
    assert sp.seconds("mesh.launch") == pytest.approx(15e-9)


def test_without_executor_spans_no_split():
    assert ExecSplit(Trace(copy.deepcopy(HAND))).split() == {}


def _read_all(reduced, **ctx):
    c = SimpleNamespace(trace=Trace(reduced), **ctx)
    return [m.read(c) for m in (device_idle_share, programs_per_image,
                                collective_ms, conv_roofline, mfu)]


def test_accepted_metrics_read_the_same_with_executor_spans():
    ctx = dict(model_flops=1000, conv_min_s=12e-9,
               peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    assert _read_all(with_exec(HAND), **ctx) == _read_all(HAND, **ctx)
    tr = Trace(with_exec(HAND))
    assert tr.idle_by_span() == Trace(HAND).idle_by_span()
    assert set(EXEC_SPANS) <= set(tr.host)


def _fixture():
    path = (Path(__file__).parent / "fixtures" /
            "mobilenet_v1_224.chip1.exec.json.gz")
    return json.loads(gzip.decompress(path.read_bytes()))


def test_recorded_chip_trace_with_executor_spans():
    """Three requests of `exec_split.py`'s first traced window of
    `mobilenet_v1_224.chip1.closed1` on a TPU v5e, with the executor's
    profiler-sink tracer installed.  The device's record ends before the
    third request's answer is on the host, so the window holds the first
    two.  The expected values were worked out apart from `exec_split.py`,
    on a boolean timeline of every nanosecond of the window."""
    reduced = _fixture()
    sp = ExecSplit(Trace(reduced))
    assert sp.tr.images == 2
    got = sp.split()
    assert got["geometry_ms"] == pytest.approx(1.7792585)
    assert got["launch_ms"] == pytest.approx(12.158953)
    assert got["wait_ms"] == pytest.approx(0.33867)
    assert got["lookup_ms"] == pytest.approx(0.6754245)
    assert got["executor_other_ms"] == pytest.approx(3.5745165)
    assert got["request_cover_of_session_run"] == pytest.approx(
        0.9985358750877427)
    assert got["per_image"] == {
        "mesh.request": 1.0, "mesh.geometry": 24.0, "mesh.lookup": 48.0,
        "mesh.build": 0.0, "mesh.launch": 48.0, "mesh.wait": 1.0}
    assert got["programs_by_kind"] == {"jit_stage_compute": 24.0,
                                       "jit_stage_gather": 24.0}
    assert dict(got["idle_gaps_inner"]) == pytest.approx({
        "mesh.request": 0.014321594, "mesh.launch": 0.010409749,
        "mesh.geometry": 0.010095629, "mesh.lookup": 0.001329304})
    assert got["idle_session_run_under_exec_span"] == pytest.approx(1.0)


def test_recorded_chip_trace_reads_the_same_without_executor_spans():
    """The accepted metrics read the same on the recorded trace with the
    executor's spans as with them taken out."""
    with_spans = _fixture()
    without = copy.deepcopy(with_spans)
    for name in EXEC_SPANS:
        del without["host"][name]
    ctx = dict(model_flops=1_137_530_880, conv_min_s=1e-4,
               peak={"flops_per_s": 197e12})
    got = _read_all(with_spans, **ctx)
    assert got == _read_all(without, **ctx)
    assert got[0] == pytest.approx(96.38068962208133)
    assert got[1] == 48.0 and got[2] is None
