"""The trace reduction and the per-layer metric readers, on a trace made
by hand, whose every number is worked out in the comments."""
from types import SimpleNamespace

import pytest

from chipbench.metrics import (collective_ms, conv_roofline,
                               device_idle_share, mfu, programs_per_image)
from chipbench.trace import BETWEEN, Trace

# a conv shard kernel and the classifier's matmul kernel, as a TPU names
# its ops: by their HLO text
KERNEL_OP = ('%fn1.5 = f32[8,8,4]{2,1,0:T(8,128)} custom-call('
             'f32[1,10,10,2]{3,2,1,0:T(8,128)} %bitcast.6, '
             'f32[3,3,2,4]{3,2,1,0:T(4,128)} %copy.4), '
             'custom_call_target="tpu_custom_call", '
             'frontend_attributes={kernel_metadata={}}')
MATMUL_OP = ('%fn1.9 = f32[1,10]{1,0} custom-call(f32[1,4]{1,0} %x.1, '
             'f32[4,10]{1,0} %w.2), custom_call_target="tpu_custom_call"')

# Two requests, 0-100 ns and 130-200 ns: the window is 200 ns.
HAND = {
    "host": {
        "request": [[0, 100], [130, 70]],
        "put_input": [[0, 10], [130, 10]],
        "session_run": [[10, 80], [140, 45]],
        "fetch_output": [[90, 10], [185, 15]],
    },
    "devices": [
        {   # busy 30-60, 150-180, 195-200 (clipped): 65 ns
            "ops": [["fusion.1", 30, 20], ["all-gather-start.3", 40, 20],
                    [KERNEL_OP, 150, 30], ["copy.4", 195, 10]],
            # launches at 25 and 150 lie in requests; 105 lies between
            "modules": [["jit_f", 25, 40], ["jit_g", 105, 5],
                        ["jit_h", 150, 30]],
        },
        {   # busy 30-40, 150-200: 60 ns; the matmul is no conv kernel
            "ops": [["all-gather-done.1", 30, 10], ["fusion.2", 150, 50],
                    [MATMUL_OP, 160, 10]],
            "modules": [["jit_f", 30, 10]],
        },
    ],
}


@pytest.fixture
def ctx():
    return SimpleNamespace(
        trace=Trace(HAND), model_flops=1000, conv_min_s=12e-9,
        peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def test_window_and_busy(ctx):
    tr = ctx.trace
    assert tr.images == 2
    assert tr.window_s == pytest.approx(200e-9)
    assert tr.busy_s(0) == pytest.approx(65e-9)
    assert tr.busy_s(1) == pytest.approx(60e-9)
    assert tr.gaps(0) == [(0, 30), (60, 150), (180, 195)]


def test_device_idle_share(ctx):
    # (135/200 + 140/200) / 2
    assert device_idle_share.read(ctx) == pytest.approx(68.75)


def test_programs_per_image(ctx):
    # device 0: two of its three launches start inside a request
    assert programs_per_image.read(ctx) == pytest.approx(1.0)


def test_collective_ms(ctx):
    # 20 ns on device 0 + 10 ns on device 1, over 2 devices and 2 images
    assert collective_ms.read(ctx) == pytest.approx(7.5e-6)


def test_conv_roofline(ctx):
    # 12 ns least time per image, 2 images, 30 ns of conv kernel
    assert conv_roofline.read(ctx) == pytest.approx(80.0)


def test_mfu(ctx):
    # 2 images * 1000 FLOP / (200 ns * 2 chips * 1e12 FLOP/s)
    assert mfu.read(ctx) == pytest.approx(0.5)


def test_breakdown(ctx):
    tr = ctx.trace
    # gap midpoints: device 0 at 15 (session_run), 105 (between the
    # requests), 187 (fetch_output); device 1 at 15 (session_run), 95
    # (fetch_output); averaged over the 2 devices
    got = {k: v for k, v in tr.idle_by_span()}
    assert got == pytest.approx({"fetch_output": 62.5e-9,
                                 BETWEEN: 45e-9, "session_run": 30e-9})
    assert [k for k, _ in tr.idle_by_span()][0] == "fetch_output"
    top = dict(tr.top_ops())
    assert top == pytest.approx({
        "fusion": 35e-9, "fn1:tpu_custom_call": 20e-9,
        "all-gather-start": 10e-9, "all-gather-done": 5e-9,
        "copy": 2.5e-9})


def test_empty_trace_reads_nothing():
    empty = {"host": {k: [] for k in HAND["host"]},
             "devices": [{"ops": [], "modules": []}]}
    c = SimpleNamespace(trace=Trace(empty), model_flops=1, conv_min_s=1.0,
                        peak={"flops_per_s": 1.0})
    for m in (device_idle_share, programs_per_image, collective_ms,
              conv_roofline, mfu):
        assert m.read(c) is None


def test_recorded_chip_trace():
    """Three requests of a traced `resnet101_224.chip1.closed1` run on a
    TPU v5e, cut from the reduced trace.  The device's record ends before
    the third request's answer is on the host, so the window holds the
    first two.  The expected values were worked out apart from
    `trace.py`, on a boolean timeline of every nanosecond of the window."""
    import json
    from pathlib import Path
    reduced = json.loads((Path(__file__).parent / "fixtures" /
                          "resnet101_224.chip1.json").read_text())
    tr = Trace(reduced)
    assert tr.images == 2
    assert tr.window_s == pytest.approx(196_670_310e-9)
    c = SimpleNamespace(trace=tr, model_flops=15_613_648_896,
                        conv_min_s=3.634046593406589e-4,
                        peak={"flops_per_s": 197e12})
    assert device_idle_share.read(c) == pytest.approx(96.88909220715624)
    assert programs_per_image.read(c) == 311.0
    # one conv kernel op per conv layer and image: 104 in resnet101
    assert len(tr.ops(0, conv_roofline.KERNEL)) == 208
    assert tr.op_seconds(conv_roofline.KERNEL) == pytest.approx(
        3_166_970e-9)
    assert conv_roofline.read(c) == pytest.approx(
        100 * 2 * 3.634046593406589e-4 / 3_166_970e-9)
    # one chip: no collectives to read
    assert collective_ms.read(c) is None


def test_recorded_four_chip_trace():
    """Two requests of a traced `resnet101_224.mesh4.closed1` run on four
    TPU v5e chips, of which the window holds the first, worked out as
    above: the collectives are the ops whose HLO text starts with one
    (`%all-gather.4 = ...`), on every device."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).parent / "fixtures" / "resnet101_224.mesh4.json.gz"
    reduced = json.loads(gzip.decompress(path.read_bytes()))
    tr = Trace(reduced)
    assert (tr.images, len(tr.devices)) == (1, 4)
    assert tr.window_s == pytest.approx(303_441_422e-9)
    c = SimpleNamespace(trace=tr, model_flops=15_613_648_896,
                        conv_min_s=3.634046593406589e-4,
                        peak={"flops_per_s": 197e12})
    assert device_idle_share.read(c) == pytest.approx(98.69566555089501)
    assert programs_per_image.read(c) == 302.0
    assert collective_ms.read(c) == pytest.approx(1.77075625)
    for d in range(4):
        assert len(tr.ops(d, conv_roofline.KERNEL)) == 119
    assert conv_roofline.read(c) == pytest.approx(
        100 * 3.634046593406589e-4 / 2_543_765e-9)


def test_a_device_record_that_stops_early_ends_the_window():
    """Device 1's record stops inside the second request: the window
    holds only the first."""
    cut = {"host": HAND["host"], "devices": [
        HAND["devices"][0],
        {"ops": [["fusion.1", 30, 10], ["fusion.2", 150, 20]],
         "modules": [["jit_f", 30, 10]]}]}
    tr = Trace(cut)
    assert tr.images == 1
    assert tr.window_s == pytest.approx(100e-9)
    assert tr.busy_s(0) == pytest.approx(30e-9)


def test_recorded_four_chip_trace_whose_device_0_stops_early():
    """The last three requests of the traced four-chip run the fixture
    above is cut from (op names shortened): device 0's record holds 284
    of the 302 launches of the second and none of the third, the other
    devices' hold all of them.  The window keeps the first request, whole
    on every device."""
    import gzip
    import json
    from pathlib import Path
    path = (Path(__file__).parent / "fixtures" /
            "resnet101_224.mesh4.tail.json.gz")
    reduced = json.loads(gzip.decompress(path.read_bytes()))
    starts = [[a for _, a, _ in d["modules"]] for d in reduced["devices"]]
    second, third = (reduced["host"]["request"][i][0] for i in (1, 2))
    assert [sum(second <= a < third for a in s) for s in starts] == \
        [284, 302, 302, 302]
    assert [sum(a >= third for a in s) for s in starts] == [0, 302, 302, 302]
    tr = Trace(reduced)
    assert tr.images == 1
    assert programs_per_image.read(SimpleNamespace(trace=tr)) == 302.0
