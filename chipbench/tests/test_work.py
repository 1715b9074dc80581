"""Work counts of the configurations, pinned, and the program's layer
graphs checked against the plain references' tables."""
import json

import pytest

from chipbench import run, work
from chipbench.models import mobilenet_v1, resnet

CONFIGS = run.BENCH / "configs"

# (config, reference, layers, weights, FLOP per image)
PINNED = [
    ("resnet101_224", resnet, 140, 44_442_816, 15_613_648_896),
    ("resnet101_224_mesh4", resnet, 140, 44_442_816, 15_613_648_896),
    ("mobilenet_v1_224", mobilenet_v1, 29, 4_209_088, 1_137_530_880),
]


@pytest.mark.parametrize("name,ref,n_layers,n_weights,n_flops", PINNED)
def test_counts_are_pinned(name, ref, n_layers, n_weights, n_flops):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    table = ref.layers(cfg)
    s = work.summary(table, work.DTYPE_BYTES[cfg["dtype"]])
    assert (s["layers"], s["weights"], s["flops"]) == (
        n_layers, n_weights, n_flops)
    assert s["weight_bytes"] == 4 * n_weights


@pytest.mark.parametrize("name,ref,n_layers,n_weights,n_flops", PINNED)
def test_program_graph_matches_reference(name, ref, n_layers, n_weights,
                                         n_flops):
    from repro.configs.edge_models import EDGE_MODELS
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    graph = EDGE_MODELS[cfg["model"]](cfg["image_size"])
    run.check_graph(graph, ref.layers(cfg))
    assert sum(l.flops() for l in graph.layers) == n_flops


def test_graph_mismatch_is_refused():
    from repro.configs.edge_models import EDGE_MODELS
    cfg = json.loads((CONFIGS / "resnet101_224.json").read_text())
    graph = EDGE_MODELS["resnet101"](224)
    table = resnet.layers(cfg)
    table[5] = dict(table[5], s=2)
    with pytest.raises(run.BenchError, match="layer"):
        run.check_graph(graph, table)


def test_roofline_bound_of_a_layer():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    # 1x1 conv, 2x2x3 -> 2x2x5: 2*4*5*3 = 120 FLOP;
    # (12 in + 15 weights + 20 out) * 4 B = 188 B
    l = work.layer("pw", "conv", 2, 2, 3, 5)
    assert work.flops(l) == 120
    assert work.bytes_moved(l, 4) == 188
    assert work.min_seconds(l, 4, peak) == (18.8, "memory")
    # depthwise 3x3 over 4x4x2, pad 1: 2*16*2*9 = 576 FLOP;
    # (32 in + 18 weights + 32 out) * 4 B = 328 B
    d = work.layer("dw", "dwconv", 4, 4, 2, 2, 3, 1, 1)
    assert (work.flops(d), work.bytes_moved(d, 4)) == (576, 328)
    total, bound = work.conv_min_seconds([l, d], 4, peak)
    assert total == pytest.approx(18.8 + 32.8)
    assert bound == {"compute": 0, "memory": 2}
