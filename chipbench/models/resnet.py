"""Plain ResNet reference: the bottleneck network of He et al. 2016
("Deep Residual Learning for Image Recognition", arXiv:1512.03385,
Table 1) in ``jax.numpy``, with no kernels, partitioning or caching.

The departures from the paper are the ones the configuration file lists,
and the program shares each of them: no BatchNorm (taken as folded into
the conv weights), no ReLU, no biases, the stride of a downsampling block
on its 3x3 conv (ResNet v1.5), and the final global pool a max pool.

``layers(cfg)`` is the layer table in serving order; ``forward`` takes
the weights in that table's order (every conv, then the classifier).
"""
from __future__ import annotations

from typing import Dict, List

from chipbench.work import layer as _layer


def layers(cfg) -> List[Dict]:
    """The network's layers in the order they run, as plain dicts."""
    h = w = cfg["image_size"]
    stem, sp = cfg["stem"], cfg["stem_pool"]
    out = [_layer("conv1", "conv", h, w, cfg["in_channels"], stem["out"],
                  stem["kernel"], stem["stride"], stem["pad"])]
    h, w, c = out[-1]["out_h"], out[-1]["out_w"], stem["out"]
    out.append(_layer("maxpool", "pool", h, w, c, c, sp["kernel"],
                      sp["stride"], sp["pad"]))
    h, w = out[-1]["out_h"], out[-1]["out_w"]
    for si, st in enumerate(cfg["stages"]):
        for r in range(st["blocks"]):
            s = st["stride"] if r == 0 else 1
            n = f"s{si}r{r}"
            out.append(_layer(n + "a", "conv", h, w, c, st["width"]))
            out.append(_layer(n + "b", "conv", h, w, st["width"],
                              st["width"], 3, s, 1))
            oh, ow = out[-1]["out_h"], out[-1]["out_w"]
            out.append(_layer(n + "c", "conv", oh, ow, st["width"],
                              st["out"]))
            if s != 1 or c != st["out"]:
                out.append(_layer(n + "s", "conv", h, w, c, st["out"], 1, s))
            out.append(_layer(n + "+", "add", oh, ow, st["out"], st["out"]))
            h, w, c = oh, ow, st["out"]
    out.append(_layer("avgpool", "pool", h, w, c, c, h, h, 0))
    out.append(_layer("fc", "fc", 1, 1, c, cfg["num_classes"]))
    return out


def forward(cfg, ws, x, precision: str):
    """Logits ``[N, num_classes]`` of images ``x`` ``[N, H, W, C]``."""
    from chipbench import numerics as nm

    it = iter(ws)

    def conv(x, s, p):
        return nm.conv(x, next(it), s, p, precision)

    stem, sp = cfg["stem"], cfg["stem_pool"]
    x = conv(x, stem["stride"], stem["pad"])
    x = nm.maxpool(x, sp["kernel"], sp["stride"], sp["pad"])
    c = stem["out"]
    for st in cfg["stages"]:
        for r in range(st["blocks"]):
            s = st["stride"] if r == 0 else 1
            y = conv(conv(conv(x, 1, 0), s, 1), 1, 0)
            skip = conv(x, s, 0) if s != 1 or c != st["out"] else x
            x, c = y + skip, st["out"]
    x = nm.maxpool(x, x.shape[1], x.shape[1], 0)
    return nm.dot(x.reshape(x.shape[0], -1), next(it), precision)
