"""Plain MobileNet v1 reference: Howard et al. 2017 ("MobileNets",
arXiv:1704.04861, Table 1, width multiplier 1.0) in ``jax.numpy``, with
no kernels, partitioning or caching.

The departures from the paper are the ones the configuration file lists,
and the program shares each of them: no BatchNorm (taken as folded into
the conv weights), no ReLU, no biases, and the final global pool a max
pool.

``layers(cfg)`` is the layer table in serving order; ``forward`` takes
the weights in that table's order (every conv, then the classifier).
``model_args`` and ``inputs`` are the image references' own
(``chipbench/models/image.py``).
"""
from __future__ import annotations

from typing import Dict, List

from chipbench.models.image import inputs, model_args  # noqa: F401
from chipbench.work import layer as _layer


def layers(cfg) -> List[Dict]:
    """The network's layers in the order they run, as plain dicts."""
    h = w = cfg["image_size"]
    stem = cfg["stem"]
    out = [_layer("conv0", "conv", h, w, cfg["in_channels"], stem["out"],
                  stem["kernel"], stem["stride"], stem["pad"])]
    c = stem["out"]
    for i, (s, cout) in enumerate(cfg["blocks"]):
        h, w = out[-1]["out_h"], out[-1]["out_w"]
        out.append(_layer(f"dw{i + 1}", "dwconv", h, w, c, c, 3, s, 1))
        h, w = out[-1]["out_h"], out[-1]["out_w"]
        out.append(_layer(f"pw{i + 1}", "conv", h, w, c, cout))
        c = cout
    h = out[-1]["out_h"]
    out.append(_layer("avgpool", "pool", h, h, c, c, h, h, 0))
    out.append(_layer("fc", "fc", 1, 1, c, cfg["num_classes"]))
    return out


def forward(cfg, ws, x, precision: str):
    """Logits ``[N, num_classes]`` of images ``x`` ``[N, H, W, C]``."""
    from chipbench import numerics as nm

    it = iter(ws)
    stem = cfg["stem"]
    x = nm.conv(x, next(it), stem["stride"], stem["pad"], precision)
    for s, _ in cfg["blocks"]:
        x = nm.conv(x, next(it), s, 1, precision, groups=x.shape[-1])
        x = nm.conv(x, next(it), 1, 0, precision)
    x = nm.maxpool(x, x.shape[1], x.shape[1], 0)
    return nm.dot(x.reshape(x.shape[0], -1), next(it), precision)
