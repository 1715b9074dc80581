"""A test-only reference: the program's ``inception`` graph at width 32,
a stem conv, two GoogLeNet-style modules of four branches joined by
CONCAT, a global max pool and a classifier.  The harness's op table has
no concat, so this module declares it, with the IR type that maps to it.
A request is one ``[32, 32, 3]`` image."""
from __future__ import annotations

from typing import Dict, List

from chipbench.models.image import inputs, model_args  # noqa: F401
from chipbench.work import Op, layer, moved

CONFIG = {"name": "inception_small", "model": "inception",
          "reference": "ref_inception", "image_size": 32, "in_channels": 3,
          "num_classes": 100, "stem": 32,
          # per module: 1x1, 3x3 reduce, 3x3, 5x5 reduce, 5x5, pool proj
          "modules": [[16, 12, 24, 4, 8, 8], [24, 16, 32, 6, 12, 12]],
          "dtype": "float32", "precision": "highest"}

#: a channel concatenation: no weights and no FLOP, its inputs read once
#: (their channels sum to ``in_c``) and its output written once
OPS = {"concat": Op(lambda l: (), lambda l: 0, lambda l, b: moved(l, b))}
IR_OPS = {"CONCAT": "concat"}


def layers(cfg) -> List[Dict]:
    h = w = cfg["image_size"]
    out = [layer("stem", "conv", h, w, cfg["in_channels"], cfg["stem"],
                 3, 2, 1)]
    h, c = out[-1]["out_h"], cfg["stem"]
    for i, (c1, c3r, c3, c5r, c5, cp) in enumerate(cfg["modules"]):
        n = f"i{i + 1}"
        cat = c1 + c3 + c5 + cp
        out += [layer(f"{n}.1x1", "conv", h, h, c, c1),
                layer(f"{n}.3r", "conv", h, h, c, c3r),
                layer(f"{n}.3x3", "conv", h, h, c3r, c3, 3, 1, 1),
                layer(f"{n}.5r", "conv", h, h, c, c5r),
                layer(f"{n}.5x5", "conv", h, h, c5r, c5, 5, 1, 2),
                layer(f"{n}.pool", "pool", h, h, c, c, 3, 1, 1),
                layer(f"{n}.pp", "conv", h, h, c, cp),
                layer(f"{n}.cat", "concat", h, h, cat, cat)]
        c = cat
    out.append(layer("avgpool", "pool", h, h, c, c, h, h, 0))
    out.append(layer("fc", "fc", 1, 1, c, cfg["num_classes"]))
    return out


def forward(cfg, ws, x, precision: str):
    """Logits ``[N, num_classes]`` of images ``x`` ``[N, H, W, C]``."""
    import jax.numpy as jnp

    from chipbench import numerics as nm
    it = iter(ws)

    def conv(x, s=1, p=0):
        return nm.conv(x, next(it), s, p, precision)

    x = conv(x, 2, 1)
    for _ in cfg["modules"]:
        b1 = conv(x)
        b3 = conv(conv(x), 1, 1)
        b5 = conv(conv(x), 1, 2)
        bp = conv(nm.maxpool(x, 3, 1, 1))
        x = jnp.concatenate([b1, b3, b5, bp], axis=-1)
    x = nm.maxpool(x, x.shape[1], x.shape[1], 0)
    return nm.dot(x.reshape(x.shape[0], -1), next(it), precision)
