"""What the image references share: the program's graph builders take
one width, and a request is one standard-normal float32 image."""
from __future__ import annotations

from typing import Dict

import numpy as np


def model_args(cfg) -> Dict:
    """Keyword arguments of the program's graph builder."""
    return {"width": cfg["image_size"]}


def inputs(cfg, rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` images ``[n, image_size, image_size, in_channels]``."""
    s = cfg["image_size"]
    return rng.standard_normal((n, s, s, cfg["in_channels"]),
                               dtype=np.float32)
