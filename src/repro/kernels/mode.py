"""The one interpret-mode selector behind every Pallas entry point."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` when given; otherwise interpret-mode Pallas on every
    backend except a TPU, where the kernels compile natively."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
