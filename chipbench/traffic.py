"""The one traffic generator: it reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and drives a ``serve(image)`` callable.

Parameters a mix file gives:

- ``arrival``: ``"closed"``, a closed loop: each client sends its next
  request when the answer to the previous one is on the host.  A request
  is due when the previous one completes, so its latency runs from then.
- ``clients``: how many clients; the loop runs one.
- ``image_pool``: how many distinct images are drawn from the seed before
  the window.  Requests take them in an order drawn from the same seed,
  round and round; the compute does not depend on the values.

Every seed gives the same number of requests of the same size in a
window, so a seed changes the values and their order, never the work.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

MIXES = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> Dict:
    mix = json.loads((MIXES / f"{name}.json").read_text())
    if mix.get("arrival") != "closed" or mix.get("clients") != 1:
        raise ValueError(f"traffic {name!r}: the generator runs a closed "
                         f"loop of one client, got {mix}")
    if int(mix.get("image_pool", 0)) < 1:
        raise ValueError(f"traffic {name!r}: image_pool must be >= 1")
    return mix


def image_pool(mix: Dict, shape, seed: int) -> np.ndarray:
    """``[image_pool, *shape]`` float32 standard-normal images."""
    rng = np.random.default_rng([seed, 1])
    return rng.standard_normal((mix["image_pool"], *shape),
                               dtype=np.float32)


def order(mix: Dict, seed: int) -> np.ndarray:
    """The order in which requests take the pool's images."""
    return np.random.default_rng([seed, 2]).permutation(mix["image_pool"])


@dataclasses.dataclass
class Request:
    image: int            # index into the pool
    due_s: float          # host clock when the request was due
    done_s: float         # host clock when its answer was on the host
    answer: Optional[np.ndarray]
    error: Optional[str]  # why the request failed, or None

    @property
    def latency_s(self) -> float:
        return self.done_s - self.due_s


def closed_loop(serve: Callable, pool: np.ndarray, seq: np.ndarray,
                until_s: float, first: int = 0) -> List[Request]:
    """Send requests back to back until the host clock passes ``until_s``.
    ``serve(image)`` returns the answer, or raises, or returns None for
    an answer that the system itself reports as failed.  Request ``i``
    takes image ``seq[(first + i) % len(seq)]``."""
    out: List[Request] = []
    i = first
    while True:
        due = time.perf_counter()
        if due >= until_s:
            return out
        idx = int(seq[i % len(seq)])
        try:
            ans, err = serve(pool[idx]), None
            if ans is None:
                err = "the system reported the answer as failed"
        except Exception as exc:  # a failed request is counted, not fatal
            ans, err = None, repr(exc)
        out.append(Request(idx, due, time.perf_counter(), ans, err))
        i += 1
