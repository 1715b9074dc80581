"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

Interpret mode accepts kernels the chip's compiler refuses (unaligned
blocks, strided slices, VMEM overflow).  These cases compile the kernels
at the real widths of the edge models and the decode spec against a
described ``v5e:2x2`` topology — nothing runs, so no chip is needed.  The
topology is described inside a module fixture, never at import, so every
test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.conv2d import conv2d_shard
from repro.kernels.flash_attention import flash_decode_paged
from repro.kernels.ops import matmul_tiled

#: name -> (x shape, w shape, pads, stride, depthwise)
CONV_CASES = {
    # resnet conv1 7x7 s2 on the whole 224x224 image (a 1-node plan)
    "resnet_conv1_whole": ((224, 224, 3), (7, 7, 3, 64), (3, 3, 3, 3), 2,
                           False),
    # the same layer as the top InH shard of 4: output rows [0, 28) read
    # input rows [-3, 58), so the shard carries 58 rows and a top pad
    "resnet_conv1_top_of_4": ((58, 224, 3), (7, 7, 3, 64), (3, 0, 3, 3), 2,
                              False),
    "mobilenet_dw_s2_112": ((112, 112, 64), (3, 3, 1, 64), (1, 1, 1, 1), 2,
                            True),
    "resnet_proj_1x1_s2_56": ((56, 56, 256), (1, 1, 256, 512),
                              (0, 0, 0, 0), 2, False),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:    # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes, dtypes=None):
    dtypes = dtypes or [jnp.float32] * len(shapes)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text     # the Mosaic kernel is in there


@pytest.mark.parametrize("name", sorted(CONV_CASES))
def test_conv_shard_compiles(one_chip, name):
    xs, ws, pads, stride, dw = CONV_CASES[name]
    _compile(lambda x, w: conv2d_shard(x, w, pads=pads, stride=stride,
                                       depthwise=dw, interpret=False),
             one_chip, xs, ws)


def test_fc_matmul_compiles(one_chip):
    """The edge models' classifier head: 1x2048 @ 2048x1000."""
    _compile(lambda x, w: matmul_tiled(x, w, interpret=False),
             one_chip, (1, 2048), (2048, 1000))


def test_flash_decode_paged_compiles(one_chip):
    """The ``small`` decode spec (8 heads of 64) over 16 pages of 16."""
    bh, pages, ps, hd = 8, 16, 16, 64
    _compile(lambda q, k, v, t: flash_decode_paged(q, k, v, t, 20,
                                                   interpret=False),
             one_chip, (bh, hd), (bh, pages, ps, hd), (bh, pages, ps, hd),
             (pages,), dtypes=[jnp.float32] * 3 + [jnp.int32])
