"""``conv_roofline``: the conv shard kernel's share of its roofline.

Per image, the least time one chip could take for every conv, pointwise
and depthwise layer (``work.conv_min_seconds``: the larger of FLOP over
peak FLOP/s and bytes over HBM bytes/s, for the algorithm's own work),
over the device time of the kernel's events, summed over the cell's
devices.  Layer: kernels.

``KERNEL`` finds ``conv2d_shard``'s ``pallas_call`` among the trace's
ops, which are named by their HLO text.  The call has no ``name=``, and
its op takes the name of the jitted program around it (``%fn1.N`` on a
TPU v5e, JAX 0.9), so the pattern is the op's shape instead: a
``tpu_custom_call`` of two 4-D operands, the phase-split input and the
HWIO weights.  The only other Pallas kernel on the path, the classifier's
``matmul_tiled``, takes 2-D operands.  One such op per conv layer and
image was counted by hand in the first chip trace (104 for resnet101)."""
import re

KERNEL = re.compile(
    r"custom-call\(\w+\[\d+(?:,\d+){3}\]\{[^}]*\} \S+, "
    r"\w+\[\d+(?:,\d+){3}\]\{[^}]*\} \S+\), "
    r'custom_call_target="tpu_custom_call"')


def read(ctx):
    tr = ctx.trace
    if tr.images == 0:
        return None
    t = tr.op_seconds(KERNEL)
    if t == 0.0:
        return None
    return 100.0 * ctx.conv_min_s * tr.images / t
