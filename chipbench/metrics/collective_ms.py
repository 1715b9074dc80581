"""``collective_ms``: device time of the collectives per image, averaged
over the cell's devices.  Layer: mesh executor collectives.

The names are XLA's op names for the collectives the mesh executor
emits (``all_gather`` re-layouts, the ``ppermute`` halo exchange), with
their asynchronous start/done halves.  A TPU names each op by its HLO
text, ``%all-gather.4 = ...``; an op that only takes a collective's
result as its operand does not match."""
import re

COLLECTIVE = re.compile(
    r"^%?(all-gather|collective-permute|all-reduce|reduce-scatter|"
    r"all-to-all)")


def read(ctx):
    tr = ctx.trace
    if tr.images == 0:
        return None
    t = tr.op_seconds(COLLECTIVE)
    if t == 0.0:
        return None
    return 1e3 * t / len(tr.devices) / tr.images
