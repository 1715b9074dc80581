"""``mfu``: the model's FLOP per image times the images completed in the
traced window, over the window times the chips times the chip's peak.
Layer: model step.

The peak is the chip's published bfloat16 matrix peak, its only
published one, though the program contracts at float32."""


def read(ctx):
    tr = ctx.trace
    if tr.images == 0 or tr.window_s <= 0:
        return None
    done = ctx.model_flops * tr.images
    return 100.0 * done / (tr.window_s * len(tr.devices)
                           * ctx.peak["flops_per_s"])
