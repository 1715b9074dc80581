"""``programs_per_image``: XLA module launches on the first device per
traced image, counting the launches that start inside a request span.
Layer: mesh executor dispatch."""


def read(ctx):
    tr = ctx.trace
    if tr.images == 0 or not tr.devices[0]["modules"]:
        return None
    return tr.modules_in_requests(0) / tr.images
