"""Device time of the UDA accumulation kernels (the ops that
``bench/matchers/`` assigns to the layer ``uda``) per answer, per device,
over the traced window."""


def read(ctx):
    return ctx.layer_ms_per_answer("uda")
