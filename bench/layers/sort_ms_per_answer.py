"""Device time of XLA sort ops (group-id assignment, join probes, kernel
operand layout) per answer, per device, over the traced window."""


def read(ctx):
    return ctx.layer_ms_per_answer("sort")
