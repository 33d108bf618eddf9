"""Device time of collective ops between chips per answer, per device,
over the traced window."""


def read(ctx):
    return ctx.layer_ms_per_answer("collective")
