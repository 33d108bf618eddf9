"""The UDA kernels' share of their roofline: the least time the chip
could take for the UDA work the traced window's requests needed, over
the device time the kernels took.

The work is counted by ``bench/work/<kind>.py`` from each request's
selected tuples and output groups (never masked rows, padding or the
kernel's grid); each request's least time is the larger of its
operations over the peak FLOP/s and its bytes over the peak HBM
bandwidth (``bench/peaks.json``), summed over the requests.  The only
published compute peak of a v5e is the bf16 MXU rate, which f32 vector
work cannot reach, so the compute bound is loose."""


def read(ctx):
    spent = ctx.reduced.layer_s.get("uda", 0.0)
    ideal = 0.0
    for kind, shape in ctx.work:
        ops, nbytes = ctx.ops_bytes(kind, shape)
        ideal += max(ops / ctx.peaks["flops_per_s"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    if spent <= 0.0 or ideal <= 0.0:
        return None
    return 100.0 * ideal / spent
