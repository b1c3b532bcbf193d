"""The MIS-2 fixed point's share of its HBM roofline: the least bytes of
the Alg. 1 schedule on this graph (``bench.data.ref_mis2``) over the
device time of the fixed-point programs in the trace, against the
chip's peak HBM bandwidth.  The count is a lower bound for any engine,
so the share cannot pass 100%."""


def read(ctx):
    if ctx.trace is None or not ctx.least_bytes or not ctx.peaks:
        return None
    device_s = sum(s for name, s in ctx.trace["modules"].items()
                   if "fixed_point" in name)
    if device_s <= 0:
        return None
    return 100.0 * ctx.least_bytes / (device_s
                                      * ctx.peaks["hbm_bytes_per_s"])
