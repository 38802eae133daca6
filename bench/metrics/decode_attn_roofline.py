"""decode_attn_roofline: least time the chip needs for the decode
attention of the window's requests (`counts.decode_attn_need`: each
resident slot's valid compressed K/V, scales and ring, plus q and out)
over the summed device time of the decode attention kernel
(`decode_attn_pallas` / `decode_attn_paged_pallas` in "XLA Ops")."""
import counts
import tracing

KERNEL = r"decode_attn(_paged)?_pallas(\.\d+)?$"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    t, n = tracing.summed_ns(ctx.trace["devices"][0]["ops"], KERNEL,
                             *ctx.span)
    if not n:
        return None
    from harness import counts_shape
    f, b = counts.decode_attn_need(ctx.counts_reqs(),
                                   counts_shape(ctx.cell))
    return counts.roofline_share(f, b, t / 1e9, ctx.peaks)[0]
